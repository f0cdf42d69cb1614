"""Tasks a query ran whose decorrelated subquery aggregate grouped an input
cut to the outer query's keys (``subquery.agg_reduced``: +1 a task whose
marked partial aggregate stood on the semi-join reduction by the outer
query's key domain). 0 where no such subquery runs, or where none was
reduced; a program without the counter gives ``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["subquery.agg_reduced"])
