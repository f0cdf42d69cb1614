"""Groups a query's decorrelated subquery aggregates emitted
(``subquery.agg_groups``: the rows of their final aggregates, one a
correlation key): what the joins above them are handed. 0 where no such
subquery runs; a program without the counter gives ``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["subquery.agg_groups"])
