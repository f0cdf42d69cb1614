"""Live rows a query sent into the aggregates that decorrelate a scalar
subquery (``subquery.agg_rows``: the rows into the partial aggregate that
the planner introduced for ``WHERE x < (SELECT agg ... WHERE inner =
outer)``, after the scan's pushed filters, summed over the query's tasks).
0 where no such subquery runs; a program without the counter gives
``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["subquery.agg_rows"])
