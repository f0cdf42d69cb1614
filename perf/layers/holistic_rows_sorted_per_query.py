"""Live rows a query's window and percentile operators put through a sort
(``holistic.rows_sorted``: + the live rows of every sort a ``WindowExec`` or
``PercentileExec`` dispatches), counted on the host where the operator
dispatches it. 0 in a cell without such a query; a program without the
counter gives ``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["holistic.rows_sorted"])
