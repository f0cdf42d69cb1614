"""Probe tables a query's joins built (``join.builds``: +1 for every
``build_side`` a ``HashJoinExec`` made, the strategy's decision build and
rebuilds after a dictionary remap included, summed over the query's tasks).
0 where no join runs; a program without the counter gives ``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["join.builds"])
