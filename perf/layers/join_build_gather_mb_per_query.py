"""Megabytes a query's join builds gathered through their sort's
permutation (``join.build_gather_bytes``: each ``build_side`` a
``HashJoinExec`` made, the sorted keys' static capacity x itemsize, summed
over the query's tasks; the payload stays in arrival order and is gathered
by the probe). 0 where no join runs; a program without the counter gives
``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["join.build_gather_bytes"], 1e-6)
