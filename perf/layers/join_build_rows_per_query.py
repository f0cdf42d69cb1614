"""Live rows a query's joins built into probe tables (``join.build_rows``:
the live rows of every ``build_side`` a ``HashJoinExec`` made, rebuilds
after a dictionary remap included, summed over the query's tasks). 0 where
no join runs; a program without the counter gives ``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["join.build_rows"])
