"""Live probe rows a query sent through ``LEFT``, ``SEMI`` and ``ANTI`` joins
(``join.noninner.probe_rows``: the preserved side's rows, summed over the
query's tasks). 0 where every join is inner; a program without the counter
gives ``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["join.noninner.probe_rows"])
