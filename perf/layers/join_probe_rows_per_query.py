"""Live probe rows a query sent through its joins, of every kind
(``join.probe_rows``, of which ``join.noninner.probe_rows`` is the part
that is not inner), summed over the query's tasks. 0 where no join runs; a
program without the counter gives ``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["join.probe_rows"])
