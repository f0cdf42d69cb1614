"""Probe batches of a query whose dictionary unification changed the
build side of a join on a string key (``join.key_remaps``: each one a
rebuild of the probe table), summed over the query's tasks. 0 where no join
runs on a string key; a program without the counter gives ``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["join.key_remaps"])
