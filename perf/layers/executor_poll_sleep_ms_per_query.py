"""Milliseconds per query the executor's poll loop slept because the
scheduler granted it nothing (``phase.executor.poll_sleep.seconds``): with
one client that is most of the time between a stage's end and the next
grant, and an upper bound on what a push or a shorter poll could win."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["phase.executor.poll_sleep.seconds"], 1e3)
