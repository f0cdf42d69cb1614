"""Blocking device-to-host reads the executor made per query
(``phase.task.d2h.count``): each is a round trip that stalls a task thread,
and the list by call site (``...count:<site>``) is in the counters too."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["phase.task.d2h.count"])
