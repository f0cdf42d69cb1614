"""Milliseconds per query spent persisting plan hints at task ends
(``phase.task.hints_save.seconds``): fingerprint, read-merge-write of
``plan_hints.json``. Grows with the file, which is why an aged one slows
every cell (``PERF.md`` §6)."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["phase.task.hints_save.seconds"], 1e3)
