"""Milliseconds per query that operators spent in their own code on task
threads (the sum of ``op.*.self_seconds``): Python, tracing and the dispatch
of their programs, the metering of their batches included; not their
inputs' time and not a host phase's. Lies inside ``task_wall_ms``, and
inside what ``task_unnamed_ms_per_query`` leaves."""

from layers._operators import ms_per_query


def read(obs):
    return ms_per_query(obs)
