"""Milliseconds per query that finished tasks' statuses sat in the executor's
queue before a poll took them to the scheduler
(``phase.executor.status_wait.seconds``: stamped where the task thread
queues the status, added up where the poll loop drains it). Every stage ends
with such a wait, and the next stage cannot be granted before it is over.
A program from before PR 30 has no such counter: ``None``, the metric left
out."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["phase.executor.status_wait.seconds"], 1e3)
