"""Milliseconds per query that the plan-hint store's writer thread spent
persisting (``phase.executor.hints_write.seconds``: a pass is the
fingerprint of what a later process could read and, only if it moved, the
read-merge-write of ``plan_hints.json``). The work ``task.hints_save``
bracketed at every task's end until PR 32, now off the task's path, once a
debounce interval and beside the tasks; what a task still pays is
``hints_save_ms_per_query``. A program from before PR 32 has no such
counter: ``None``, the metric left out."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["phase.executor.hints_write.seconds"], 1e3)
