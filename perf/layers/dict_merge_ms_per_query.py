"""Milliseconds per query of host dictionary algebra
(``phase.task.dict_merge.seconds``): string columns to sorted dictionaries
and codes and back at a scan, a stage boundary or the result, and the merging
of dictionaries that differ, all of it Python or Arrow work per dictionary
entry. It lies inside a task's ``wall_seconds`` and inside
``task_unnamed_ms_per_query``, whose reader does not take it out."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["phase.task.dict_merge.seconds"], 1e3)
