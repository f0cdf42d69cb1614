"""Shuffle bytes the window's task attempts wrote to disk, per query, in MB
(``shuffle_write_bytes`` of ``system.task_attempts``): the stage boundaries'
cost in I/O, and what a run writes to the host's disk."""

from layers._history import attempt_cost, window_jobs


def read(obs):
    jobs = window_jobs(obs)
    if jobs is None or not obs["attempts"]:
        return None
    wrote = attempt_cost(obs, jobs, "shuffle_write_bytes")
    if wrote <= 0:
        return None
    return wrote / 1e6 / len(jobs)
