"""Per query, the summed ``wall_seconds`` of its task attempts
(``system.task_attempts``), averaged over the window's jobs. A job's latency
minus this is what the control plane adds between tasks."""

from layers._history import attempt_cost, window_jobs


def read(obs):
    jobs = window_jobs(obs)
    if jobs is None or not obs["attempts"]:
        return None
    wall = attempt_cost(obs, jobs, "wall_seconds")
    if wall <= 0:
        return None
    return 1e3 * wall / len(jobs)
