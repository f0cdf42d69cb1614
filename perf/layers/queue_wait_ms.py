"""Mean time from a job's submission to its first task assignment
(``queue_wait_s`` of ``system.queries``) over the window's jobs."""

from layers._history import window_jobs


def read(obs):
    jobs = window_jobs(obs)
    if jobs is None:
        return None
    return 1e3 * sum(float(j["queue_wait_s"]) for j in jobs) / len(jobs)
