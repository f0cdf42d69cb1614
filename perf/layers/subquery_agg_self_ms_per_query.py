"""Milliseconds per query that task threads spent in the own code of the
aggregates that decorrelate a scalar subquery
(``subquery.agg_self_seconds``: their ``self_s``, which
``agg_self_ms_per_query`` counts too), divided as that metric divides. 0
where no such subquery runs; a program without the counter gives
``None``."""

from layers._history import window_jobs
from layers._phases import delta


def read(obs):
    jobs = window_jobs(obs)
    seconds = delta(obs, "subquery.agg_self_seconds")
    if jobs is None or seconds is None:
        return None
    return 1e3 * seconds / len(jobs)
