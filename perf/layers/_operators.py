"""The operators' own time over the window (``ballista_tpu/obs/trace.py
stretch``: each operator's ``self_s`` on its task thread, without its inputs
and without any host phase, summed by the executor into
``op.<family>.self_seconds`` once a task), in milliseconds per query of the
window's jobs, as ``task_unnamed_ms_per_query`` divides, so that the numbers
add up. The program declares the counters at 0; a program without them gives
``None`` and the metric is left out."""

from layers._history import window_jobs
from layers._phases import delta

FAMILIES = ("scan", "pipeline", "aggregate", "join", "holistic", "exchange",
            "other")


def self_seconds(obs, families=FAMILIES):
    """The window's summed ``op.<family>.self_seconds`` of ``families``, or
    ``None`` if the program has none of them."""
    found = [d for d in (delta(obs, f"op.{f}.self_seconds") for f in families)
             if d is not None]
    return sum(found) if found else None


def ms_per_query(obs, families=FAMILIES):
    jobs = window_jobs(obs)
    seconds = self_seconds(obs, families)
    if jobs is None or seconds is None:
        return None
    return 1e3 * seconds / len(jobs)
