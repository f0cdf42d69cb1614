"""What is left of ``task_wall_ms`` once the named phases inside a task are
taken out: per query, the summed ``wall_seconds`` of its attempts minus the
``task.*`` phases that the interval brackets (scan decode, host-to-device,
device-to-host, shuffle write and fetch, hint save; ``task.decode`` and
``task.report`` lie outside it). The remainder is operator Python, tracing
and dispatch: what no phase names yet."""

from layers._history import attempt_cost, window_jobs
from layers._phases import delta

INSIDE_WALL = ("task.scan_host", "task.h2d", "task.d2h",
               "task.shuffle_write", "task.shuffle_fetch", "task.hints_save")


def read(obs):
    jobs = window_jobs(obs)
    if jobs is None or not obs["attempts"]:
        return None
    named = [delta(obs, f"phase.{p}.seconds") for p in INSIDE_WALL]
    if all(d is None for d in named):
        return None
    wall = attempt_cost(obs, jobs, "wall_seconds")
    if wall <= 0:
        return None
    return 1e3 * (wall - sum(d for d in named if d is not None)) / len(jobs)
