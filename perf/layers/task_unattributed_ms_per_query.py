"""What of ``task_wall_ms`` still has no owner: per query, the summed
``wall_seconds`` of its attempts minus the eight ``task.*`` phases inside
that interval (the six that ``task_unnamed_ms_per_query`` subtracts, and
``task.dict_merge`` and ``task.dict_predicate``) minus the operators' own
time (``operator_self_ms_per_query``). The task runner's own code, the
resolution of the operators' metrics and an attempt thrown away are in it;
phases on a task's helper threads (a scan prefetch worker) that overlap the
task thread are subtracted too, so it is signed."""

from layers._history import attempt_cost, window_jobs
from layers._operators import self_seconds
from layers._phases import delta
from layers.task_unnamed_ms_per_query import INSIDE_WALL

PHASES = INSIDE_WALL + ("task.dict_merge", "task.dict_predicate")


def read(obs):
    jobs = window_jobs(obs)
    if jobs is None or not obs["attempts"]:
        return None
    own = self_seconds(obs)
    if own is None:
        return None
    wall = attempt_cost(obs, jobs, "wall_seconds")
    if wall <= 0:
        return None
    named = sum(d for d in (delta(obs, f"phase.{p}.seconds") for p in PHASES)
                if d is not None)
    return 1e3 * (wall - named - own) / len(jobs)
