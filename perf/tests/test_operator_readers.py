"""The readers of the operators' own time (``op.<family>.self_seconds``):
``None`` on a program without the counters, as the parent commit's, and per
query of the window's jobs on a hand-made ``obs``, as
``test_phase_labels.py`` does for the phase readers."""

import importlib

import pytest

READERS = [
    "operator_self_ms_per_query", "agg_self_ms_per_query",
    "join_self_ms_per_query", "holistic_self_ms_per_query",
    "task_unattributed_ms_per_query",
]
FAMILIES = ("scan", "pipeline", "aggregate", "join", "holistic", "exchange",
            "other")


def obs(before, after):
    """Two completed jobs of the window (and a failed query), their
    attempts' ``wall_seconds`` 1.5 and 0.5."""
    job = {"job_id": "j", "status": "completed", "session_id": "s",
           "submitted_s": 5.0}
    return {
        "queries": [{"error": None, "t0": 5.0, "t1": 6.0},
                    {"error": None, "t0": 6.0, "t1": 7.0},
                    {"error": "boom", "t0": 7.0, "t1": 8.0}],
        "jobs": [job, dict(job, job_id="k")], "session_id": "s",
        "window_t0": 0.0, "window_t1": 10.0,
        "attempts": [{"job_id": "j", "cost": {"wall_seconds": 1.5}},
                     {"job_id": "k", "cost": {"wall_seconds": 0.5}}],
        "counters_before": before, "counters_after": after,
    }


DECLARED = {f"op.{f}.self_seconds": 0.0 for f in FAMILIES}
PARENT = {"backend_compiles": 3.0, "phase.task.d2h.seconds": 1.0,
          "agg.sort_passes": 4}


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_on_the_parents_counters(name):
    reader = importlib.import_module(f"layers.{name}")
    assert reader.read(obs(PARENT, dict(PARENT,
                                        **{"phase.task.d2h.seconds": 1.4})))\
        is None
    assert reader.read(obs(None, None)) is None


@pytest.mark.parametrize("name", READERS[:4])
def test_declared_and_unmoved_reads_zero(name):
    reader = importlib.import_module(f"layers.{name}")
    assert reader.read(obs(DECLARED, dict(DECLARED))) == 0.0


def test_the_families_per_query_of_the_window_jobs():
    after = dict(DECLARED, **{
        "op.scan.self_seconds": 0.10, "op.aggregate.self_seconds": 0.30,
        "op.join.self_seconds": 0.20, "op.holistic.self_seconds": 0.04,
        "op.exchange.self_seconds": 0.06,
    })
    before = dict(DECLARED, **{"op.aggregate.self_seconds": 0.10})

    def read(name):
        return importlib.import_module(f"layers.{name}").read(
            obs(before, after))

    # two completed jobs: the failed query divides nothing
    assert read("agg_self_ms_per_query") == pytest.approx(100.0)
    assert read("join_self_ms_per_query") == pytest.approx(100.0)
    assert read("holistic_self_ms_per_query") == pytest.approx(20.0)
    assert read("operator_self_ms_per_query") == pytest.approx(300.0)


def test_unattributed_is_the_wall_less_eight_phases_and_operators():
    phases = {f"phase.task.{p}.seconds": 0.1 for p in (
        "scan_host", "h2d", "d2h", "shuffle_write", "shuffle_fetch",
        "hints_save", "dict_merge", "dict_predicate")}
    # outside wall_seconds: not subtracted
    phases["phase.task.decode.seconds"] = 0.5
    phases["phase.executor.poll_sleep.seconds"] = 9.0
    after = dict(DECLARED, **phases, **{"op.join.self_seconds": 0.6})
    read = importlib.import_module("layers.task_unattributed_ms_per_query")
    # (2.0 s of wall - 0.8 of phases - 0.6 of operators) / 2 jobs
    assert read.read(obs(dict(DECLARED), after)) == pytest.approx(300.0)
    # signed: helper threads' phases can overlap the task thread
    after["op.join.self_seconds"] = 1.6
    assert read.read(obs(dict(DECLARED), after)) == pytest.approx(-200.0)
    # the operators' counters and no phase entered yet
    assert read.read(obs(dict(DECLARED), dict(DECLARED))) == \
        pytest.approx(1000.0)


def test_the_sum_adds_up_with_task_unnamed():
    """``task_unnamed`` less the two dictionary phases, less the operators,
    is ``task_unattributed``: the same wall, the same jobs."""
    phases = {f"phase.task.{p}.seconds": 0.05 for p in (
        "scan_host", "h2d", "d2h", "shuffle_write", "shuffle_fetch",
        "hints_save", "dict_merge", "dict_predicate")}
    after = dict(DECLARED, **phases, **{"op.pipeline.self_seconds": 0.4})

    def read(name):
        return importlib.import_module(f"layers.{name}").read(
            obs(dict(DECLARED), after))

    assert read("task_unnamed_ms_per_query") - 2 * 25.0 \
        - read("operator_self_ms_per_query") == \
        pytest.approx(read("task_unattributed_ms_per_query"))
