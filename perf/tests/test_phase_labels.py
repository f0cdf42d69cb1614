"""What the program's ``ballista/<phase>`` annotations (PR 25) do to the idle
table, on planes made by hand, and that each reader of a ``phase.*`` counter
gives nothing on counters that lack its key, as the parent commit's do."""

import importlib

import pytest

import reduce_trace

MS = 1_000_000
D2H = "np.asarray(jax.Array)"

PHASE_READERS = [
    "d2h_reads_per_query", "d2h_wait_ms_per_query", "h2d_mb_per_query",
    "shuffle_io_ms_per_query", "executor_poll_sleep_ms_per_query",
    "hints_save_ms_per_query", "sched_plan_ms_per_query",
    "task_unnamed_ms_per_query",
]


def planes(host_lines):
    """One device whose program runs 0-10 ms, 110-120 ms and 330-340 ms: an
    idle gap of 100 ms and one of 210 ms for the host lines to explain."""
    ops = [(0, 10 * MS, "%fusion.1 = f32[8]"),
           (110 * MS, 10 * MS, "%fusion.1 = f32[8]"),
           (330 * MS, 10 * MS, "%fusion.1 = f32[8]")]
    modules = [(s, d, "jit_join_probe(123)") for s, d, _ in ops]
    return [
        ("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", modules)]),
        ("/host:CPU", [(f"python3-{i}", ev)
                       for i, ev in enumerate(host_lines)]),
    ]


def idle(host_lines):
    out = reduce_trace.reduce_planes(planes(host_lines), 0.34)
    return dict(out["breakdown"]["idle_gaps"]), out


def test_d2h_phase_takes_the_label_from_the_bare_read():
    """The phase brackets the blocking call tightly, so its span starts a
    hair before JAX's own ``np.asarray`` event: both cover the gap, and the
    earlier start takes it."""
    bare, _ = idle([[(12 * MS, 96 * MS, D2H)]])
    assert bare == {D2H: pytest.approx(0.1), reduce_trace.UNTRACED:
                    pytest.approx(0.21)}
    named, out = idle([[
        (12 * MS - 2_000, 96 * MS + 4_000, "ballista/task.d2h:shrink.count"),
        (12 * MS, 96 * MS, D2H),
    ]])
    assert named == {"ballista/task.d2h:shrink.count": pytest.approx(0.1),
                     reduce_trace.UNTRACED: pytest.approx(0.21)}
    assert D2H not in named
    assert dict(out["breakdown"]["device_ops"])[
        "program jit_join_probe"] == pytest.approx(0.03)


def test_poll_sleep_names_a_gap_nothing_else_covers():
    """The executor asleep between grants, on its own thread, while a short
    traced event elsewhere covers under a fifth of the gap."""
    named, _ = idle([
        [(125 * MS, 100 * MS, "ballista/executor.poll_sleep"),
         (226 * MS, 100 * MS, "ballista/executor.poll_sleep")],
        [(130 * MS, 5 * MS, "PjitFunction(join_probe)")],
    ])
    assert named == {
        "ballista/executor.poll_sleep": pytest.approx(0.21),
        reduce_trace.UNTRACED: pytest.approx(0.1),
    }


def test_a_working_span_loses_to_a_longer_sleep():
    """The labeller's known weakness (PERF.md §7): a sleep that covers more
    of a gap than the thread that was working in it takes the label."""
    named, _ = idle([
        [(120 * MS, 205 * MS, "ballista/executor.poll_sleep")],
        [(121 * MS, 150 * MS, "ballista/scheduler.plan")],
    ])
    assert "ballista/scheduler.plan" not in named
    assert named["ballista/executor.poll_sleep"] == pytest.approx(0.21)


@pytest.mark.parametrize("name", PHASE_READERS)
def test_reader_returns_none_without_its_counter(name):
    reader = importlib.import_module(f"layers.{name}")
    job = {"job_id": "j", "status": "completed", "session_id": "s",
           "submitted_s": 5.0}
    obs = {
        "queries": [{"error": None, "t0": 5.0, "t1": 6.0}],
        "jobs": [job], "session_id": "s", "window_t0": 0.0,
        "window_t1": 10.0,
        "attempts": [{"job_id": "j", "cost": {"wall_seconds": 1.5}}],
        # the parent's counters: compile counters only
        "counters_before": {"backend_compiles": 3.0},
        "counters_after": {"backend_compiles": 3.0},
    }
    assert reader.read(obs) is None
    obs["counters_before"] = obs["counters_after"] = None
    assert reader.read(obs) is None


def test_readers_read_the_window_delta_per_query():
    def obs(after):
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
            "counters_before": {"phase.task.d2h.count": 10.0,
                                "phase.task.d2h.seconds": 1.0},
            "counters_after": after,
        }

    after = {"phase.task.d2h.count": 50.0, "phase.task.d2h.seconds": 1.5,
             "phase.task.h2d.bytes": 4e6,
             "phase.task.shuffle_write.seconds": 0.2,
             "phase.task.hints_save.seconds": 0.1}
    read = {n: importlib.import_module(f"layers.{n}").read(obs(after))
            for n in PHASE_READERS}
    assert read["d2h_reads_per_query"] == 20.0
    assert read["d2h_wait_ms_per_query"] == pytest.approx(250.0)
    assert read["h2d_mb_per_query"] == pytest.approx(2.0)
    # the fetch counter is absent: the write alone is read
    assert read["shuffle_io_ms_per_query"] == pytest.approx(100.0)
    assert read["hints_save_ms_per_query"] == pytest.approx(50.0)
    assert read["executor_poll_sleep_ms_per_query"] is None
    assert read["sched_plan_ms_per_query"] is None
    # 2.0 s of wall minus 0.5 + 0.2 + 0.1 s of named phases, over 2 jobs
    assert read["task_unnamed_ms_per_query"] == pytest.approx(600.0)
