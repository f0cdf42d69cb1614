"""Serving fast path: single-stage bypass + batched task grants
(docs/serving.md) and the q15 warm-pass determinism fix.

Unit coverage of the batched ``assign_next_tasks`` seam and the
executor's job-scoped strategy snapshot; direct-servicer coverage of
the PollWork grant-batching compat matrix (legacy ``free_slots == 0``
executors still get exactly one task through the singular field);
standalone-cluster acceptance that a bypassed job preserves the full
JobInfo/history/cost contract, that retries stay bounded, and — the
ROADMAP FIRST item — that q15 returns its 1 row on EVERY warm pass,
not just the cold one.
"""

import time

import pyarrow as pa
import pytest

from ballista_tpu.config import BallistaConfig

# ---------------------------------------------------------------------------
# unit: batched assignment seam
# ---------------------------------------------------------------------------


def test_assign_next_tasks_grants_up_to_n_distinct():
    from ballista_tpu.scheduler.stage_manager import StageManager

    sm = StageManager()
    sm.add_running_stage("j", 1, 6)
    sm.add_final_stage("j", 1)
    batch = sm.assign_next_tasks("e1", max_n=4)
    assert len(batch) == 4
    assert sorted(p[2] for p in batch) == [0, 1, 2, 3]
    # drains to exhaustion without over-granting
    rest = sm.assign_next_tasks("e1", max_n=4)
    assert sorted(p[2] for p in rest) == [4, 5]
    assert sm.assign_next_tasks("e1", max_n=4) == []


def test_assign_next_tasks_max_n_one_matches_single():
    from ballista_tpu.scheduler.stage_manager import StageManager

    sm = StageManager()
    sm.add_running_stage("j", 1, 2)
    sm.add_final_stage("j", 1)
    one = sm.assign_next_tasks("e1", max_n=1)
    assert len(one) == 1


# ---------------------------------------------------------------------------
# unit: executor job-scoped strategy snapshot (the q15 drift fix)
# ---------------------------------------------------------------------------


def test_job_snapshot_freezes_strategies_within_a_job():
    """Every task of one job must fold under the SAME strategy base:
    commits from task N (self._plan_cache.update) may not leak into
    task N+1 of the same job — that cross-task adoption is exactly the
    q15 warm-pass fold-order drift (ROADMAP FIRST item)."""
    from ballista_tpu.executor.executor import Executor

    ex = Executor.__new__(Executor)
    from ballista_tpu.analysis.witness import make_lock
    import collections

    ex._plan_cache = {"k1": "cold"}
    ex._snapshot_lock = make_lock("Executor._snapshot_lock")
    ex._job_snapshots = collections.OrderedDict()

    snap_a = ex._job_snapshot("jobA")
    assert snap_a == {"k1": "cold"}
    # a task of jobA commits a freshly-learned strategy
    ex._plan_cache["k2"] = "learned-mid-job"
    ex._plan_cache["k1"] = "remeasured"
    # the NEXT task of jobA still sees the frozen base
    assert ex._job_snapshot("jobA") == {"k1": "cold"}
    assert "k2" not in ex._job_snapshot("jobA")
    # a future job adopts the committed strategies
    snap_b = ex._job_snapshot("jobB")
    assert snap_b == {"k1": "remeasured", "k2": "learned-mid-job"}


def test_job_snapshot_retention_bounded():
    from ballista_tpu.executor.executor import Executor
    from ballista_tpu.analysis.witness import make_lock
    import collections

    ex = Executor.__new__(Executor)
    ex._plan_cache = {}
    ex._snapshot_lock = make_lock("Executor._snapshot_lock")
    ex._job_snapshots = collections.OrderedDict()
    for i in range(200):
        ex._job_snapshot(f"job{i}")
    assert len(ex._job_snapshots) <= 64
    # FIFO: the oldest jobs aged out, the newest survive
    assert "job199" in ex._job_snapshots
    assert "job0" not in ex._job_snapshots


# ---------------------------------------------------------------------------
# direct servicer: the PollWork grant-batching compat matrix
# ---------------------------------------------------------------------------


def _direct_scheduler(batch="4", partitions="4"):
    from ballista_tpu.exec.context import TpuContext
    from ballista_tpu.scheduler.server import SchedulerServer

    ctx = TpuContext()
    ctx.register_table(
        "t",
        pa.table(
            {"k": [i % 7 for i in range(2000)],
             "v": [float(i) for i in range(2000)]}
        ),
    )
    cfg = (
        BallistaConfig()
        .with_setting("ballista.shuffle.partitions", partitions)
        .with_setting("ballista.tpu.task_grant_batch", batch)
    )
    sched = SchedulerServer(provider=ctx, config=cfg)
    return ctx, sched


def _submit_and_wait_claimable(ctx, sched, n):
    logical = ctx.sql_to_logical(
        "select k, sum(v) as s from t group by k"
    )
    job_id = sched.submit_logical(logical, "s-direct")
    deadline = time.time() + 15
    while time.time() < deadline:
        if sched.stage_manager.inflight_tasks() >= n:
            return job_id
        time.sleep(0.01)
    raise AssertionError("stage tasks never became claimable")


def _poll(sched, free_slots):
    from ballista_tpu.proto import pb
    from ballista_tpu.scheduler.server import SchedulerGrpcServicer

    req = pb.PollWorkParams(
        metadata=pb.ExecutorMetadata(
            id="e-test", host="localhost", port=1, grpc_port=2,
            specification=pb.ExecutorSpecification(
                task_slots=8, n_devices=1
            ),
        ),
        can_accept_task=True,
        free_slots=free_slots,
    )
    return SchedulerGrpcServicer(sched).PollWork(req, None)


def test_pollwork_batches_up_to_min_of_slots_and_knob():
    ctx, sched = _direct_scheduler(batch="4", partitions="4")
    try:
        _submit_and_wait_claimable(ctx, sched, 4)
        r = _poll(sched, free_slots=8)
        # min(free_slots=8, task_grant_batch=4) = 4 grants in ONE
        # round-trip; the first is mirrored into the singular field for
        # pre-batching executors
        assert len(r.tasks) == 4
        assert r.HasField("task")
        assert r.task.task_id.partition_id == r.tasks[0].task_id.partition_id
        parts = [td.task_id.partition_id for td in r.tasks]
        assert len(set(parts)) == 4, parts
    finally:
        sched.shutdown()


def test_pollwork_free_slots_caps_grant():
    ctx, sched = _direct_scheduler(batch="4", partitions="4")
    try:
        _submit_and_wait_claimable(ctx, sched, 4)
        r = _poll(sched, free_slots=2)
        assert len(r.tasks) == 2
    finally:
        sched.shutdown()


def test_pollwork_legacy_executor_gets_exactly_one():
    """``free_slots == 0`` is a pre-batching executor: it must get at
    most ONE task, delivered through the singular ``task`` field it
    reads."""
    ctx, sched = _direct_scheduler(batch="4", partitions="4")
    try:
        _submit_and_wait_claimable(ctx, sched, 4)
        r = _poll(sched, free_slots=0)
        assert len(r.tasks) == 1
        assert r.HasField("task")
    finally:
        sched.shutdown()


def test_pollwork_batch_knob_one_serializes_grants():
    ctx, sched = _direct_scheduler(batch="1", partitions="4")
    try:
        _submit_and_wait_claimable(ctx, sched, 4)
        r = _poll(sched, free_slots=8)
        assert len(r.tasks) == 1
    finally:
        sched.shutdown()


# ---------------------------------------------------------------------------
# acceptance: single-stage bypass on a standalone cluster
# ---------------------------------------------------------------------------


def _standalone(data, **settings):
    from ballista_tpu.client.context import BallistaContext

    cfg = BallistaConfig().with_setting("ballista.shuffle.partitions", "1")
    for k, v in settings.items():
        cfg = cfg.with_setting(k.replace("__", "."), v)
    ctx = BallistaContext.standalone(cfg)
    for name, t in data.items():
        ctx.register_table(name, t)
    return ctx


def _small_table():
    return pa.table(
        {"a": list(range(100)), "b": [float(i) for i in range(100)]}
    )


def test_bypass_serves_single_stage_with_full_job_parity():
    ctx = _standalone({"t": _small_table()})
    sched = ctx._standalone_cluster.scheduler
    try:
        r = ctx.sql("select a, b from t where a < 10").collect()
        assert r.num_rows == 10
        assert sched.obs_bypass_total == 1
        with sched._lock:
            job = max(sched.jobs.values(), key=lambda j: j.submitted_s)
        assert job.bypass and job.status == "completed"
        # observability/charging parity with the stage-managed path:
        # cost vector ingested, query class assigned, completed
        # locations recorded, history terminal record present
        deadline = time.time() + 5
        while time.time() < deadline and job.cost is None:
            time.sleep(0.02)
        assert job.cost is not None and job.cost.wall_seconds > 0
        assert job.query_class
        assert job.completed_locations
        recs = [
            rec for rec in sched.history.jobs()
            if rec["job_id"] == job.job_id
        ]
        assert recs and recs[0]["status"] == "completed"
    finally:
        ctx.close()


def test_bypass_knob_off_routes_through_stage_manager():
    ctx = _standalone(
        {"t": _small_table()}, ballista__tpu__single_stage_bypass="false"
    )
    sched = ctx._standalone_cluster.scheduler
    try:
        r = ctx.sql("select a, b from t where a < 10").collect()
        assert r.num_rows == 10
        assert sched.obs_bypass_total == 0
        with sched._lock:
            job = max(sched.jobs.values(), key=lambda j: j.submitted_s)
        assert not job.bypass
    finally:
        ctx.close()


def test_bypass_multi_partition_plans_not_eligible():
    """More than one input partition means real orchestration work —
    the bypass must stand aside."""
    ctx = _standalone(
        {"t": _small_table()}, **{"ballista.shuffle.partitions": "2"}
    )
    sched = ctx._standalone_cluster.scheduler
    try:
        r = ctx.sql("select a, b from t where a < 10").collect()
        assert r.num_rows == 10
        assert sched.obs_bypass_total == 0
    finally:
        ctx.close()


def test_bypass_retry_recovers_injected_crash():
    from ballista_tpu.testing import faults

    faults.install(
        [{"point": "task_crash", "partition": 0, "attempt": 0,
          "max_fires": 1}]
    )
    try:
        ctx = _standalone({"t": _small_table()})
        sched = ctx._standalone_cluster.scheduler
        try:
            r = ctx.sql("select a from t where a < 5").collect()
            assert r.num_rows == 5
            assert sched.obs_bypass_total == 1
            with sched._lock:
                job = max(
                    sched.jobs.values(), key=lambda j: j.submitted_s
                )
            assert job.status == "completed"
            assert job.total_retries >= 1
        finally:
            ctx.close()
    finally:
        faults.install(None)


def test_bypass_retry_exhaustion_fails_job():
    from ballista_tpu.errors import BallistaError
    from ballista_tpu.testing import faults

    faults.install([{"point": "task_crash", "partition": 0}])
    try:
        ctx = _standalone(
            {"t": _small_table()},
            ballista__tpu__task_max_attempts="1",
        )
        sched = ctx._standalone_cluster.scheduler
        try:
            with pytest.raises(BallistaError, match="injected task crash"):
                ctx.sql("select a from t where a < 5").collect()
            with sched._lock:
                job = max(
                    sched.jobs.values(), key=lambda j: j.submitted_s
                )
            assert job.status == "failed" and job.bypass
            assert "injected task crash" in job.error
            assert job.total_retries == 0
        finally:
            ctx.close()
    finally:
        faults.install(None)


# ---------------------------------------------------------------------------
# the ROADMAP FIRST item: q15 warm-pass determinism
# ---------------------------------------------------------------------------


def test_q15_every_warm_pass_returns_its_row():
    """q15 filters on ``total_revenue = (select max(...))`` — a float
    equality that last-ULP fold drift between the two structurally-
    identical revenue branches turns into a silently EMPTY result. At
    HEAD before the job-scoped strategy snapshot this returned 1 row
    cold and then 0 rows on warm passes (clean runs yielded 1,1,0,0,0):
    the executor-lifetime plan cache let task N's freshly-committed
    strategies change task N+1's fold order WITHIN one job. Six passes,
    one row EVERY time, with the replay witness asserting zero content-
    hash mismatches across every shuffle of every pass."""
    import pathlib

    from ballista_tpu.analysis import replay
    from ballista_tpu.tpch import gen_all

    sql = (
        pathlib.Path(__file__).resolve().parent.parent
        / "benchmarks/queries/q15.sql"
    ).read_text()
    data = gen_all(scale=0.01)
    ctx = _standalone(data, **{"ballista.shuffle.partitions": "4"})
    replay.enable()
    try:
        rows = []
        for _ in range(6):
            rows.append(ctx.sql(sql).collect().num_rows)
        assert rows == [1] * 6, (
            f"q15 warm-pass drift is back: row counts {rows}"
        )
        replay.assert_clean()
    finally:
        replay.enable(False)
        replay.reset()
        ctx.close()


# ---------------------------------------------------------------------------
# plan hints leave the task (docs/compile_cache.md)
# ---------------------------------------------------------------------------


def test_plan_hints_are_marked_by_tasks_and_written_by_the_store(
    tmp_path, monkeypatch
):
    """A finished task only marks the executor's hint store. What the
    store's writer persists holds no entry keyed by a job id, so a second
    run of the same query, which learns nothing a later process could
    read, writes nothing; and stop() leaves no writer thread."""
    import threading

    import numpy as np

    from ballista_tpu.compilecache import hints, metrics

    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", str(tmp_path))
    monkeypatch.setattr(hints, "WRITE_DEBOUNCE_S", 0.05)
    rng = np.random.default_rng(32)
    n = 6000
    data = {
        "t": pa.table({"k": rng.integers(0, 40, n),
                       "v": rng.uniform(0, 100, n).round(2)}),
        "d": pa.table({"id": np.arange(40, dtype=np.int64),
                       "grp": np.arange(40, dtype=np.int64) % 5}),
    }
    sql = (
        "SELECT grp, SUM(v) AS sv FROM t JOIN d ON k = id "
        "GROUP BY grp ORDER BY grp"
    )
    ctx = _standalone(data, **{"ballista.shuffle.partitions": "2"})
    cluster = ctx._standalone_cluster
    executor = cluster.executors[0].executor
    path = tmp_path / "plan_hints.json"
    try:
        with metrics.delta() as first:
            ctx.sql(sql).collect()
            executor._hints.flush()
        assert first.value["hints_saved"] >= 1
        with metrics.delta() as second:
            again = ctx.sql(sql).collect()
            executor._hints.flush()
        assert again.num_rows == 5
        jobs = sorted(cluster.scheduler.jobs)
        assert len(jobs) == 2
        # in memory the jobs' entries are there for their later tasks ...
        assert any(jobs[0] in repr(k) for k in executor._plan_cache)
        assert any(jobs[1] in repr(k) for k in executor._plan_cache)
        # ... and the file has neither's
        written = path.read_text(encoding="utf-8")
        assert jobs[0] not in written and jobs[1] not in written
        assert second.value.get("hints_saved", 0) == 0
        assert second.value["hints.writes_skipped_unchanged"] >= 1
        # the phase is still every task's, and now the mark
        tasks = second.value["phase.task.hints_save.count"]
        assert tasks >= 3 and second.value["hints.marks"] == tasks
    finally:
        ctx.close()
    assert not [
        t for t in threading.enumerate()
        if t.name == hints.WRITER_THREAD_NAME and t.is_alive()
    ]
