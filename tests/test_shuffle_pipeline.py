"""Streaming pipelined shuffle: overlapped fetch, eager publication,
compression, and data-plane hardening (ISSUE 6, docs/shuffle.md).

Covers the tier-1 (fast, in-process) surface:
- Flight `do_get` path containment: tickets escaping the executor's
  shuffle root are rejected with a typed Flight error.
- Mixed compressed/uncompressed files inside ONE consumed partition (the
  rolling-upgrade shape), zero-row upstream outputs, and an _IpcAppender
  that closes with no batches written.
- Overlapped fetch (shuffle_fetch_concurrency > 1) yields the exact
  sequential stream — same rows, same order — and raises a location's
  fetch error at the same position the sequential loop would.
- Eager reader semantics against a scripted location feed: map-task
  ordered consumption, wait-for-unpublished, terminal failure, deadline.
- The producer-kill-mid-stream fault point.
- Serde round-trip (byte-stable) for eager reader plans.

The chaos-scale eager test (2-executor cluster, producer killed after
consumers streamed part of its output) lives in test_chaos_eager.py.
"""

import dataclasses
import os

import numpy as np
import pyarrow as pa
import pyarrow.flight as paflight
import pyarrow.ipc as paipc
import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.datatypes import DataType, Field, Schema
from ballista_tpu.errors import ShuffleFetchError
from ballista_tpu.exec.base import TaskContext
from ballista_tpu.executor.reader import (
    ShuffleLocationsView,
    ShuffleReaderExec,
    fetch_partition_table,
)
from ballista_tpu.executor.shuffle import _IpcAppender
from ballista_tpu.scheduler_types import PartitionLocation

SCHEMA2 = Schema([Field("k", DataType.INT64), Field("v", DataType.FLOAT64)])
ARROW2 = pa.schema([("k", pa.int64()), ("v", pa.float64())])


def _write_file(path, start, rows, codec=None, n_batches=1):
    opts = paipc.IpcWriteOptions(compression=codec) if codec else None
    kw = {"options": opts} if opts is not None else {}
    with paipc.new_file(path, ARROW2, **kw) as w:
        for b in range(n_batches):
            lo = start + b * rows
            w.write_batch(
                pa.record_batch(
                    [
                        pa.array(np.arange(lo, lo + rows, dtype=np.int64)),
                        pa.array(np.arange(lo, lo + rows, dtype=np.float64)),
                    ],
                    schema=ARROW2,
                )
            )


def _loc(path, partition=0, executor_id="e1", host="127.0.0.1", port=0):
    return PartitionLocation(
        job_id="job", stage_id=1, partition=partition,
        executor_id=executor_id, host=host, port=port, path=path,
    )


def _collect_keys(plan, ctx, partition=0):
    out = []
    for b in plan.execute(partition, ctx):
        valid = np.asarray(b.valid)
        out.append(np.asarray(b.columns[0])[valid])
    return np.concatenate(out) if out else np.array([], dtype=np.int64)


def _ctx(**settings):
    cfg = BallistaConfig()
    for k, v in settings.items():
        cfg = cfg.with_setting(k, v)
    return TaskContext(config=cfg)


# ---------------------------------------------------------------------------
# satellite: path containment in BallistaFlightService.do_get
# ---------------------------------------------------------------------------


def test_flight_do_get_path_containment(tmp_path):
    from ballista_tpu.client.flight import close_pool, make_ticket
    from ballista_tpu.executor.flight_service import start_flight_server

    work = tmp_path / "work"
    work.mkdir()
    inside = work / "data-0.arrow"
    _write_file(str(inside), 0, 8)
    outside = tmp_path / "secret.arrow"
    _write_file(str(outside), 100, 8)

    svc, port, _t = start_flight_server("127.0.0.1", 0, str(work))
    try:
        client = paflight.connect(f"grpc://127.0.0.1:{port}")
        # honest ticket: streams fine
        ok = client.do_get(make_ticket(_loc(str(inside)))).read_all()
        assert ok.num_rows == 8
        # escapes via an absolute path outside the root
        with pytest.raises(paflight.FlightServerError, match="escapes"):
            client.do_get(make_ticket(_loc(str(outside)))).read_all()
        # escapes via ../ traversal from inside the root
        sneaky = str(work / ".." / "secret.arrow")
        with pytest.raises(paflight.FlightServerError, match="escapes"):
            client.do_get(make_ticket(_loc(sneaky))).read_all()
        client.close()
    finally:
        close_pool()
        svc.shutdown()


# ---------------------------------------------------------------------------
# satellite: shuffle-file edge cases
# ---------------------------------------------------------------------------


def test_mixed_codecs_in_one_partition(tmp_path):
    """One consumed partition holding none/lz4/zstd files (writers from
    different rollout generations): readers auto-detect per file."""
    paths = []
    for i, codec in enumerate((None, "lz4", "zstd")):
        p = str(tmp_path / f"data-{i}.arrow")
        _write_file(p, i * 10, 10, codec=codec)
        paths.append(p)
    plan = ShuffleReaderExec([[_loc(p) for p in paths]], SCHEMA2)
    keys = _collect_keys(plan, _ctx())
    assert sorted(keys.tolist()) == list(range(30))
    # and the whole-table local path (zero-copy mmap) handles codecs too
    for i, p in enumerate(paths):
        t = fetch_partition_table(_loc(p))
        assert t.column("k").to_pylist() == list(range(i * 10, i * 10 + 10))


@pytest.mark.parametrize("push", [False, True], ids=["pull", "push"])
@pytest.mark.parametrize("on_disk", [True, False], ids=["file", "gone"])
def test_colocated_file_is_read_locally_else_flight(
    tmp_path, monkeypatch, push, on_disk
):
    """The colocated path has no switch: a location whose file exists on
    this filesystem is read from the file (no Flight call at all), one
    whose file is gone goes to the serving executor over Flight. A push
    location with no live stream in this process does the same through
    its spilled file or DoExchange, and meters the fall-back."""
    import ballista_tpu.client.flight as flight
    from ballista_tpu.executor.reader import fetch_partition_batches

    p = str(tmp_path / "data-0.arrow")
    if on_disk:
        _write_file(p, 0, 6, n_batches=2)
    loc = dataclasses.replace(_loc(p, port=1), push=push)
    wire = pa.record_batch(
        [pa.array([-1], pa.int64()), pa.array([-1.0])], schema=ARROW2
    )
    calls = []

    def over_flight(name):
        def fetch(loc_, *args, **kwargs):
            calls.append((name, loc_.path))
            yield wire
        return fetch

    monkeypatch.setattr(
        flight, "fetch_partition_batches", over_flight("do_get")
    )
    monkeypatch.setattr(
        flight, "fetch_push_batches", over_flight("do_exchange")
    )
    fallbacks = []
    got = list(
        fetch_partition_batches(
            loc, retries=1, on_push_fallback=lambda: fallbacks.append(1)
        )
    )
    if on_disk:
        assert calls == []
        assert [rb.column(0).to_pylist() for rb in got] == [
            list(range(6)), list(range(6, 12))
        ]
        assert fallbacks == ([1] if push else [])
    else:
        assert calls == [("do_exchange" if push else "do_get", p)]
        assert got == [wire] and fallbacks == []


def test_zero_row_upstream_output(tmp_path):
    """A zero-row upstream file and an empty location list both read as
    an empty (but well-formed) stream."""
    empty = str(tmp_path / "data-0.arrow")
    with paipc.new_file(empty, ARROW2):
        pass  # schema-only file, zero batches
    nonempty = str(tmp_path / "data-1.arrow")
    _write_file(nonempty, 0, 5)
    plan = ShuffleReaderExec([[_loc(empty), _loc(nonempty)]], SCHEMA2)
    keys = _collect_keys(plan, _ctx())
    assert keys.tolist() == [0, 1, 2, 3, 4]
    # no locations at all -> one empty DeviceBatch, schema preserved
    plan2 = ShuffleReaderExec([[]], SCHEMA2)
    batches = list(plan2.execute(0, _ctx()))
    assert len(batches) == 1 and batches[0].num_rows() == 0


def test_empty_batch_string_column_carries_dictionary():
    """The empty-partition -> string-filter shape (q5 at 4-way shuffle on
    a small SF): DeviceBatch.empty must attach an (empty) dictionary to
    STRING fields so string operators see a string column, not a missing
    one. Broken at seed — the filter raised 'string column without
    dictionary in comparison'."""
    from ballista_tpu.columnar.batch import DeviceBatch
    from ballista_tpu.exec.pipeline import FilterExec
    from ballista_tpu.expr import logical as L

    schema = Schema([Field("name", DataType.STRING)])
    empty = DeviceBatch.empty(schema)
    assert "name" in empty.dictionaries
    assert len(empty.dictionaries["name"]) == 0

    from ballista_tpu.exec.base import ExecutionPlan

    class Src(ExecutionPlan):
        def schema(self):
            return schema

        def execute(self, partition, ctx):
            yield DeviceBatch.empty(schema)

    f = FilterExec(
        Src(),
        L.BinaryExpr(
            L.Column("name"), L.Operator.EQ, L.Literal("x", DataType.STRING)
        ),
    )
    out = list(f.execute(0, _ctx()))
    assert sum(b.num_rows() for b in out) == 0


def test_ipc_appender_zero_writes(tmp_path):
    """An appender that closes with no batches written: clean (0, 0, 0)
    stats and NO file on disk (empty buckets publish no location)."""
    path = str(tmp_path / "data-9.arrow")
    app = _IpcAppender(path)
    assert app.close() == (0, 0, 0, False)
    assert not os.path.exists(path)
    # with compression options too
    app2 = _IpcAppender(path, options=paipc.IpcWriteOptions(compression="lz4"))
    assert app2.close() == (0, 0, 0, False)
    assert not os.path.exists(path)


def test_writer_sort_scatter_partitions_rows(tmp_path):
    """The single sort-based scatter: buckets cover the input exactly,
    rows within a bucket keep input order (stable), and per-file metadata
    matches what was written."""
    from ballista_tpu.columnar.batch import DeviceBatch
    from ballista_tpu.exec.base import ExecutionPlan, UnknownPartitioning
    from ballista_tpu.executor.shuffle import ShuffleWriterExec
    from ballista_tpu.expr import logical as L

    n = 1000
    keys = np.arange(n, dtype=np.int64) % 37

    class Src(ExecutionPlan):
        def schema(self):
            return SCHEMA2

        def output_partitioning(self):
            return UnknownPartitioning(1)

        def execute(self, partition, ctx):
            yield DeviceBatch.from_host(
                SCHEMA2,
                [keys, np.arange(n, dtype=np.float64)],
                n,
            )

    w = ShuffleWriterExec("job", 1, Src(), [L.Column("k")], 4)
    ctx = _ctx()
    ctx.work_dir = str(tmp_path)
    metas = w.execute_shuffle_write(0, ctx)
    assert sum(m.num_rows for m in metas) == n
    seen = []
    for m in metas:
        with paipc.open_file(pa.memory_map(m.path)) as r:
            t = r.read_all()
        assert t.num_rows == m.num_rows
        v = t.column("v").to_pylist()
        # stable scatter: original order preserved within the bucket
        assert v == sorted(v)
        # one partition id per file
        ks = set(t.column("k").to_pylist())
        seen.append((m.partition_id, ks))
    all_rows = [k for _, ks in seen for k in ks]
    assert len(set(all_rows)) == 37


# ---------------------------------------------------------------------------
# tentpole layer 1: overlapped fetch
# ---------------------------------------------------------------------------


def test_overlapped_fetch_bit_identical_to_sequential(tmp_path):
    paths = []
    for i in range(6):
        p = str(tmp_path / f"data-{i}.arrow")
        _write_file(p, i * 300, 100, n_batches=3)
        paths.append(p)
    locs = [[_loc(p) for p in paths]]
    seq = _collect_keys(
        ShuffleReaderExec(locs, SCHEMA2),
        _ctx(**{"ballista.tpu.shuffle_fetch_concurrency": "0"}),
    )
    conc = _collect_keys(
        ShuffleReaderExec(locs, SCHEMA2),
        _ctx(**{"ballista.tpu.shuffle_fetch_concurrency": "4"}),
    )
    # identical stream, not merely identical multiset: order preserved
    assert seq.tolist() == conc.tolist()
    assert seq.tolist() == list(range(1800))


def test_overlapped_fetch_metrics(tmp_path):
    paths = []
    for i in range(4):
        p = str(tmp_path / f"data-{i}.arrow")
        _write_file(p, i * 10, 10)
        paths.append(p)
    plan = ShuffleReaderExec([[_loc(p) for p in paths]], SCHEMA2)
    _collect_keys(plan, _ctx(**{"ballista.tpu.shuffle_fetch_concurrency": "3"}))
    c = plan.metrics.counters
    assert c["fetched_batches"] == 4
    assert c["fetched_bytes"] > 0
    assert c.get("fetch_overlap_hits", 0) + c.get(
        "fetch_overlap_misses", 0
    ) >= 4


def test_overlapped_fetch_error_position(tmp_path):
    """A corrupt location's typed error surfaces when the consumer reaches
    it — locations before it stream completely first, exactly like the
    sequential loop (recovery semantics unchanged)."""
    good = str(tmp_path / "data-0.arrow")
    _write_file(good, 0, 10)
    bad = str(tmp_path / "data-1.arrow")
    with open(bad, "wb") as f:
        f.write(b"ARROW1\x00\x00garbage-not-an-ipc-file")
    locs = [[_loc(good), _loc(bad)]]
    for conc in ("0", "4"):
        plan = ShuffleReaderExec(locs, SCHEMA2)
        ctx = _ctx(**{"ballista.tpu.shuffle_fetch_concurrency": conc})
        got = []
        with pytest.raises(ShuffleFetchError) as ei:
            for b in plan.execute(0, ctx):
                valid = np.asarray(b.valid)
                got.extend(np.asarray(b.columns[0])[valid].tolist())
        assert ei.value.transient is False  # corruption: recompute, not redial
        # the good location may already have flushed through (device-batch
        # chunking can hold it back, but it must never be lost silently)
        assert got == [] or got == list(range(10))


def test_overlapped_fetch_early_stop_joins_workers(tmp_path):
    """A consumer that stops early (LIMIT) must not leak fetch threads."""
    import threading

    paths = []
    for i in range(6):
        p = str(tmp_path / f"data-{i}.arrow")
        _write_file(p, i * 50, 50, n_batches=4)
        paths.append(p)
    plan = ShuffleReaderExec([[_loc(p) for p in paths]], SCHEMA2)
    ctx = _ctx(**{"ballista.tpu.shuffle_fetch_concurrency": "4"})
    before = {t.name for t in threading.enumerate()}
    it = plan.execute(0, ctx)
    next(it)
    it.close()  # GeneratorExit -> stop event -> pool join
    after = {t.name for t in threading.enumerate()}
    leaked = {
        n for n in after - before if n.startswith("shuffle-fetch")
    }
    assert not leaked, leaked


# ---------------------------------------------------------------------------
# tentpole layer 2: eager reader semantics (scripted location feed)
# ---------------------------------------------------------------------------


def _eager_plan(n_out=1):
    return ShuffleReaderExec(
        [[] for _ in range(n_out)], SCHEMA2,
        job_id="job", stage_id=1, eager=True,
    )


def _eager_ctx(poller, **settings):
    ctx = _ctx(**{
        "ballista.tpu.eager_poll_ms": "1",
        **settings,
    })
    ctx.shuffle_locations = poller
    return ctx


def test_eager_reader_consumes_in_map_task_order(tmp_path):
    """Publication order is 2 then 0+1 then commit; consumption must be
    map-task order 0,1,2 — the barriered order — regardless."""
    paths = {}
    for i in range(3):
        p = str(tmp_path / f"data-{i}.arrow")
        _write_file(p, i * 10, 10)
        paths[i] = p

    calls = {"n": 0}

    def poller(job_id, stage_id, partition):
        calls["n"] += 1
        n = calls["n"]
        if n == 1:
            # task 2 finished first: published but BEYOND the prefix
            return ShuffleLocationsView(
                [(2, _loc(paths[2]))], tasks_done_prefix=0,
                complete=False, failed=False,
            )
        if n == 2:
            return ShuffleLocationsView(
                [(0, _loc(paths[0])), (1, _loc(paths[1])),
                 (2, _loc(paths[2]))],
                tasks_done_prefix=2, complete=False, failed=False,
            )
        return ShuffleLocationsView(
            [(0, _loc(paths[0])), (1, _loc(paths[1])),
             (2, _loc(paths[2]))],
            tasks_done_prefix=3, complete=True, failed=False,
        )

    plan = _eager_plan()
    keys = _collect_keys(plan, _eager_ctx(poller))
    assert keys.tolist() == list(range(30))
    assert plan.metrics.counters["eager_polls"] >= 2


def test_eager_reader_zero_location_commit():
    """A committed stage that published nothing for this partition (every
    producer wrote zero rows here) yields one empty batch."""

    def poller(job_id, stage_id, partition):
        return ShuffleLocationsView([], 2, True, False)

    plan = _eager_plan()
    batches = list(plan.execute(0, _eager_ctx(poller)))
    assert len(batches) == 1 and batches[0].num_rows() == 0


def test_eager_reader_failed_source_raises_typed_error():
    def poller(job_id, stage_id, partition):
        return ShuffleLocationsView([], 0, False, True)

    plan = _eager_plan()
    with pytest.raises(ShuffleFetchError, match="gone"):
        list(plan.execute(0, _eager_ctx(poller)))


def test_eager_reader_wait_deadline():
    def poller(job_id, stage_id, partition):
        return ShuffleLocationsView([], 0, False, False)  # never progresses

    plan = _eager_plan()
    ctx = _eager_ctx(poller, **{"ballista.tpu.eager_wait_s": "0.05"})
    with pytest.raises(ShuffleFetchError, match="deadline") as ei:
        list(plan.execute(0, ctx))
    # the machine-parsed marker the scheduler uses to requeue WITHOUT
    # consuming a bounded attempt: a slow producer is not a lost one,
    # and charging the wait would fail jobs barriered mode completes
    assert "[eager-wait-timeout]" in str(ei.value)


def test_eager_wait_timeout_requeues_without_attempt_charge():
    """Scheduler side of the deadline semantics: a task failure carrying
    the eager-wait-timeout marker goes FAILED -> PENDING without
    attempts+=1, so repeated waits on a slow producer can never exhaust
    task_max_attempts."""
    from ballista_tpu.scheduler.stage_manager import (
        StageManager, TaskState,
    )
    from ballista_tpu.scheduler_types import PartitionId

    sm = StageManager()
    sm.add_running_stage("j", 2, n_tasks=1, max_attempts=2)
    err = (
        "ShuffleFetchError: [eager-wait-timeout] eager shuffle wait "
        "deadline (0.1s) exceeded for stage 1 partition 0 "
        "[shuffle-fetch job=j stage=1 partition=0 executor=]"
    )
    # mirrors apply_task_statuses: recovery re-opened nothing and the
    # marker is present -> count_attempt=False
    for _ in range(3):  # more rounds than max_attempts
        sm.update_task_status(
            PartitionId("j", 2, 0), TaskState.RUNNING, executor_id="e1"
        )
        events = sm.update_task_status(
            PartitionId("j", 2, 0),
            TaskState.FAILED,
            error=err,
            retryable=True,
            count_attempt="[eager-wait-timeout]" not in err,
        )
        kinds = [type(e).__name__ for e in events]
        assert "JobFailed" not in kinds, kinds
    stage = sm.get_stage("j", 2)
    assert stage.tasks[0].attempts == 0
    assert stage.tasks[0].state == TaskState.PENDING


def test_eager_reader_refuses_local_context():
    from ballista_tpu.errors import ExecutionError

    plan = _eager_plan()
    with pytest.raises(ExecutionError, match="scheduler-connected"):
        list(plan.execute(0, _ctx()))


def test_eager_reader_serde_roundtrip():
    from ballista_tpu.serde import BallistaCodec

    codec = BallistaCodec()
    plan = ShuffleReaderExec(
        [[], []], SCHEMA2, job_id="j123", stage_id=7, eager=True
    )
    enc = codec.physical_to_proto(plan).SerializeToString()
    node = type(codec.physical_to_proto(plan))()
    node.ParseFromString(enc)
    dec = codec.physical_from_proto(node)
    assert dec.eager and dec.job_id == "j123" and dec.stage_id == 7
    assert len(dec.partition_locations) == 2
    # byte-stable: enc(dec(enc)) == enc (the serde-closure contract)
    assert codec.physical_to_proto(dec).SerializeToString() == enc
    # barriered encodings stay byte-identical to the pre-eager wire
    barriered = ShuffleReaderExec([[]], SCHEMA2)
    enc_b = codec.physical_to_proto(barriered).SerializeToString()
    assert b"j123" not in enc_b


# ---------------------------------------------------------------------------
# chaos plumbing: producer_kill fault point
# ---------------------------------------------------------------------------


def test_producer_kill_rule_breaks_stream_after_batches(tmp_path):
    from ballista_tpu.client.flight import close_pool
    from ballista_tpu.executor.flight_service import start_flight_server
    from ballista_tpu.testing import faults

    work = tmp_path / "work"
    work.mkdir()
    p = str(work / "data-0.arrow")
    _write_file(p, 0, 10, n_batches=5)
    svc, port, _t = start_flight_server("127.0.0.1", 0, str(work))
    try:
        faults.install(
            [{"point": "producer_kill", "stage": 1, "partition": 0,
              "after_batches": 2, "max_fires": 1}],
            seed=7,
        )
        remote = _loc(p, host="127.0.0.1", port=port)
        # go through the Flight client directly (the local file exists, so
        # the reader-level helper would short-circuit to the local path)
        from ballista_tpu.client.flight import fetch_partition_batches

        got = []
        with pytest.raises(ShuffleFetchError) as ei:
            for rb in fetch_partition_batches(remote, retries=1):
                got.append(rb.num_rows)
        # two batches flowed before the producer died mid-stream
        assert got == [10, 10]
        assert ei.value.transient is False
        inj = faults.active()
        assert [pt for pt, _ in inj.log] == ["producer_kill"]
    finally:
        faults.install(None)
        close_pool()
        svc.shutdown()
