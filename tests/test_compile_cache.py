"""Compile-latency subsystem (ballista_tpu/compilecache/,
docs/compile_cache.md): capacity-bucket ladder, shared trace cache, AOT
prewarm, closed-vocabulary gate, and the heartbeat metrics path.

The tier-1 contracts proven here:

- the ladder is the ONLY capacity policy (boundaries exact, explicit
  ladders extend geometrically, config round-trips);
- a second identical submission re-traces NOTHING (the executor decodes a
  fresh plan instance per task — instance-held jits used to re-trace the
  whole plan every attempt and every repeat);
- prewarm leaks zero threads through either task loop's stop() and never
  breaks the query path (failures degrade to lazy compiles);
- every jit site in the source is registered in the vocabulary and every
  TPC-H operator declares its compile surface (q1-q22 lowering);
- compile counters ride heartbeats into the scheduler REST state.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from ballista_tpu.columnar.batch import (
    MIN_CAPACITY,
    CapacityLadder,
    DeviceBatch,
    capacity_ladder,
    round_capacity,
    set_capacity_buckets,
)
from ballista_tpu.datatypes import DataType, Field, Schema


@pytest.fixture
def restore_ladder():
    """Any test that installs a custom ladder must not leak it into the
    rest of the suite (the ladder is process-global by design)."""
    spec = capacity_ladder().spec()
    yield
    set_capacity_buckets(spec)


# ------------------------------------------------------ capacity ladder ----


def test_default_ladder_matches_historical_pow2():
    lad = CapacityLadder()
    assert lad.spec() == "2048:2"
    # n=0 and tiny n clamp to the floor
    assert lad.round(0) == MIN_CAPACITY
    assert lad.round(1) == MIN_CAPACITY
    # exactly at a bucket edge stays there; edge+1 jumps a full step
    assert lad.round(MIN_CAPACITY) == MIN_CAPACITY
    assert lad.round(MIN_CAPACITY + 1) == 2 * MIN_CAPACITY
    assert lad.round(1 << 20) == 1 << 20
    assert lad.round((1 << 20) + 1) == 1 << 21


def test_geometric_ladder_boundaries():
    lad = CapacityLadder(min_cap=1000, ratio=4)
    assert lad.round(0) == 1000
    assert lad.round(1000) == 1000
    assert lad.round(1001) == 4000
    assert lad.round(4000) == 4000
    assert lad.round(4001) == 16000
    assert lad.buckets_upto(5000) == (1000, 4000, 16000)


def test_explicit_ladder_extends_geometrically():
    lad = CapacityLadder.parse("2048,10000,100000")
    assert lad.round(0) == 2048
    assert lad.round(2048) == 2048
    assert lad.round(2049) == 10000
    assert lad.round(10001) == 100000
    # past the explicit top: geometric with the default ratio (2)
    assert lad.round(100001) == 200000
    assert lad.buckets_upto(150000) == (2048, 10000, 100000, 200000)


def test_ladder_parse_rejects_malformed_specs():
    for bad in ("0", "2048:1", "4", "-1,2048"):
        with pytest.raises(ValueError):
            CapacityLadder.parse(bad)
    # the config layer validates through the same parser
    from ballista_tpu.config import BallistaConfig

    with pytest.raises(Exception):
        BallistaConfig().with_setting(
            "ballista.tpu.capacity_buckets", "2048:1"
        )


def test_set_capacity_buckets_governs_round_capacity(restore_ladder):
    set_capacity_buckets("2048:4")
    assert round_capacity(2049) == 8192
    assert round_capacity(8193) == 32768
    set_capacity_buckets("")  # empty spec = default ladder
    assert round_capacity(2049) == 4096


def test_device_batch_empty_string_dicts_survive_custom_ladder(
    restore_ladder,
):
    """PR 6's fix (empty batches attach dictionaries to STRING fields)
    must hold at every ladder point, not just the pow2 defaults."""
    set_capacity_buckets("2048,6144")
    schema = Schema(
        [Field("k", DataType.INT64), Field("s", DataType.STRING)]
    )
    b = DeviceBatch.empty(schema, capacity=round_capacity(5000))
    assert b.capacity == 6144
    assert "s" in b.dictionaries and len(b.dictionaries["s"].values) == 0
    assert int(b.count_valid()) == 0
    # from_host at a non-pow2 bucket pads correctly
    b2 = DeviceBatch.from_host(
        Schema([Field("x", DataType.INT64)]),
        [np.arange(3000, dtype=np.int64)],
        3000,
    )
    assert b2.capacity == 6144
    assert int(b2.count_valid()) == 3000


def test_adaptive_capacity_retry_snaps_to_ladder(restore_ladder):
    """run_with_capacity_retry's grown capacity rounds through the
    ladder, so adaptive retries share compiled programs with everything
    else at that bucket (exec/base.py)."""
    set_capacity_buckets("2048:4")
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.errors import CapacityError
    from ballista_tpu.exec.base import run_with_capacity_retry

    seen = []

    def body(ctx):
        seen.append(ctx.agg_capacity_override or 0)
        if len(seen) < 2:
            raise CapacityError("grow", required=5000)
        return "ok"

    cfg = BallistaConfig()
    assert run_with_capacity_retry(cfg, body) == "ok"
    assert seen[1] in capacity_ladder().buckets_upto(seen[1])


# ------------------------------------------------------ trace cache --------


def test_shared_callable_dedupes_and_bounds():
    from ballista_tpu.compilecache import tracecache

    tracecache.clear()
    built = []

    def build():
        built.append(1)
        return lambda x: x + 1

    f1 = tracecache.shared_callable(("t", 1), build)
    f2 = tracecache.shared_callable(("t", 1), build)
    assert f1 is f2 and len(built) == 1
    assert tracecache.shared_callable(("t", 2), build) is not f1
    assert len(built) == 2
    tracecache.clear()


def test_no_retrace_on_second_identical_submission():
    """The satellite contract: an identical second submission through the
    full context path re-traces NOTHING. Fresh ExecutionPlan instances
    are built per submission (exactly like executor-decoded task plans);
    without the shared trace cache each re-jitted filter/projection/join
    program re-traced here."""
    import pyarrow as pa

    from ballista_tpu.compilecache import metrics
    from ballista_tpu.exec.context import TpuContext

    ctx = TpuContext()
    n = 4000
    rng = np.random.default_rng(3)
    ctx.register_table(
        "t",
        pa.table(
            {
                "k": pa.array(rng.integers(0, 50, n)),
                "v": pa.array(rng.uniform(0, 1, n)),
                "s": pa.array(
                    np.array(["a", "b", "c"])[rng.integers(0, 3, n)]
                ),
            }
        ),
    )
    ctx.register_table(
        "d",
        pa.table(
            {
                "id": pa.array(np.arange(50, dtype=np.int64)),
                "grp": pa.array((np.arange(50) % 7).astype(np.int64)),
            }
        ),
    )
    sql = (
        "SELECT grp, SUM(v) AS sv, COUNT(*) AS c FROM t JOIN d ON k = id "
        "WHERE v < 0.9 AND s <> 'c' GROUP BY grp ORDER BY grp"
    )
    first = ctx.sql(sql).collect()
    # one more run lets data-adaptive capacities (learned aggregate
    # slice/group capacities) settle — that learning is a one-time
    # capacity CHANGE, not a cache miss
    ctx.sql(sql).collect()
    with metrics.delta() as d:
        again = ctx.sql(sql).collect()
    assert d.value.get("traces", 0) == 0, (
        f"identical submission re-traced: {d.value}"
    )
    assert first.to_pydict() == again.to_pydict()


def test_distributed_resubmission_reuses_traces():
    """Same contract across the distributed path: the standalone executor
    decodes a fresh plan per task; the second identical job must hit the
    shared trace cache instead of re-tracing (and the scheduler must see
    compile counters from the executor's polls)."""
    import pyarrow as pa

    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.compilecache import metrics

    ctx = BallistaContext.standalone()
    try:
        rng = np.random.default_rng(5)
        n = 3000
        ctx.register_table(
            "t",
            pa.table(
                {
                    "k": pa.array(rng.integers(0, 20, n)),
                    "v": pa.array(rng.uniform(0, 1, n)),
                }
            ),
        )
        sql = "SELECT k, SUM(v) AS s FROM t WHERE v < 0.8 GROUP BY k"
        r1 = ctx.sql(sql).collect()
        ctx.sql(sql).collect()  # adaptive capacities settle
        with metrics.delta() as d:
            r2 = ctx.sql(sql).collect()
        assert d.value.get("traces", 0) == 0, (
            f"repeat job re-traced: {d.value}"
        )
        assert (
            r1.to_pandas().sort_values("k").reset_index(drop=True).equals(
                r2.to_pandas().sort_values("k").reset_index(drop=True)
            )
        )
        # compile counters rode PollWork into the scheduler (REST payload)
        from ballista_tpu.scheduler.rest import scheduler_state

        sched = ctx._standalone_cluster.scheduler
        state = scheduler_state(sched)
        assert state["executors"], "no executors registered"
        compile_metrics = state["executors"][0]["compile"]
        assert compile_metrics.get("traces", 0) > 0, compile_metrics
    finally:
        ctx.close()


# ------------------------------------------------------ prewarm ------------


def test_prewarm_modes_and_thread_hygiene():
    from ballista_tpu.compilecache import metrics, prewarm

    before = set(threading.enumerate())
    prewarm.reset_latch()
    base = metrics.snapshot().get("prewarmed_signatures", 0)
    h = prewarm.start_prewarm("background", buckets=(2048,))
    assert h.n_signatures > 0
    assert h.join(timeout=240), "prewarm did not finish in time"
    done = metrics.snapshot().get("prewarmed_signatures", 0) - base
    assert done == h.n_signatures, (done, h.n_signatures)
    # latched: same buckets again is a no-op handle
    h2 = prewarm.start_prewarm("background", buckets=(2048,))
    assert h2.n_signatures == 0
    # off never spawns anything
    assert prewarm.start_prewarm("off").n_signatures == 0
    h.stop()  # idempotent after join
    leaked = [
        t
        for t in set(threading.enumerate()) - before
        if t.name.startswith("compile-prewarm")
    ]
    assert not leaked, leaked
    prewarm.reset_latch()


def test_prewarm_failure_is_nonfatal():
    """A signature whose compile raises must only increment the failure
    counter — the query path never depends on prewarm succeeding."""
    from ballista_tpu.compilecache import metrics, prewarm
    from ballista_tpu.compilecache.registry import PrewarmSignature

    base = metrics.snapshot().get("prewarm_failures", 0)
    sig = PrewarmSignature(
        "ops.perm.sort_argsort", 2048, ("int64",), variant="boom",
        compile=lambda: (_ for _ in ()).throw(RuntimeError("boom")),
    )
    prewarm._compile_one(sig)
    assert metrics.snapshot()["prewarm_failures"] == base + 1


def test_executor_server_joins_prewarm_on_stop():
    """ExecutorServer.stop with prewarm=background leaves zero prewarm
    threads behind (the zero-thread-leak shutdown contract; the full
    cluster audit is tests/test_shutdown_hygiene.py)."""
    import os
    import tempfile

    from ballista_tpu.compilecache import prewarm
    from ballista_tpu.executor.executor import Executor, PollLoop

    prewarm.reset_latch()
    os.environ["BALLISTA_TPU_PREWARM_BUCKETS"] = "2048"
    try:
        with tempfile.TemporaryDirectory() as wd:
            loop = PollLoop(
                Executor(executor_id="px", work_dir=wd),
                "127.0.0.1:1",  # never dialed successfully — that's fine
                "127.0.0.1",
                0,
                prewarm="background",
            )
            loop.start()
            loop.stop()
        leaked = [
            t
            for t in threading.enumerate()
            if t.name.startswith("compile-prewarm") and t.is_alive()
        ]
        assert not leaked, leaked
    finally:
        os.environ.pop("BALLISTA_TPU_PREWARM_BUCKETS", None)
        prewarm.reset_latch()


# ------------------------------------------------------ vocabulary gate ----


def test_vocabulary_closed_over_source_report():
    """Every jit site in ops/ + exec/ is registered (and no stale
    entries): the source-derived report IS the ground truth, so a new
    jax.jit cannot ship without declaring its compile surface."""
    from ballista_tpu.compilecache import registry

    problems = registry.check_vocabulary()
    assert problems == [], "\n".join(problems)


def test_tpch_q1_to_q22_lowering_stays_in_vocabulary():
    """The tier-1 closed-vocabulary gate (ISSUE 7 satellite): logical →
    physical → stage lowering of all 22 TPC-H queries; any operator class
    outside OPERATOR_KERNELS (or kernel outside VOCABULARY) fails —
    recompile-vocabulary growth cannot land silently."""
    from ballista_tpu.analysis.__main__ import run_compile_vocab

    ok, summary = run_compile_vocab()
    assert ok, summary
    assert "22 TPC-H queries" in summary


# ------------------------------------------------------ hint cache ---------


def test_hint_store_round_trip(tmp_path, monkeypatch):
    """Persisted entries survive a save/load cycle; process-local tallies
    and non-literal values are dropped; in-memory learning wins merges."""
    from ballista_tpu.compilecache.hints import HintStore, store_path

    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", str(tmp_path))
    hint = {"agg_capacity": 1 << 22}
    cache = {
        ("shrink", "HashJoinExec: ...", 0, 1 << 21): 4096,
        ("join_flags", "", "plan display", (2,), None): (
            np.True_, False,  # numpy bools canonicalize to python bools
        ),
        ("dec_sum", "", "site", 1): 4,
        "__build_cache_bytes__": 123456,  # ephemeral: never persisted
        ("bad", "value"): object(),  # no literal repr: dropped
    }
    s = HintStore()
    s.load_once(hint, cache)  # no file yet: no-op, arms the fingerprint
    assert s.save_if_changed(hint, cache)
    assert not s.save_if_changed(hint, cache)  # debounced: unchanged

    h2, c2 = {}, {"existing": 1}
    s2 = HintStore()
    n = s2.load_once(h2, c2)
    assert n == 4  # 3 entries + agg_capacity
    assert s2.load_once(h2, c2) == 0  # once means once
    assert h2["agg_capacity"] == 1 << 22
    assert c2[("shrink", "HashJoinExec: ...", 0, 1 << 21)] == 4096
    assert c2[("join_flags", "", "plan display", (2,), None)] == (True, False)
    assert "__build_cache_bytes__" not in c2
    assert ("bad", "value") not in c2
    assert c2["existing"] == 1
    # memory wins the merge: a pre-existing key is not overwritten
    h3, c3 = {"agg_capacity": 1 << 23}, {("dec_sum", "", "site", 1): 6}
    HintStore().load_once(h3, c3)
    assert h3["agg_capacity"] == 1 << 23
    assert c3[("dec_sum", "", "site", 1)] == 6
    assert store_path() == str(tmp_path / "plan_hints.json")


def test_hint_store_corrupt_file_and_off(tmp_path, monkeypatch):
    from ballista_tpu.compilecache.hints import HintStore, store_path

    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", str(tmp_path))
    (tmp_path / "plan_hints.json").write_text("{not json", encoding="utf-8")
    h, c = {}, {}
    assert HintStore().load_once(h, c) == 0
    assert h == {} and c == {}
    # wrong version: ignored wholesale
    (tmp_path / "plan_hints.json").write_text(
        '{"version": 99, "entries": {"1": "2"}}', encoding="utf-8"
    )
    assert HintStore().load_once(h, c) == 0
    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", "off")
    assert store_path() is None
    assert not HintStore().save_if_changed({"agg_capacity": 4096}, {})
    # JAX_CACHE=off keeps the whole persistence surface inert too
    monkeypatch.delenv("BALLISTA_TPU_HINT_CACHE")
    monkeypatch.setenv("BALLISTA_TPU_JAX_CACHE", "off")
    assert store_path() is None


def test_hint_persistence_seeds_a_fresh_context(tmp_path, monkeypatch):
    """End-to-end cold-start contract: a fresh context (standing in for a
    fresh process — its hint/plan caches start empty) is seeded from the
    hint file a previous context persisted, skipping the adaptive
    learning its first run would otherwise pay, with identical results."""
    import pyarrow as pa

    from ballista_tpu.compilecache import metrics
    from ballista_tpu.exec.context import TpuContext

    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", str(tmp_path))
    rng = np.random.default_rng(11)
    n = 6000
    tables = {
        "t": pa.table(
            {
                "k": pa.array(rng.integers(0, 40, n)),
                "v": pa.array(rng.uniform(0, 100, n).round(2)),
            }
        ),
        "d": pa.table(
            {
                "id": pa.array(np.arange(40, dtype=np.int64)),
                "grp": pa.array((np.arange(40) % 5).astype(np.int64)),
            }
        ),
    }
    sql = (
        "SELECT grp, SUM(v) AS sv FROM t JOIN d ON k = id "
        "GROUP BY grp ORDER BY grp"
    )
    ctx1 = TpuContext()
    for name, t in tables.items():
        ctx1.register_table(name, t)
    ctx1.sql(sql).collect()
    # the settled (run-2+) result is the reference: learned decimal-sum
    # scaling makes money sums exact, and a hinted cold run starts there
    settled = ctx1.sql(sql).collect()
    # a collect only marks the store; its writer, or a flush, persists
    ctx1._hints.flush()
    assert (tmp_path / "plan_hints.json").exists()
    learned = dict(ctx1._plan_cache)
    assert learned, "expected the query to learn plan-shape facts"

    ctx2 = TpuContext()
    for name, t in tables.items():
        ctx2.register_table(name, t)
    with metrics.delta() as d:
        again = ctx2.sql(sql).collect()
    assert d.value.get("hints_loaded", 0) > 0, d.value
    # the seeded keys are the ones ctx1 learned (minus ephemerals)
    for k in learned:
        if k != "__build_cache_bytes__":
            assert k in ctx2._plan_cache, k
    assert settled.to_pydict() == again.to_pydict()


def _writer_threads():
    import threading

    from ballista_tpu.compilecache.hints import WRITER_THREAD_NAME

    return [
        t for t in threading.enumerate()
        if t.name == WRITER_THREAD_NAME and t.is_alive()
    ]


def _wait_for(cond, timeout=10.0):
    import time

    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


@pytest.fixture
def writers():
    """The live writer threads of this test's own stores. An earlier
    test's context may still have one: what it has pending is written now,
    not into this test's directory later, and its thread is not counted."""
    from ballista_tpu.compilecache import hints

    hints._flush_marked()
    before = set(_writer_threads())
    return lambda: [t for t in _writer_threads() if t not in before]


def test_hint_mark_defers_and_coalesces(tmp_path, monkeypatch, writers):
    """A mark touches no file; the store's writer persists once for the
    many marks of one debounce interval, and ends when no mark has come
    for a while."""
    from ballista_tpu.compilecache import hints, metrics

    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", str(tmp_path))
    monkeypatch.setattr(hints, "WRITE_DEBOUNCE_S", 0.4)
    monkeypatch.setattr(hints, "_WRITER_IDLE_S", 0.2)
    path = tmp_path / "plan_hints.json"
    hint, cache = {}, {}
    s = hints.HintStore()
    s.load_once(hint, cache)
    with metrics.delta() as d:
        for i in range(50):
            cache[("shrink", f"site {i}", 0, 1024)] = 64
            s.mark(hint, cache)
        assert not path.exists()  # inside the debounce: nothing written
        assert len(writers()) == 1
        assert _wait_for(lambda: not writers())
    assert path.exists()
    assert d.value["hints.marks"] == 50
    assert d.value["hints_saved"] == 1
    # (an earlier test's idling writer may add a pass of its own)
    assert d.value["phase.executor.hints_write.count"] >= 1
    c2 = {}
    hints.HintStore().load_once({}, c2)
    assert len(c2) == 50
    # a mark over an unchanged state wakes the writer, which writes nothing
    mtime = path.stat().st_mtime_ns
    with metrics.delta() as d:
        s.mark(hint, cache)
        assert _wait_for(lambda: not writers())
    assert d.value.get("hints_saved", 0) == 0
    assert d.value["hints.writes_skipped_unchanged"] == 1
    assert path.stat().st_mtime_ns == mtime


def test_hint_flush_is_synchronous_and_close_joins(
    tmp_path, monkeypatch, writers
):
    from ballista_tpu.compilecache import hints, metrics

    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", str(tmp_path))
    monkeypatch.setattr(hints, "WRITE_DEBOUNCE_S", 60.0)
    path = tmp_path / "plan_hints.json"
    hint, cache = {"agg_capacity": 4096}, {("shrink", "a", 0, 8): 4}
    s = hints.HintStore()
    s.load_once(hint, cache)
    s.mark(hint, cache)
    assert not path.exists()
    assert s.flush()  # written when flush returns, a minute early
    assert path.exists()
    assert not s.flush()  # nothing pending
    assert len(writers()) == 1  # still inside its debounce
    cache[("shrink", "b", 0, 8)] = 2
    s.mark(hint, cache)
    s.close()  # ends the debounce, flushes, joins
    assert not writers()
    c2 = {}
    hints.HintStore().load_once({}, c2)
    assert c2 == cache
    # after close a mark counts, starts no writer and writes nothing
    cache[("shrink", "c", 0, 8)] = 1
    with metrics.delta() as d:
        s.mark(hint, cache)
    assert d.value["hints.marks"] == 1
    assert not writers()


_JOB_KEY = {
    "join_flags": lambda job: ("join_flags", job, "plan display", (2,), None),
    "dec_sum": lambda job: ("dec_sum", job, "site", 1),
    "dec_sum_last": lambda job: ("dec_sum_last", job, "site", 1),
    "agg_sorted": lambda job: ("agg_sorted", job, "site", False, 0),
    "agg_state_cap": lambda job: ("agg_state_cap", job, "site", 0),
    "agg_state_prefix": lambda job: ("agg_state_prefix", job, "site", 0),
    "join_lut": lambda job: ("join_lut", _JOB_KEY["join_flags"](job)),
    "expand_cap": lambda job: (
        "expand_cap", _JOB_KEY["join_flags"](job), "INNER", 0
    ),
}


def test_hint_job_scoped_families_are_all_named():
    from ballista_tpu.compilecache.hints import _JOB_SCOPED_FAMILIES

    assert _JOB_SCOPED_FAMILIES | {"join_lut", "expand_cap"} == set(_JOB_KEY)


@pytest.mark.parametrize("family", sorted(_JOB_KEY))
def test_hint_entries_keyed_by_a_job_id_stay_in_memory(
    family, tmp_path, monkeypatch, writers
):
    """What no later process can read is not written: an entry whose key
    carries a served job's id, directly or through a nested strategy
    key, neither enters the file nor moves the fingerprint; the same
    family under the empty job id (the local context) and the unscoped
    families round-trip as before."""
    from ballista_tpu.compilecache import hints, metrics

    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", str(tmp_path))
    path = tmp_path / "plan_hints.json"
    key = _JOB_KEY[family]
    hint = {"agg_capacity": 1 << 20}
    cache = {
        key(""): 7,
        ("shrink", "HashJoinExec: ...", 0, 1 << 21): 4096,
        ("aqe", "class-a"): (("flip", 2, 0),),
    }
    s = hints.HintStore()
    s.load_once(hint, cache)
    assert s.save_if_changed(hint, cache)
    written = path.read_text(encoding="utf-8")
    with metrics.delta() as d:
        cache[key("job-one")] = 9
        cache[key("job-two")] = 11
        assert not s.save_if_changed(hint, cache)  # fingerprint held
    assert d.value["hints.writes_skipped_unchanged"] == 1
    assert d.value["hints.entries_job_scoped_skipped"] == 2
    assert path.read_text(encoding="utf-8") == written
    # and when something persistable does change, they still stay out
    cache[("shrink", "other site", 0, 64)] = 8
    assert s.save_if_changed(hint, cache)
    assert "job-one" not in path.read_text(encoding="utf-8")
    h2, c2 = {}, {}
    hints.HintStore().load_once(h2, c2)
    assert h2 == hint
    assert c2 == {
        k: v for k, v in cache.items()
        if "job-one" not in repr(k) and "job-two" not in repr(k)
    }
    assert len(c2) == 4 and c2[key("")] == 7


def test_restarted_executor_loads_what_the_stopped_one_flushed(
    tmp_path, monkeypatch, writers
):
    """A clean stop persists everything learned: close() flushes a mark
    the writer has not reached yet, and the next executor on the same
    hint directory starts from it."""
    from ballista_tpu.compilecache import hints
    from ballista_tpu.executor.executor import Executor

    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", str(tmp_path / "hints"))
    monkeypatch.setattr(hints, "WRITE_DEBOUNCE_S", 60.0)
    ex = Executor("e-1", str(tmp_path / "work"))
    learned = {
        ("shrink", "FilterExec: ...", 0, 1 << 20): 2048,
        ("dec_sum", "", "site", 0): 3,
    }
    ex._plan_cache.update(learned)
    ex._plan_cache[("dec_sum", "job-1", "site", 0)] = 3
    ex._capacity_hint["agg_capacity"] = 1 << 18
    ex._hints.mark(ex._capacity_hint, ex._plan_cache)
    assert not (tmp_path / "hints" / "plan_hints.json").exists()
    ex.close()
    assert not writers()
    ex2 = Executor("e-2", str(tmp_path / "work"))
    try:
        assert ex2._plan_cache == learned
        assert ex2._capacity_hint == {"agg_capacity": 1 << 18}
    finally:
        ex2.close()


def test_hint_write_failure_disables_the_writer_once(
    tmp_path, monkeypatch, caplog, writers
):
    import logging

    from ballista_tpu.compilecache import hints, metrics

    blocker = tmp_path / "a_file"
    blocker.write_text("not a directory", encoding="utf-8")
    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", str(blocker / "hints"))
    monkeypatch.setattr(hints, "WRITE_DEBOUNCE_S", 0.05)
    monkeypatch.setattr(hints, "_WRITER_IDLE_S", 0.05)
    hint, cache = {}, {("shrink", "a", 0, 8): 4}
    s = hints.HintStore()
    s.load_once(hint, cache)
    with caplog.at_level(logging.WARNING, logger=hints.log.name):
        with metrics.delta() as d:
            s.mark(hint, cache)
            assert _wait_for(lambda: not writers())
            assert s._write_failed
            for i in range(5):  # disabled: no writer, no second warning
                cache[("shrink", "b", i, 8)] = 4
                s.mark(hint, cache)
                assert not writers()
            assert not s.flush()
            s.close()
    warnings = [r for r in caplog.records if "not writable" in r.getMessage()]
    assert len(warnings) == 1
    assert d.value["hints.marks"] == 6
    assert d.value.get("hints_saved", 0) == 0


def test_concurrent_marks_during_plan_cache_resizes(
    tmp_path, monkeypatch, writers
):
    """Four task threads commit into the owner's plan cache (it resizes as
    it grows) and mark while the writer snapshots it: nothing raises on
    either side (hints._snapshot_items), and after close() the file
    holds the last state."""
    import sys
    import threading

    from ballista_tpu.compilecache import hints

    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", str(tmp_path))
    monkeypatch.setattr(hints, "WRITE_DEBOUNCE_S", 0.005)
    raised = []
    monkeypatch.setattr(threading, "excepthook", raised.append)
    hint, cache = {}, {}
    s = hints.HintStore()
    s.load_once(hint, cache)
    n_threads, n_keys = 4, 1000

    def task_thread(t):
        for i in range(n_keys):
            cache[("shrink", f"site {t}", i, 1024)] = i
            cache[("dec_sum", f"job-{t}", "site", i)] = i
            s.mark(hint, cache)

    threads = [
        threading.Thread(target=task_thread, args=(t,))
        for t in range(n_threads)
    ]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    s.close()
    assert not writers()
    assert not raised, raised
    c2 = {}
    hints.HintStore().load_once({}, c2)
    # at most _MAX_ENTRIES of them, newest first: here all fit
    assert c2 == {k: v for k, v in cache.items() if k[0] == "shrink"}
    assert len(c2) == n_threads * n_keys


# ------------------------------------------------------ metrics ------------


def test_metrics_delta_and_cache_off_inertness():
    """metrics.delta captures per-block counters; and with
    BALLISTA_TPU_JAX_CACHE=off the persistent-cache machinery is fully
    disabled (satellite 1: 'off' used to leave the min-compile-time
    eligibility walk armed)."""
    import subprocess
    import sys

    from ballista_tpu.compilecache import metrics

    import jax
    import jax.numpy as jnp

    with metrics.delta() as d:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
    assert d.value.get("traces", 0) >= 1
    out = subprocess.run(
        [sys.executable, "-c",
         "import ballista_tpu, jax; "
         "print(jax.config.jax_enable_compilation_cache, "
         "repr(jax.config.jax_compilation_cache_dir))"],
        capture_output=True, text=True, timeout=120,
        env={
            **__import__("os").environ, "BALLISTA_TPU_JAX_CACHE": "off",
        },
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[0] == "False", out.stdout
