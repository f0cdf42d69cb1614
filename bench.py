#!/usr/bin/env python
"""TPC-H benchmark harness (ref: benchmarks/src/bin/tpch.rs:245-249 —
`tpch benchmark`, N iterations per query, JSON summary).

Runs the headline queries (BASELINE.md: q1/q3/q5/q6/q18) on the TPU, with
a cold (compile) pass and warm iterations, then measures the same queries
on the CPU backend in a second child process to form the BASELINE.md x5
denominator. The parent never touches JAX and the two children run one
after the other: a chip belongs to one process at a time. The device child
fails when JAX gives it anything but a TPU; only a caller that sets
JAX_PLATFORMS=cpu itself (the CPU denominator child does) gets a CPU run,
and its result says so.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "queries/sec", "vs_baseline": N}
where value = warm throughput over the headline set on this backend and
vs_baseline = speedup vs the CPU-executor run (>1 means the device is
faster; BASELINE.md target is >=5). Detailed per-query timings go to
BENCH_DETAIL.json and stderr.

Env knobs: BENCH_SF (default 1; 0.1 for a quick run), BENCH_ITERS
(default 3), BENCH_QUERIES (comma list, default q1,q3,q5,q6,q18),
BENCH_SKIP_CPU=1. BENCH_CONFIG applies extra session settings
("ballista.tpu.hbm_budget_mb=16384,ballista.tpu.scan_stream_mb=2048");
BENCH_PARQUET=1 registers the tables as parquet files (written once to
BENCH_PARQUET_DIR, default ./bench_data/sf<SF>) so the streamed-scan +
prefetch paths and row-group pruning are exercised — the SF>=10
out-of-core configurations. BENCH_STREAM_SLICE_MB shrinks the streamed
slice (default 1GB) and BENCH_ROW_GROUP_ROWS the written row groups
(default 1M rows) so the prefetch A/B also runs at small SF.
BENCH_SERVE=1 runs the serving fast-path suite (docs/serving.md):
result-cache cold-vs-hit, a saturated closed-loop point-query ablation
(bypass on/off, grant batch 4/1), and the open-loop mixed sweep with
cache/bypass/batch ablation arms, writing BENCH_SERVE.json.
BENCH_AQE=1 runs the adaptive-query-execution suite (docs/aqe.md):
adaptive-vs-static on seeded skewed/misestimated data plus a TPC-H
warm guardrail, writing BENCH_AQE.json.
Details land in BENCH_DETAIL.json (SF=1) or
BENCH_SF<SF>_DETAIL.json, with peak host RSS, per-query spill bytes /
passes, and — when a query streamed — a prefetch-disabled A/B warm
timing.
"""

import json
import os
import pathlib
import subprocess
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
QDIR = HERE / "benchmarks" / "queries"

SF = float(os.environ.get("BENCH_SF", "1"))
ITERS = int(os.environ.get("BENCH_ITERS", "3"))
QUERIES = os.environ.get("BENCH_QUERIES", "q1,q3,q5,q6,q18").split(",")


def _bench_config():
    from ballista_tpu.config import BallistaConfig

    # single-chip suite: host-side partition splitting only multiplies
    # blocking syncs (the XLA program parallelizes internally); distributed
    # partitioning is exercised by the cluster tests, not the chip bench
    cfg = BallistaConfig().with_setting("ballista.shuffle.partitions", "1")
    for kv in os.environ.get("BENCH_CONFIG", "").split(","):
        if kv.strip():
            k, v = kv.split("=", 1)
            cfg = cfg.with_setting(k.strip(), v.strip())
    return cfg


def _register_tables(ctx) -> tuple[dict, float]:
    """Register the TPC-H tables; returns ({name: rows}, gen_seconds).
    BENCH_PARQUET=1 writes the tables once to parquet (multiple row
    groups, so the streamed scan / prefetch / pruning paths run) and
    registers the files; generation is skipped entirely when the files
    already exist — at SF>=10 that is most of a cold run's wall clock."""
    import pyarrow.parquet as papq

    from ballista_tpu.tpch import all_schemas

    names = list(all_schemas())
    rows: dict = {}
    if os.environ.get("BENCH_PARQUET"):
        pdir = pathlib.Path(
            os.environ.get("BENCH_PARQUET_DIR", HERE / "bench_data")
        ) / f"sf{SF:g}"
        gen_s = 0.0
        missing = [n for n in names if not (pdir / f"{n}.parquet").exists()]
        if missing:
            from ballista_tpu.tpch import gen_all

            pdir.mkdir(parents=True, exist_ok=True)
            t0 = time.time()
            data = gen_all(scale=SF)
            rg_rows = int(os.environ.get("BENCH_ROW_GROUP_ROWS", 1 << 20))
            for name in missing:
                papq.write_table(
                    data[name], pdir / f"{name}.parquet",
                    row_group_size=rg_rows,
                )
            gen_s = time.time() - t0
        for name in names:
            path = str(pdir / f"{name}.parquet")
            ctx.register_parquet(name, path)
            rows[name] = papq.ParquetFile(path).metadata.num_rows
        return rows, gen_s
    from ballista_tpu.tpch import gen_all

    t0 = time.time()
    data = gen_all(scale=SF)
    gen_s = time.time() - t0
    for name, t in data.items():
        ctx.register_table(name, t)
        rows[name] = t.num_rows
    return rows, gen_s


def _peak_rss_mb() -> float:
    import resource

    return round(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024, 1
    )


def _percentiles(xs) -> dict:
    """Nearest-rank p50/p95/p99 over a latency sample (tail tracking:
    means hide exactly the latencies an SLO cares about). Keys match the
    per-query BENCH_* plan-artifact fields."""
    s = sorted(xs)

    def pct(p: float) -> float:
        if not s:
            return 0.0
        return s[min(len(s) - 1, int(round(p * (len(s) - 1))))]

    return {
        "p50": round(pct(0.50), 4),
        "p95": round(pct(0.95), 4),
        "p99": round(pct(0.99), 4),
    }


_PLAN_COUNTERS = (
    "spill_bytes", "spill_passes", "stream_slices",
    "prefetch_hits", "prefetch_misses",
    # shuffle data-plane counters (executor/reader.py): populated when a
    # plan contains ShuffleReaderExec nodes (distributed runs; the
    # single-chip suite shuffles with partitions=1 and shows zeros)
    "fetched_bytes", "fetched_batches",
    "fetch_overlap_hits", "fetch_overlap_misses", "eager_polls",
    # push-shuffle counters (docs/shuffle.md): in-memory bytes committed
    # by writers, bytes the window spilled to disk, and reads that fell
    # back from a push location to the pull plane
    "pushed_bytes", "push_spill_bytes", "push_fallbacks",
)


def _plan_counters(phys) -> dict:
    from ballista_tpu.exec.base import plan_counters

    return {
        k: v for k, v in plan_counters(phys, _PLAN_COUNTERS).items() if v
    }


def _cost_fields(history_store) -> dict:
    """Tracked cost fields (docs/observability.md "Cost accounting"):
    the newest query-log record's cost vector — the perf trajectory
    records efficiency (cpu/shuffle/spill) alongside latency in every
    BENCH_* per-query plan artifact. Reads the engine's OWN accounting
    (the local context's query log, or a cluster scheduler's history
    store) instead of re-measuring."""
    try:
        rows = history_store.jobs(limit=1)
    except Exception:  # noqa: BLE001 — accounting off / empty store
        return {}
    if not rows:
        return {}
    cost = rows[0].get("cost") or {}
    return {
        "cpu_seconds": round(float(cost.get("cpu_seconds", 0)), 4),
        "shuffle_bytes": int(cost.get("shuffle_read_bytes", 0))
        + int(cost.get("shuffle_write_bytes", 0)),
        "spill_bytes": int(cost.get("spill_bytes", 0)),
    }


def _collect_with_plan(ctx, sql: str):
    """(table, rows, executed plan) — the plan so per-query metrics
    (spill bytes, prefetch hit ratio) can be read AFTER the run."""
    t, phys = ctx.sql(sql).collect_with_plan()
    return t, t.num_rows, phys


def run_suite() -> dict:
    """Run the query set in-process on the current JAX backend."""
    sys.path.insert(0, str(HERE))
    import jax

    from ballista_tpu.exec.context import TpuContext

    backend = jax.devices()[0].platform
    if backend != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench: JAX found platform {backend!r}, not a TPU. A CPU run "
            "is made only when asked for with JAX_PLATFORMS=cpu"
        )
    cfg = _bench_config()

    ssmb = os.environ.get("BENCH_STREAM_SLICE_MB")
    if ssmb:
        # shrink streamed-scan slices so the prefetch A/B is exercisable
        # below SF=10 (default slice is 1GB: smaller runs see one slice
        # and the overlap has nothing to hide behind)
        from ballista_tpu.exec.scan import ParquetScanExec

        ParquetScanExec.STREAM_SLICE_BYTES = int(float(ssmb) * (1 << 20))

    ctx = TpuContext(cfg)
    rows, gen_s = _register_tables(ctx)

    out = {
        "backend": backend,
        "sf": SF,
        "gen_seconds": round(gen_s, 2),
        "table_rows": rows,
        "config": cfg.settings(),
        "queries": {},
    }
    from ballista_tpu.compilecache import metrics as compile_metrics

    prefetch_on = cfg.prefetch_depth() > 0
    for qn in QUERIES:
        sql = (QDIR / f"{qn}.sql").read_text()
        # compile-latency tracking (docs/compile_cache.md): traces during
        # the cold pass = the query's distinct-signature count this
        # process; compile_seconds = wall time inside XLA backend compiles
        with compile_metrics.delta() as cold_d:
            t0 = time.time()
            _, nrows, phys = _collect_with_plan(ctx, sql)
            cold = time.time() - t0
        warms = []
        with compile_metrics.delta() as warm_d:
            for _ in range(ITERS):
                t0 = time.time()
                _, nrows, phys = _collect_with_plan(ctx, sql)
                warms.append(time.time() - t0)
        counters = _plan_counters(phys)
        warm_pcts = _percentiles(warms)
        q = {
            "cold_s": round(cold, 4),
            "warm_s": [round(w, 4) for w in warms],
            "warm_best_s": round(min(warms), 4),
            # tail tracking across repeats (docs/observability.md): the
            # perf trajectory keeps tails, not just bests/averages
            "warm_p50_s": warm_pcts["p50"],
            "warm_p95_s": warm_pcts["p95"],
            "warm_p99_s": warm_pcts["p99"],
            "rows": nrows,
            "lineitem_rows_per_s": int(rows["lineitem"] / min(warms)),
            # tracked compile-cost fields (BENCH_* plan schema): future
            # rounds chart compile cost alongside throughput
            "n_signatures": int(cold_d.value.get("traces", 0)),
            "compile_seconds": round(
                cold_d.value.get("compile_seconds", 0), 4
            ),
            "warm_retraces": int(warm_d.value.get("traces", 0)),
            **counters,
        }
        # tracked cost fields (docs/observability.md): the final warm
        # pass's cost vector from the context's own query log
        q.update(_cost_fields(ctx._system_history()))
        hits = counters.get("prefetch_hits", 0)
        misses = counters.get("prefetch_misses", 0)
        if hits + misses:
            q["prefetch_hit_ratio"] = round(hits / (hits + misses), 3)
        # observability overhead tracking (docs/observability.md):
        # (1) tracing-off overhead must be NIL — with ballista.tpu.trace
        # at its "off" default, no span may have been recorded by the
        # timed passes above (the off path never mints a trace context,
        # so the in-process ring stays empty — asserted, not hoped);
        # (2) BENCH_PROFILE=1 additionally measures EXPLAIN ANALYZE-style
        # per-operator capture: one instrumented warm pass, overhead
        # reported per query.
        from ballista_tpu.obs import trace as obs_trace

        if cfg.trace() == "off":
            n_spans = len(obs_trace.snapshot())
            assert n_spans == 0, (
                f"{qn}: tracing is off but {n_spans} spans were recorded "
                "— the off path must cost (and allocate) nothing"
            )
            q["trace_off_spans"] = 0
        if os.environ.get("BENCH_PROFILE"):
            from ballista_tpu.obs import profile as obs_profile

            # `phys` is the instance the physical-plan cache returns for
            # this (query, config, data) key, so the timed pass below
            # re-executes exactly this instrumented tree (cache hits
            # reset metrics but keep the metering wrappers)
            obs_profile.instrument_plan(phys)
            t0 = time.time()
            _collect_with_plan(ctx, sql)
            profiled = time.time() - t0
            q["profile_capture_s"] = round(profiled, 4)
            q["profile_overhead_s"] = round(profiled - min(warms), 4)
        if prefetch_on and counters.get("stream_slices", 0) > 1:
            # prefetch A/B on streamed queries: same data, same run, depth
            # 0 — the acceptance signal that compute/IO overlap pays
            old = ctx.config
            ctx.config = old.with_setting("ballista.tpu.prefetch_depth", "0")
            try:
                _collect_with_plan(ctx, sql)  # cold (fresh plan instance)
                nwarmeans = []
                for _ in range(ITERS):
                    t0 = time.time()
                    _collect_with_plan(ctx, sql)
                    nwarmeans.append(time.time() - t0)
            finally:
                ctx.config = old
            q["warm_noprefetch_s"] = [round(w, 4) for w in nwarmeans]
            q["prefetch_speedup"] = round(
                min(nwarmeans) / max(min(warms), 1e-9), 3
            )
        out["queries"][qn] = q
    out["warm_total_s"] = round(
        sum(q["warm_best_s"] for q in out["queries"].values()), 4
    )
    out["queries_per_s"] = round(len(QUERIES) / out["warm_total_s"], 4)
    # whole-suite compile surface: distinct signatures traced and XLA
    # compile seconds across every query this process ran (cold + warm —
    # warm retraces count too, they are exactly what tracecache kills)
    suite_compile = compile_metrics.snapshot()
    out["n_signatures"] = int(suite_compile.get("traces", 0))
    out["compile_seconds"] = round(
        suite_compile.get("compile_seconds", 0), 4
    )
    out["persistent_cache_hits"] = int(
        suite_compile.get("persistent_cache_hits", 0)
    )
    out["persistent_cache_misses"] = int(
        suite_compile.get("persistent_cache_misses", 0)
    )
    out["peak_rss_mb"] = _peak_rss_mb()
    out["spill_bytes_total"] = sum(
        q.get("spill_bytes", 0) for q in out["queries"].values()
    )
    return out


def run_shuffle_suite() -> dict:
    """BENCH_SHUFFLE=1: the shuffle data-plane benchmark (ISSUE 6 /
    docs/shuffle.md), reporting toward the "shuffle GB/s over ICI"
    north-star. Two tiers:

    1. **Reader fan-in micro** — one ShuffleReaderExec pulling a 256MB
       partition spread over several Flight servers (the multi-executor
       fan-in shape), over REAL loopback Flight: raw `shuffle_gb_s` plus
       the fetch-overlap counters, per knob configuration.
    2. **Query A/B under an emulated inter-host link** — q5/q18 on a
       2-executor standalone cluster with the local-file fast path off
       (every shuffle byte takes the wire path, as on separate hosts) and
       remote fetches paced to BENCH_SHUFFLE_NIC_GBPS using per-codec
       wire-byte ratios measured from real IPC serialization. Loopback
       has no wire, so WITHOUT pacing the knobs can only cost (threads +
       codec CPU, ~5-10% here) — the pacing restores the one property of
       the target deployment this box cannot exhibit: shuffle bytes take
       time proportional to their size. Sequential baseline
       (concurrency 0, codec none) vs pipelined (concurrency 4, lz4),
       eager OFF in both arms so the A/B isolates the fetch layer.

    An eager-vs-barriered q5 comparison (defaults otherwise, no pacing)
    is included as an informational third section.

    Env: BENCH_SHUFFLE_SF (default 0.05), BENCH_SHUFFLE_NIC_GBPS
    (default 0.002), BENCH_ITERS. Writes BENCH_SHUFFLE.json.

    Why 0.002 GB/s: the emulated rate is chosen to reproduce the TARGET
    deployment's shuffle-time-to-compute-time ratio, not a physical NIC.
    At TPC-H SF100 on the TPU target, a shuffle-heavy query moves
    O(100GB) against tens of seconds of device compute — transfer and
    compute are the same order. This CPU box computes q5/q18 at roughly
    1 MB of shuffled bytes per compute-second (~1000x more compute per
    byte than the device target), so an undistorted wire would make
    shuffle invisible here and ANY fetch-layer A/B meaningless. Scaling
    the emulated link by the same factor restores the target's ratio;
    the artifact labels the rate so nobody mistakes these for loopback
    numbers (the raw, unpaced numbers are reported alongside).
    """
    import shutil
    import tempfile

    import numpy as np
    import pyarrow as pa
    import pyarrow.ipc as paipc

    import ballista_tpu.client.flight as _fl
    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.tpch import gen_all

    sf = float(os.environ.get("BENCH_SHUFFLE_SF", "0.05"))
    nic_gbps = float(os.environ.get("BENCH_SHUFFLE_NIC_GBPS", "0.002"))
    iters = max(2, ITERS)
    data = gen_all(scale=sf)

    # measured wire-bytes ratio per codec (real IPC serialization of a
    # representative lineitem batch — what the Flight stream would carry)
    sample = (
        data["lineitem"].slice(0, 1 << 16).combine_chunks().to_batches()[0]
    )

    def ser_len(codec):
        sink = pa.BufferOutputStream()
        opts = paipc.IpcWriteOptions(compression=codec) if codec else None
        kw = {"options": opts} if opts else {}
        with paipc.new_stream(sink, sample.schema, **kw) as w:
            w.write_batch(sample)
        return len(sink.getvalue())

    raw = ser_len(None)
    ratio = {
        "none": 1.0,
        "lz4": round(ser_len("lz4") / raw, 4),
        "zstd": round(ser_len("zstd") / raw, 4),
    }

    out = {
        "sf": sf,
        "emulated_nic_gbps": nic_gbps,
        "emulation_rationale": (
            "rate chosen so shuffle-transfer/compute matches the SF100 "
            "device target (~1000x more compute per byte on this CPU box "
            "than on the TPU; see run_shuffle_suite docstring) — the "
            "query_ab section measures the wire-bound regime the feature "
            "targets, reader_fanin the raw loopback data plane"
        ),
        "codec_wire_ratio": ratio,
        "iters": iters,
    }

    # -- tier 1: reader fan-in micro over real Flight (no pacing) ----------
    import dataclasses as _dc

    from ballista_tpu.executor.flight_service import start_flight_server
    from ballista_tpu.executor.reader import ShuffleReaderExec
    from ballista_tpu.scheduler_types import PartitionLocation
    from ballista_tpu.datatypes import DataType, Field, Schema as BSchema
    from ballista_tpu.exec.base import TaskContext

    tmp = tempfile.mkdtemp(prefix="bench-shuffle-")
    arrow2 = pa.schema([("k", pa.int64()), ("v", pa.float64())])
    rows_per, n_batches, n_servers, files_per = 1 << 16, 32, 4, 2
    rb = pa.record_batch(
        [pa.array(np.arange(rows_per, dtype=np.int64)),
         pa.array(np.random.rand(rows_per))],
        schema=arrow2,
    )
    locs, real, servers = [], {}, []
    orig_ticket = _fl.make_ticket
    try:
        for s in range(n_servers):
            sdir = os.path.join(tmp, f"exec-{s}")
            os.makedirs(sdir)
            svc, port, _t = start_flight_server("127.0.0.1", 0, sdir)
            servers.append(svc)
            for i in range(files_per):
                p = os.path.join(sdir, f"data-{i}.arrow")
                with paipc.new_file(p, arrow2) as w:
                    for _ in range(n_batches):
                        w.write_batch(rb)
                fake = f"/bench-remote/e{s}-{i}.arrow"
                real[fake] = p
                locs.append(
                    PartitionLocation(
                        "j", 1, 0, f"e{s}", "127.0.0.1", port, fake
                    )
                )
        total_bytes = sum(os.path.getsize(p) for p in real.values())
        _fl.make_ticket = lambda l, compression="", **kw: orig_ticket(
            _dc.replace(l, path=real.get(l.path, l.path)), compression, **kw
        )
        bschema = BSchema(
            [Field("k", DataType.INT64), Field("v", DataType.FLOAT64)]
        )

        def fanin(conc, codec, use_locs=None, fastpath=True):
            cfg = (
                BallistaConfig()
                .with_setting(
                    "ballista.tpu.shuffle_fetch_concurrency", str(conc)
                )
                .with_setting("ballista.tpu.shuffle_compression", codec)
                .with_setting(
                    "ballista.tpu.shuffle_local_fastpath",
                    "true" if fastpath else "false",
                )
            )
            best, counters = None, {}
            for _ in range(iters):
                plan = ShuffleReaderExec(
                    [list(use_locs if use_locs is not None else locs)],
                    bschema,
                )
                t0 = time.time()
                for b in plan.execute(0, TaskContext(config=cfg)):
                    np.asarray(b.valid)  # sync to host; drop
                dt = time.time() - t0
                if best is None or dt < best:
                    best, counters = dt, dict(plan.metrics.counters)
            return {
                "seconds": round(best, 4),
                "shuffle_gb_s": round(
                    counters.get("fetched_bytes", 0) / best / 1e9, 3
                ),
                "fetched_bytes": counters.get("fetched_bytes", 0),
                "fetched_batches": counters.get("fetched_batches", 0),
                "fetch_overlap_hits": counters.get("fetch_overlap_hits", 0),
                "fetch_overlap_misses": counters.get(
                    "fetch_overlap_misses", 0
                ),
                "push_fallbacks": counters.get("push_fallbacks", 0),
            }

        # push-stream mirror of the same 256MB: one in-memory registry
        # stream per location, fetched over DoExchange (fastpath off =
        # the Flight wire path; idempotent take -> re-iterable per iter)
        from ballista_tpu.executor.push import REGISTRY as _PUSH_REG
        from ballista_tpu.executor.push import stream_key as _skey

        push_locs = []
        for s in range(n_servers):
            sdir = os.path.join(tmp, f"exec-{s}")
            svc_port = locs[s * files_per].port
            for i in range(files_per):
                key = _skey("jpush", 1, s * files_per + i, 0)
                ppath = os.path.join(
                    sdir, "jpush", "1", "0",
                    f"push-{s * files_per + i}.arrow",
                )
                stream = _PUSH_REG.open(key, ppath, sdir, None)
                for _ in range(n_batches):
                    _PUSH_REG.append(stream, rb, 1 << 40)
                _PUSH_REG.seal(stream)
                push_locs.append(
                    PartitionLocation(
                        "jpush", 1, 0, f"e{s}", "127.0.0.1", svc_port,
                        ppath, push=True,
                        map_partition=s * files_per + i,
                    )
                )

        out["reader_fanin"] = {
            "total_mb": round(total_bytes / 1e6, 1),
            "servers": n_servers,
            "sequential_none": fanin(0, "none"),
            "overlapped_none": fanin(4, "none"),
            "overlapped_lz4": fanin(4, "lz4"),
            # the push plane over the same wire: no disk read server-side
            "overlapped_push_wire": fanin(
                4, "none", use_locs=push_locs, fastpath=False
            ),
            # colocated consumption straight from the registry (the
            # in-process zero-copy ceiling)
            "overlapped_push_colocated": fanin(
                4, "none", use_locs=push_locs, fastpath=True
            ),
        }
    finally:
        # an exception mid-tier must not leave the Flight servers running,
        # the make_ticket monkeypatch installed for the A/B tiers below,
        # ~256MB of generated shuffle files, or the push-registry mirror
        # of the same bytes behind
        _fl.make_ticket = orig_ticket
        from ballista_tpu.executor.push import REGISTRY as _PUSH_REG

        for s in range(n_servers):
            _PUSH_REG.drop_owner(os.path.join(tmp, f"exec-{s}"))
        for svc in servers:
            svc.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)

    # -- tier 2: q5/q18 A/B under the emulated link ------------------------
    nic_bps = nic_gbps * 1e9
    orig_fpb = _fl.fetch_partition_batches
    orig_push = _fl.fetch_push_batches

    def paced(loc, retries=None, backoff_ms=None, timeout_s=None,
              compression="", **kw):
        r = ratio.get(compression or "none", 1.0)
        for b in orig_fpb(loc, retries, backoff_ms, timeout_s, compression,
                          **kw):
            time.sleep(b.nbytes * r / nic_bps)
            yield b

    def paced_push(loc, retries=None, backoff_ms=None, timeout_s=None,
                   compression="", **kw):
        r = ratio.get(compression or "none", 1.0)
        for b in orig_push(loc, retries, backoff_ms, timeout_s, compression,
                           **kw):
            time.sleep(b.nbytes * r / nic_bps)
            yield b

    def query_arm(settings, qns, pace):
        _fl.fetch_partition_batches = paced if pace else orig_fpb
        _fl.fetch_push_batches = paced_push if pace else orig_push
        cfg = (
            BallistaConfig()
            .with_setting("ballista.shuffle.partitions", "4")
            .with_setting("ballista.tpu.shuffle_local_fastpath", "false")
        )
        for k, v in settings.items():
            cfg = cfg.with_setting(k, v)
        ctx = BallistaContext.standalone(cfg, n_executors=2)
        try:
            for name, t in data.items():
                ctx.register_table(name, t)
            res = {}
            for qn in qns:
                sql = (QDIR / f"{qn}.sql").read_text()
                ctx.sql(sql).collect()  # cold
                res[qn] = min(
                    (lambda t0=time.time(): (
                        ctx.sql(sql).collect(), time.time() - t0
                    )[1])()
                    for _ in range(iters)
                )
            return res
        finally:
            ctx.close()
            _fl.fetch_partition_batches = orig_fpb
            _fl.fetch_push_batches = orig_push

    seq = query_arm(
        {
            "ballista.tpu.shuffle_fetch_concurrency": "0",
            "ballista.tpu.shuffle_compression": "none",
            "ballista.tpu.eager_shuffle": "false",
        },
        ("q5", "q18"), pace=True,
    )
    pipe = query_arm(
        {
            "ballista.tpu.shuffle_fetch_concurrency": "4",
            "ballista.tpu.shuffle_compression": "lz4",
            "ballista.tpu.eager_shuffle": "false",
        },
        ("q5", "q18"), pace=True,
    )
    out["query_ab"] = {
        qn: {
            "sequential_s": round(seq[qn], 4),
            "pipelined_s": round(pipe[qn], 4),
            "speedup": round(seq[qn] / pipe[qn], 3),
        }
        for qn in seq
    }

    # -- informational: eager vs barriered, raw loopback -------------------
    barr = query_arm(
        {"ballista.tpu.eager_shuffle": "false"}, ("q5",), pace=False
    )
    eag = query_arm(
        {"ballista.tpu.eager_shuffle": "true"}, ("q5",), pace=False
    )
    out["eager_vs_barriered_raw"] = {
        "q5": {
            "barriered_s": round(barr["q5"], 4),
            "eager_s": round(eag["q5"], 4),
            "speedup": round(barr["q5"] / eag["q5"], 3),
        }
    }
    return out


def run_sf100_suite() -> dict:
    """BENCH_SF100=1: the flagship run toward the BASELINE north-star
    ("TPC-H SF100 queries/sec; shuffle GB/s over ICI"), ISSUE 13 /
    docs/shuffle.md.

    SF100 is ~100GB of tables — this CPU box does not hold it, so the
    artifact records the LARGEST SF the box sustains (``BENCH_SF100_SF``,
    default 1, ~1GB) with the target scale named, exactly like the
    emulated-link rationale in run_shuffle_suite: the RATIOS (push vs
    pull on the wire-bound path, achieved shuffle GB/s vs the data-plane
    ceiling) are the transferable measurements; the absolute
    queries/sec scales with the hardware.

    Sections:

    - **headline** — q1/q5/q18 on a 2-executor standalone cluster at the
      committed defaults (push data plane, auto codec, coalescing):
      warm-best seconds per query, aggregate queries/sec, and the
      shipped data-plane counters (fetched/pushed/spilled bytes).
    - **shuffle_gb_s** — achieved fan-in rate during the headline runs
      (fetched_bytes / elapsed on the shuffle-heavy queries) plus the
      raw loopback data-plane ceiling from the reader-fanin micro
      (BENCH_SHUFFLE.json, committed alongside).
    - **push_vs_pull** — the wire-bound A/B: local fast path OFF (every
      shuffle byte crosses the Flight wire, the separate-hosts shape),
      eager on in both arms, push on vs off. Push must win >= 1.1x: it
      deletes the file write + file read + per-request buffer copy from
      every wire byte's path.

    Env: BENCH_SF100_SF (default 1), BENCH_SF100_QUERIES (default
    q1,q5,q18), BENCH_ITERS. Writes BENCH_SF100.json.
    """
    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.tpch import gen_all

    sf = float(os.environ.get("BENCH_SF100_SF", "1"))
    qnames = os.environ.get("BENCH_SF100_QUERIES", "q1,q5,q18").split(",")
    iters = max(2, ITERS)
    # the push window is sized to the workload's in-flight shuffle, the
    # way an operator sizes it to host RAM (q18 at SF1 keeps ~1.4GB of
    # map output in flight; the conservative 256MB library default kept
    # ~20%% of push bytes spilling mid-run, which measures the window,
    # not the data plane). Recorded in the artifact.
    window_mb = os.environ.get("BENCH_SF100_WINDOW_MB", "2048")
    data = gen_all(scale=sf)
    table_bytes = sum(t.nbytes for t in data.values())

    def run_arm(settings, qns):
        cfg = (
            BallistaConfig()
            .with_setting("ballista.shuffle.partitions", "4")
            .with_setting(
                "ballista.tpu.push_shuffle_window_mb", window_mb
            )
        )
        for k, v in settings.items():
            cfg = cfg.with_setting(k, v)
        ctx = BallistaContext.standalone(cfg, n_executors=2)
        try:
            for name, t in data.items():
                ctx.register_table(name, t)
            times = {}
            costs = {}
            for qn in qns:
                sql = (QDIR / f"{qn}.sql").read_text()
                ctx.sql(sql).collect()  # cold/compile pass
                best = None
                for _ in range(iters):
                    t0 = time.time()
                    ctx.sql(sql).collect()
                    dt = time.time() - t0
                    best = dt if best is None else min(best, dt)
                times[qn] = best
                # tracked cost fields: the last warm run's record from
                # the scheduler's persistent history
                costs[qn] = _cost_fields(
                    ctx._standalone_cluster.scheduler.history
                )
            counters = dict(
                ctx._standalone_cluster.scheduler.obs_task_counters
            )
            return times, counters, costs
        finally:
            ctx.close()

    out = {
        "target": "TPC-H SF100 queries/sec; shuffle GB/s over ICI",
        "sf": sf,
        "sf_rationale": (
            "largest SF this CPU box sustains in a 2-executor in-proc "
            "cluster (SF100 is ~100GB of tables); ratios are the "
            "transferable measurement, absolutes scale with hardware"
        ),
        "table_bytes": int(table_bytes),
        "queries": list(qnames),
        "iters": iters,
        "push_shuffle_window_mb": int(window_mb),
    }

    # -- headline: committed defaults (push plane on) ----------------------
    times, counters, costs = run_arm({}, qnames)
    total = sum(times.values())
    shuffle_keys = (
        "fetched_bytes", "pushed_bytes", "push_spill_bytes",
        "push_fallbacks", "output_rows",
    )
    out["headline"] = {
        "per_query_s": {q: round(s, 4) for q, s in times.items()},
        # cost fields per query (docs/observability.md): cpu/shuffle/
        # spill from the scheduler's persistent history records
        "per_query_cost": costs,
        "total_warm_s": round(total, 4),
        "queries_per_sec": round(len(times) / total, 4),
        "task_counters": {
            k: int(counters.get(k, 0)) for k in shuffle_keys
        },
    }
    # achieved shuffle rate while the headline queries ran: bytes the
    # readers actually pulled per second of query wall (iters+cold runs
    # all counted in the counters, so scale by runs)
    runs = iters + 1
    fetched = counters.get("fetched_bytes", 0) / runs
    out["shuffle_gb_s"] = {
        "achieved_during_headline": round(fetched / total / 1e9, 4),
        "definition": (
            "mean fetched shuffle bytes per second of warm query wall "
            "across the headline set; the raw data-plane ceiling is "
            "BENCH_SHUFFLE.json reader_fanin"
        ),
    }

    # -- push vs pull: the wire-bound DATA-PLANE A/B -----------------------
    # Produce + serve + consume one shuffle's worth of bytes through each
    # plane end-to-end, nothing else: pull writes Arrow IPC files and
    # serves them over Flight do_get; push commits the same batches into
    # the in-memory registry and serves them over do_exchange. This is
    # where the two planes actually differ — the query A/B below is
    # compute-diluted at this SF (the data plane is a few %% of q5/q18
    # wall, smaller than run-to-run noise on a shared CPU box) and is
    # reported as informational context.
    out["push_vs_pull_dataplane"] = _dataplane_ab(max(3, iters))

    # -- push vs pull under full queries (informational) -------------------
    wire_qs = [q for q in qnames if q != "q1"] or qnames
    wire = {"ballista.tpu.shuffle_local_fastpath": "false"}
    pull_times, pull_counters, _ = run_arm(
        {**wire, "ballista.tpu.push_shuffle": "false"}, wire_qs
    )
    push_times, push_counters, _ = run_arm(
        {**wire, "ballista.tpu.push_shuffle": "true"}, wire_qs
    )
    out["push_vs_pull_queries"] = {
        "regime": (
            "INFORMATIONAL: full q5/q18 wall with the local fast path "
            "off — the data plane is a few % of compute-bound query "
            "wall at this SF, below host noise; the wire-bound verdict "
            "is push_vs_pull_dataplane"
        ),
        "queries": {
            q: {
                "pull_s": round(pull_times[q], 4),
                "push_s": round(push_times[q], 4),
                "speedup": round(pull_times[q] / push_times[q], 3),
            }
            for q in wire_qs
        },
        "total_speedup": round(
            sum(pull_times.values()) / sum(push_times.values()), 3
        ),
        "push_counters": {
            k: int(push_counters.get(k, 0))
            for k in ("pushed_bytes", "push_spill_bytes", "push_fallbacks")
        },
        "pull_pushed_bytes": int(pull_counters.get("pushed_bytes", 0)),
    }
    return out


def _dataplane_ab(iters: int, total_mb: int = 512) -> dict:
    """Wire-bound push-vs-pull A/B: move ``total_mb`` of shuffle bytes
    producer -> wire -> consumer through each data plane END-TO-END.

    Both arms run the production-shaped path (coalesced ~8MB batches, 2
    serving executors x 4 streams, overlapped consumer with the local
    fast path off so every byte crosses the Flight wire):

    - **pull**: append batches to Arrow IPC files (the committed shuffle
      format), then a ShuffleReaderExec fan-in over ``do_get``.
    - **push**: commit the same batches into the in-memory push registry,
      then the same fan-in over ``do_exchange``.

    The difference is exactly what push deletes from every shuffle byte's
    life: the file write on the producer and the file open/map on the
    serve path."""
    import shutil
    import tempfile

    import numpy as np
    import pyarrow as pa

    from ballista_tpu.columnar.arrow_interop import (
        schema_from_arrow,  # noqa: F401 — parity with shuffle suite
    )
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.datatypes import DataType, Field, Schema as BSchema
    from ballista_tpu.exec.base import TaskContext
    from ballista_tpu.executor.flight_service import start_flight_server
    from ballista_tpu.executor.push import REGISTRY, stream_key
    from ballista_tpu.executor.reader import ShuffleReaderExec
    from ballista_tpu.executor.shuffle import _IpcAppender
    from ballista_tpu.scheduler_types import PartitionLocation

    n_servers, streams_per = 2, 4
    rows_per = 1 << 19  # ~8MB/batch at (int64, float64)
    n_streams = n_servers * streams_per
    batches_per = max(1, (total_mb << 20) // (rows_per * 16) // n_streams)
    rb = pa.record_batch(
        [pa.array(np.arange(rows_per, dtype=np.int64)),
         pa.array(np.random.rand(rows_per))],
        names=["k", "v"],
    )
    bschema = BSchema(
        [Field("k", DataType.INT64), Field("v", DataType.FLOAT64)]
    )
    cfg = (
        BallistaConfig()
        .with_setting("ballista.tpu.shuffle_fetch_concurrency", "4")
        .with_setting("ballista.tpu.shuffle_compression", "none")
        .with_setting("ballista.tpu.shuffle_local_fastpath", "false")
    )
    tmp = tempfile.mkdtemp(prefix="bench-dataplane-")
    servers = []
    try:
        ports = []
        for s in range(n_servers):
            sdir = os.path.join(tmp, f"exec-{s}")
            os.makedirs(sdir)
            svc, port, _t = start_flight_server("127.0.0.1", 0, sdir)
            servers.append(svc)
            ports.append(port)

        def consume(locs):
            plan = ShuffleReaderExec([list(locs)], bschema)
            for b in plan.execute(0, TaskContext(config=cfg)):
                np.asarray(b.valid)  # sync; drop
            return plan.metrics.counters.get("fetched_bytes", 0)

        def pull_round(r):
            t0 = time.time()
            locs = []
            for i in range(n_streams):
                sdir = os.path.join(tmp, f"exec-{i % n_servers}")
                path = os.path.join(sdir, "jdp", "1", "0",
                                    f"data-{r}-{i}.arrow")
                os.makedirs(os.path.dirname(path), exist_ok=True)
                w = _IpcAppender(path)
                for _ in range(batches_per):
                    w.write(rb)
                w.close()
                locs.append(PartitionLocation(
                    "jdp", 1, 0, f"e{i % n_servers}", "127.0.0.1",
                    ports[i % n_servers], path,
                ))
            nbytes = consume(locs)
            dt = time.time() - t0
            for loc in locs:
                os.remove(loc.path)
            return dt, nbytes

        def push_round(r):
            t0 = time.time()
            locs = []
            for i in range(n_streams):
                sdir = os.path.join(tmp, f"exec-{i % n_servers}")
                key = stream_key("jdp", 2, 1000 * r + i, 0)
                path = os.path.join(sdir, "jdp", "2", "0",
                                    f"push-{1000 * r + i}.arrow")
                st = REGISTRY.open(key, path, sdir, None)
                for _ in range(batches_per):
                    REGISTRY.append(st, rb, 1 << 40)
                REGISTRY.seal(st)
                locs.append(PartitionLocation(
                    "jdp", 2, 0, f"e{i % n_servers}", "127.0.0.1",
                    ports[i % n_servers], path, push=True,
                    map_partition=1000 * r + i,
                ))
            nbytes = consume(locs)
            dt = time.time() - t0
            for i in range(n_servers):
                REGISTRY.drop_owner(os.path.join(tmp, f"exec-{i}"))
            return dt, nbytes

        pull_best = push_best = None
        moved = 0
        for r in range(iters):
            dt, moved = pull_round(r)
            pull_best = dt if pull_best is None else min(pull_best, dt)
            dt, _ = push_round(r)
            push_best = dt if push_best is None else min(push_best, dt)
        return {
            "regime": (
                "produce + serve + consume one shuffle's bytes through "
                "each plane end-to-end over loopback Flight, local fast "
                "path off, coalesced ~8MB batches — the wire-bound "
                "data-plane cost per byte, undiluted by query compute"
            ),
            "moved_mb": round(moved / 1e6, 1),
            "pull_s": round(pull_best, 4),
            "push_s": round(push_best, 4),
            "pull_gb_s": round(moved / pull_best / 1e9, 3),
            "push_gb_s": round(moved / push_best / 1e9, 3),
            "speedup": round(pull_best / push_best, 3),
        }
    finally:
        for i in range(n_servers):
            REGISTRY.drop_owner(os.path.join(tmp, f"exec-{i}"))
        for svc in servers:
            svc.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)


def run_slo_suite() -> dict:
    """BENCH_SLO=1: the sustained-QPS SLO harness (ISSUE 12 /
    docs/observability.md). Drives a MIXED small/large TPC-H workload at
    a target arrival rate (open-loop: submissions fire on the clock, not
    on completions — the regime where queues actually form) against a
    2-executor standalone cluster, twice:

    - **steady** — no faults; the baseline distribution.
    - **chaos** — one executor killed (shuffle files deleted) mid-round
      while submissions keep arriving; lineage recovery + bounded
      retries must keep completing queries, and the cost shows up in the
      TAIL, which is exactly what this artifact exists to measure.

    Verdicts come from the scheduler's OWN metrics plane: after the
    rounds the harness scrapes ``/api/metrics`` (validated at the
    exposition-parser level), reads the ``ballista_job_latency_seconds``
    / ``ballista_queue_wait_seconds`` histograms per query class, and
    renders p50/p99 + queue-wait-p90 SLO verdicts against declared
    targets. Client-observed per-round latencies are reported alongside
    (they include result fetch; the server series starts at submission).
    ``ballista_spans_dropped_total`` must be 0 — the run itself proves
    the no-silent-caps rule held under load.

    Env: BENCH_SLO_SF (default 0.05), BENCH_SLO_QPS (default 2),
    BENCH_SLO_SECONDS (per round, default 25), BENCH_SLO_SMALL /
    BENCH_SLO_LARGE (query names, default q6 / q3),
    BENCH_SLO_TARGET_SMALL_P99_S / _LARGE_P99_S /
    BENCH_SLO_TARGET_QUEUE_P90_S. Writes BENCH_SLO.json.
    """
    import re
    import threading
    import urllib.request

    import numpy as np  # noqa: F401 — table gen path below

    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.obs.hist import quantile_from_cumulative
    from ballista_tpu.scheduler.rest import (
        start_rest_server,
        stop_rest_server,
    )
    from ballista_tpu.tpch import gen_all

    sf = float(os.environ.get("BENCH_SLO_SF", "0.05"))
    qps = float(os.environ.get("BENCH_SLO_QPS", "2"))
    round_s = float(os.environ.get("BENCH_SLO_SECONDS", "25"))
    small_q = os.environ.get("BENCH_SLO_SMALL", "q6")
    large_q = os.environ.get("BENCH_SLO_LARGE", "q3")
    targets = {
        "small_p99_s": float(
            os.environ.get("BENCH_SLO_TARGET_SMALL_P99_S", "10")
        ),
        "large_p99_s": float(
            os.environ.get("BENCH_SLO_TARGET_LARGE_P99_S", "20")
        ),
        "queue_wait_p90_s": float(
            os.environ.get("BENCH_SLO_TARGET_QUEUE_P90_S", "2")
        ),
    }
    sqls = {
        "small": (QDIR / f"{small_q}.sql").read_text(),
        "large": (QDIR / f"{large_q}.sql").read_text(),
    }
    # arrival mix: 2 small : 1 large (interactive-heavy, like a real
    # serving tier)
    mix = ("small", "small", "large")

    cfg = (
        BallistaConfig()
        .with_setting("ballista.shuffle.partitions", "2")
        .with_setting("ballista.tpu.task_max_attempts", "4")
    )
    data = gen_all(scale=sf)
    ctx = BallistaContext.standalone(
        cfg,
        n_executors=2,
        # tight liveness so the chaos round's expiry/recovery fits the
        # round instead of a 60s default window
        executor_timeout_s=5.0,
        expiry_check_interval_s=1.0,
    )
    sched = ctx._standalone_cluster.scheduler
    httpd, rest_port = start_rest_server(sched, "127.0.0.1", 0)
    base = f"http://127.0.0.1:{rest_port}"
    out = {
        "sf": sf,
        "qps": qps,
        "round_seconds": round_s,
        "mix": {"small": small_q, "large": large_q, "arrivals": list(mix)},
        "targets": targets,
        "rounds": {},
    }
    try:
        for name, t in data.items():
            ctx.register_table(name, t)
        # warmup (compile + caches) and class-token discovery: the
        # scheduler labels series by the opaque qclass hash; map it back
        # to small/large via the warmup jobs
        class_token = {}
        for cls in ("small", "large"):
            ctx.sql(sqls[cls]).collect()
            with sched._lock:
                latest = max(
                    sched.jobs.values(), key=lambda j: j.submitted_s
                )
            class_token[cls] = latest.query_class
            ctx.sql(sqls[cls]).collect()  # one more fully-warm pass
        assert class_token["small"] != class_token["large"]
        out["query_class_tokens"] = class_token

        lock = threading.Lock()

        def run_round(chaos: bool) -> dict:
            results: list[tuple] = []  # (class, latency_s, ok)
            threads: list[tuple] = []  # (thread, class)

            def one(cls: str) -> None:
                t0 = time.time()
                ok = True
                try:
                    ctx.sql(sqls[cls]).collect()
                except Exception:  # noqa: BLE001 — the SLO artifact
                    # reports failures; it must not die on one
                    ok = False
                with lock:
                    results.append((cls, time.time() - t0, ok))

            killed = None
            t_start = time.time()
            i = 0
            while time.time() - t_start < round_s:
                due = t_start + i / qps
                now = time.time()
                if due > now:
                    time.sleep(due - now)
                cls = mix[i % len(mix)]
                th = threading.Thread(target=one, args=(cls,))
                th.start()
                threads.append((th, cls))
                i += 1
                if chaos and killed is None and cls == "large" and (
                    time.time() - t_start >= 0.4 * round_s
                ):
                    # mid-round executor kill, timed right after a LARGE
                    # query entered flight so its multi-stage work is
                    # guaranteed to straddle the crash: loops stop,
                    # Flight dies, shuffle files are deleted — the full
                    # crashed-machine shape while load keeps arriving.
                    # Recovery (expiry sweep -> task reset + lost-shuffle
                    # recompute) must surface in the TAIL, not in failed
                    # queries.
                    time.sleep(min(0.3, 1.0 / qps))
                    killed = ctx._standalone_cluster.kill_executor(
                        1, lose_shuffle=True
                    )
            for th, _cls in threads:
                th.join(timeout=300)
            # a thread still alive after the join deadline is a HUNG
            # query — exactly the recovery failure this harness exists
            # to catch; it must count as failed, not silently vanish
            # from both the completed and failed tallies
            hung = {"small": 0, "large": 0}
            for th, cls in threads:
                if th.is_alive():
                    hung[cls] += 1
            with lock:
                got = list(results)
            rnd: dict = {"submitted": i}
            for cls in ("small", "large"):
                lat_ok = [l for c, l, ok in got if c == cls and ok]
                failed = sum(
                    1 for c, _l, ok in got if c == cls and not ok
                ) + hung[cls]
                rnd[cls] = {
                    "completed": len(lat_ok),
                    "failed": failed,
                    "hung": hung[cls],
                    "client_latency_s": _percentiles(lat_ok),
                }
            if chaos:
                state = json.load(
                    urllib.request.urlopen(base + "/api/state")
                )
                rnd["killed_executor"] = killed
                rnd["retries_total"] = sum(
                    j["retries"] for j in state["jobs"]
                )
                rnd["recomputes_total"] = sum(
                    j["recomputes"] for j in state["jobs"]
                )
            return rnd

        out["rounds"]["steady"] = run_round(chaos=False)
        out["rounds"]["chaos"] = run_round(chaos=True)

        # -- scrape + verdicts (parser-level validated) --------------------
        from ballista_tpu.obs.prometheus import validate_exposition

        text = urllib.request.urlopen(base + "/api/metrics").read().decode()
        validate_exposition(text)
        out["scrape"] = _scrape_hist_quantiles(
            text, class_token, quantile_from_cumulative
        )
        dropped = sum(
            float(m.group(1))
            for m in re.finditer(
                r"^ballista_spans_dropped_total\{[^}]*\} ([0-9.e+-]+)$",
                text, re.M,
            )
        )
        out["spans_dropped_total"] = int(dropped)
        sc = out["scrape"]
        chaos_failed = (
            out["rounds"]["chaos"]["small"]["failed"]
            + out["rounds"]["chaos"]["large"]["failed"]
        )
        out["slo"] = {
            "small_p99_s": sc["job_latency"]["small"]["p99"],
            "small_p99_ok": (
                sc["job_latency"]["small"]["p99"] <= targets["small_p99_s"]
            ),
            "large_p99_s": sc["job_latency"]["large"]["p99"],
            "large_p99_ok": (
                sc["job_latency"]["large"]["p99"] <= targets["large_p99_s"]
            ),
            "queue_wait_p90_s": sc["queue_wait"]["all"]["p90"],
            "queue_wait_p90_ok": (
                sc["queue_wait"]["all"]["p90"]
                <= targets["queue_wait_p90_s"]
            ),
            "chaos_all_completed": chaos_failed == 0,
            "spans_dropped_ok": dropped == 0,
        }
        out["slo"]["pass"] = all(
            v for k, v in out["slo"].items() if k.endswith("_ok")
            or k == "chaos_all_completed"
        )
    finally:
        stop_rest_server(httpd)
        ctx.close()
    return out


def run_serve_suite() -> dict:
    """BENCH_SERVE=1: the serving fast-path suite (docs/serving.md).

    Three stacked optimizations, each measured on its own and then
    together under open-loop load against a 2-executor standalone
    cluster:

    - **result cache** — cold q6 (miss + async populate) vs repeated
      identical q6 (scheduler-served hits): the headline is
      ``cold_s / hit_median_s`` (acceptance: >= 10x).
    - **single-stage bypass** and **batched task grants** — a
      SATURATED closed-loop ablation: N worker threads submit the
      point query back-to-back for a fixed window (cache off, so every
      rep truly executes). Under saturation the executors poll hot and
      the scheduler event loop + grant round-trips are the bottleneck,
      which is exactly what the bypass and the batch remove; an idle
      closed loop would instead measure the client/executor poll
      intervals (~0.1 s each) and show parity. Three arms share the
      base (bypass on, batch 4): ``bypass_off`` and ``batch_1`` flip
      one knob each. Reported per arm: throughput, p50/p95 latency,
      scheduler events consumed.

    The sweep drives a mixed arrival stream (point queries on a
    1-partition serving session, q6 + q3 on the default session) at a
    target rate for a fixed window, across four arms: **full** (cache +
    bypass + batch), **cache_off**, **bypass_off**, **batch_1**. Each
    arm reports completed queries/sec, scheduler events/sec and
    dispatch-lag p99 (scraped from ``ballista_event_dispatch_lag_
    seconds`` on /api/metrics, parser-validated), and the cache hit
    ratio.

    Env: BENCH_SERVE_SF (default 0.05), BENCH_SERVE_QPS (default 6),
    BENCH_SERVE_SECONDS (per open-loop arm, default 20),
    BENCH_SERVE_HITS (default 15), BENCH_SERVE_SAT_SECONDS (per
    saturated arm, default 8), BENCH_SERVE_WORKERS (default 8).
    Writes BENCH_SERVE.json.
    """
    import re
    import statistics
    import threading
    import urllib.request

    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.obs.hist import quantile_from_cumulative
    from ballista_tpu.scheduler.rest import (
        start_rest_server,
        stop_rest_server,
    )
    from ballista_tpu.tpch import gen_all

    sf = float(os.environ.get("BENCH_SERVE_SF", "0.05"))
    qps = float(os.environ.get("BENCH_SERVE_QPS", "6"))
    round_s = float(os.environ.get("BENCH_SERVE_SECONDS", "20"))
    n_hits = int(os.environ.get("BENCH_SERVE_HITS", "15"))
    sat_s = float(os.environ.get("BENCH_SERVE_SAT_SECONDS", "8"))
    n_workers = int(os.environ.get("BENCH_SERVE_WORKERS", "8"))
    data = gen_all(scale=sf)
    sql_q6 = (QDIR / "q6.sql").read_text()
    sql_q3 = (QDIR / "q3.sql").read_text()
    # the dashboard-shaped point query: single stage at 1 partition,
    # bypass-eligible, cache-hittable
    sql_point = (
        "select l_orderkey, l_partkey, l_extendedprice, l_discount "
        "from lineitem where l_orderkey = 1"
    )

    def base_cfg(**settings):
        cfg = BallistaConfig()
        for k, v in settings.items():
            cfg = cfg.with_setting(k.replace("__", "."), v)
        return cfg

    def boot(cfg):
        ctx = BallistaContext.standalone(cfg, n_executors=2)
        for name, t in data.items():
            ctx.register_table(name, t)
        return ctx

    out: dict = {
        "sf": sf,
        "qps": qps,
        "round_seconds": round_s,
        "point_sql": sql_point,
    }

    # -- (1) result cache: cold vs hit on q6 -------------------------------
    ctx = boot(base_cfg(
        **{"ballista.shuffle.partitions": "2",
           "ballista.tpu.result_cache_mb": "64"}
    ))
    sched = ctx._standalone_cluster.scheduler
    try:
        ctx.sql(sql_q6).collect()  # compile warmup — measure the engine,
        # not XLA; re-registering drops the warmup's cache entry so the
        # measured cold pass is a REAL miss + full execution
        ctx.register_table("lineitem", data["lineitem"].slice(0))
        t0 = time.time()
        cold_res = ctx.sql(sql_q6).collect()
        cold_s = time.time() - t0
        deadline = time.time() + 30
        while (time.time() < deadline
               and sched.result_cache.stats()["hits"] == 0):
            ctx.sql(sql_q6).collect()  # poll until population lands
            time.sleep(0.05)
        hit_lat = []
        for _ in range(n_hits):
            t0 = time.time()
            hit_res = ctx.sql(sql_q6).collect()
            hit_lat.append(time.time() - t0)
        assert hit_res.equals(cold_res), "cache hit not bit-exact"
        stats = sched.result_cache.stats()
        hit_med = statistics.median(hit_lat)
        out["result_cache"] = {
            "query": "q6",
            "cold_s": round(cold_s, 4),
            "hit_s": _percentiles(hit_lat),
            "speedup": round(cold_s / hit_med, 1),
            "cache_stats": stats,
            "hit_10x_ok": cold_s / hit_med >= 10.0,
        }
    finally:
        ctx.close()

    # -- (2) saturated closed-loop ablation: bypass + grant batching -------
    # n_workers threads submit a point lookup over a SMALL serving
    # table back-to-back: the executors never idle-sleep, so scheduler
    # event-loop hops and PollWork round-trips — what the bypass and
    # the batch remove — are the bottleneck being measured. (The
    # lineitem point query would scan sf*6M rows per rep and drown the
    # orchestration signal in compute; a serving-tier lookup table is
    # the workload these paths exist for.)
    import pyarrow as pa

    serve_tbl = pa.table({
        "a": list(range(20000)),
        "b": [float(i) for i in range(20000)],
    })
    sql_serve = "select a, b from serve_points where a < 100"

    def saturated(bypass: str, batch: str) -> dict:
        c = boot(base_cfg(
            **{"ballista.shuffle.partitions": "1",
               "ballista.tpu.single_stage_bypass": bypass,
               "ballista.tpu.task_grant_batch": batch}
        ))
        c.register_table("serve_points", serve_tbl)
        s = c._standalone_cluster.scheduler
        try:
            for _ in range(3):
                c.sql(sql_serve).collect()  # warmup
            ev0 = s._h_dispatch_lag.labels().snapshot()[2]
            lock = threading.Lock()
            lats: list = []
            stop_at = time.time() + sat_s
            t_start = time.time()

            def worker():
                while time.time() < stop_at:
                    t0 = time.time()
                    c.sql(sql_serve).collect()
                    with lock:
                        lats.append(time.time() - t0)

            ths = [
                threading.Thread(target=worker) for _ in range(n_workers)
            ]
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            wall = time.time() - t_start
            ev = s._h_dispatch_lag.labels().snapshot()[2] - ev0
            bypassed = s.obs_bypass_total
            if bypass == "true":
                assert bypassed >= len(lats), (bypassed, len(lats))
            else:
                assert bypassed == 0, bypassed
            return {
                "n": len(lats),
                "queries_per_sec": round(len(lats) / wall, 1),
                "latency_s": _percentiles(lats),
                "sched_events": ev,
                "sched_events_per_query": round(ev / len(lats), 2),
            }
        finally:
            c.close()

    sat_base = saturated("true", "4")
    sat_no_bypass = saturated("false", "4")
    sat_batch_1 = saturated("true", "1")
    out["saturated"] = {
        "workers": n_workers,
        "window_s": sat_s,
        "sql": sql_serve,
        "base": sat_base,
        "bypass_off": sat_no_bypass,
        "batch_1": sat_batch_1,
        "bypass_speedup_p50": round(
            sat_no_bypass["latency_s"]["p50"]
            / sat_base["latency_s"]["p50"], 3
        ),
        "bypass_events_saved_per_query": round(
            sat_no_bypass["sched_events_per_query"]
            - sat_base["sched_events_per_query"], 2
        ),
        "batch_throughput_gain": round(
            sat_base["queries_per_sec"]
            / sat_batch_1["queries_per_sec"], 3
        ),
    }

    # -- (3) the open-loop mixed sweep, four arms --------------------------
    # arrival mix: dashboard-heavy — 3 point : 2 q6 : 1 q3
    mix = ("point", "point", "q6", "point", "q6", "large")
    sqls = {"point": sql_point, "q6": sql_q6, "large": sql_q3}

    def run_arm(cache_mb: str, bypass: str, batch: str) -> dict:
        cfg = base_cfg(
            **{"ballista.shuffle.partitions": "2",
               "ballista.tpu.result_cache_mb": cache_mb,
               "ballista.tpu.task_grant_batch": batch,
               "ballista.tpu.task_max_attempts": "4"}
        )
        c1 = boot(cfg)
        cluster = c1._standalone_cluster
        s = cluster.scheduler
        # the serving session: point queries plan to ONE partition
        # (bypass-eligible); its settings live in the cache key, so its
        # hits never collide with the default session's
        c2 = BallistaContext(
            f"localhost:{cluster.scheduler_port}",
            base_cfg(
                **{"ballista.shuffle.partitions": "1",
                   "ballista.tpu.single_stage_bypass": bypass}
            ),
        )
        for name, t in data.items():
            c2.register_table(name, t)
        httpd, rest_port = start_rest_server(s, "127.0.0.1", 0)
        try:
            # warmup both sessions (compile + classes)
            c1.sql(sql_q6).collect()
            c1.sql(sql_q3).collect()
            c2.sql(sql_point).collect()
            lock = threading.Lock()
            results: list = []
            threads: list = []

            def one(cls):
                submit_ctx = c2 if cls == "point" else c1
                t0 = time.time()
                ok = True
                try:
                    submit_ctx.sql(sqls[cls]).collect()
                except Exception:  # noqa: BLE001 — the artifact reports
                    ok = False  # failures; it must not die on one
                with lock:
                    results.append((cls, time.time() - t0, ok))

            ev_count_0 = s._h_dispatch_lag.labels().snapshot()[2]
            t_start = time.time()
            i = 0
            while time.time() - t_start < round_s:
                due = t_start + i / qps
                now = time.time()
                if due > now:
                    time.sleep(due - now)
                th = threading.Thread(
                    target=one, args=(mix[i % len(mix)],)
                )
                th.start()
                threads.append(th)
                i += 1
            for th in threads:
                th.join(timeout=300)
            wall = time.time() - t_start
            ev_count = (
                s._h_dispatch_lag.labels().snapshot()[2] - ev_count_0
            )
            with lock:
                got = list(results)
            completed = sum(1 for _c, _l, ok in got if ok)
            failed = sum(1 for _c, _l, ok in got if not ok)
            text = urllib.request.urlopen(
                f"http://127.0.0.1:{rest_port}/api/metrics"
            ).read().decode()
            from ballista_tpu.obs.prometheus import validate_exposition

            validate_exposition(text)
            pairs = []
            for m in re.finditer(
                r"^ballista_event_dispatch_lag_seconds_bucket"
                r'\{le="([^"]+)"\} ([0-9.e+-]+)$',
                text, re.M,
            ):
                le = float("inf") if m.group(1) == "+Inf" else float(
                    m.group(1)
                )
                pairs.append((le, float(m.group(2))))
            cs = s.result_cache.stats()
            lookups = cs["hits"] + cs["misses"]
            arm = {
                "submitted": i,
                "completed": completed,
                "failed": failed,
                "queries_per_sec": round(completed / wall, 2),
                "sched_events_per_sec": round(ev_count / wall, 1),
                "dispatch_lag_p99_s": round(
                    quantile_from_cumulative(sorted(pairs), 0.99), 5
                ),
                "cache_hit_ratio": round(cs["hits"] / lookups, 3)
                if lookups else 0.0,
                "bypass_jobs": s.obs_bypass_total,
                "client_latency_s": _percentiles(
                    [l for _c, l, ok in got if ok]
                ),
            }
            return arm
        finally:
            stop_rest_server(httpd)
            c2.close()
            c1.close()

    out["sweep"] = {
        "mix": list(mix),
        "full": run_arm("64", "true", "4"),
        "cache_off": run_arm("0", "true", "4"),
        "bypass_off": run_arm("64", "false", "4"),
        "batch_1": run_arm("64", "true", "1"),
    }
    sw = out["sweep"]
    sat = out["saturated"]
    out["verdicts"] = {
        "cache_10x_ok": out["result_cache"]["hit_10x_ok"],
        # the bypass must cut saturated small-query latency (p50) AND
        # not lose throughput
        "bypass_faster_ok": (
            sat["bypass_speedup_p50"] > 1.0
            and sat["base"]["queries_per_sec"]
            >= sat["bypass_off"]["queries_per_sec"]
        ),
        # batched grants must raise sustained queries/sec vs batch=1
        "batch_throughput_ok": sat["batch_throughput_gain"] > 1.0,
        "cache_hit_ratio_full": sw["full"]["cache_hit_ratio"],
        "all_completed": all(
            sw[a]["failed"] == 0
            for a in ("full", "cache_off", "bypass_off", "batch_1")
        ),
    }
    out["verdicts"]["pass"] = (
        out["verdicts"]["cache_10x_ok"]
        and out["verdicts"]["bypass_faster_ok"]
        and out["verdicts"]["batch_throughput_ok"]
        and out["verdicts"]["all_completed"]
    )
    return out


def _aqe_tables(seed: int, n_fact: int, n_dim: int, n_keys: int) -> dict:
    """The seeded skewed/misestimated dataset (docs/aqe.md): Zipfian
    int keys (a hot-key groupby), string join keys (forcing the
    collect-mode join whose build side the query ORDER mis-places), a
    multi-hot-key int column (splittable skew — no single irreducible
    key), and two small dimensions (one string-keyed for the wrong-side
    build, one int-keyed for the broadcast rule)."""
    import numpy as np
    import pyarrow as pa

    rng = np.random.default_rng(seed)
    # Zipf ranks clipped to the key domain: rank 1 dominates (the
    # classic hot-key groupby), the tail is long
    ranks = rng.zipf(1.5, size=n_fact)
    key = np.minimum(ranks, n_keys).astype(np.int64)
    # moderate single-hot skew for the SPLIT arm: one key carries 15%
    # of the mass, the rest uniform — at 16 buckets the hot bucket trips
    # the skew ratio, and a split genuinely shrinks it (the hot key
    # keeps its 15%, but the uniform freight sharing its bucket spreads)
    hkey = np.where(
        rng.random(n_fact) < 0.15,
        np.int64(0),
        rng.integers(1, 1000, n_fact),
    ).astype(np.int64)
    skey = pa.array([f"s{int(k) % (n_dim * 20)}" for k in key])
    fact = pa.table(
        {
            "key": pa.array(key),
            "hkey": pa.array(hkey),
            "ikey": pa.array(
                rng.integers(0, n_dim, n_fact).astype(np.int64)
            ),
            "skey": skey,
            "v": pa.array(rng.uniform(0, 100, n_fact)),
        }
    )
    dim = pa.table(
        {
            "skey": pa.array([f"s{i}" for i in range(n_dim)]),
            "attr": pa.array((np.arange(n_dim) % 25).astype(np.int64)),
        }
    )
    dim2 = pa.table(
        {
            "ikey": pa.array(np.arange(n_dim, dtype=np.int64)),
            "iattr": pa.array((np.arange(n_dim) % 25).astype(np.int64)),
        }
    )
    hdim = pa.table(
        {
            "hkey": pa.array(np.arange(1000, dtype=np.int64)),
            "hattr": pa.array((np.arange(1000) % 25).astype(np.int64)),
        }
    )
    return {"fact": fact, "dim": dim, "dim2": dim2, "hdim": hdim}


# the AQE workload (docs/aqe.md): each query provokes one policy rule
_AQE_QUERIES = {
    # wrong-side build (dim JOIN fact puts the 2M-row fact on the build
    # side of the string-keyed collect join) + Zipf groupby -> FLIP (and
    # a coalesce of the tiny agg buckets rides along)
    "skewed_join": (
        "SELECT f.key, count(*) AS c, sum(f.v) AS s "
        "FROM dim d JOIN fact f ON d.skey = f.skey "
        "GROUP BY f.key ORDER BY s DESC LIMIT 100"
    ),
    # int-keyed partitioned join against a small dimension -> BROADCAST
    "broadcast_join": (
        "SELECT d2.iattr, count(*) AS c, sum(f.v) AS s "
        "FROM fact f JOIN dim2 d2 ON f.ikey = d2.ikey "
        "GROUP BY d2.iattr ORDER BY d2.iattr"
    ),
    # over-partitioned tiny aggregation -> COALESCE toward
    # aqe_target_partition_mb (ikey tiebreak keeps the LIMIT
    # deterministic across plans — counts tie)
    "tiny_parts": (
        "SELECT f.ikey, count(*) AS c, sum(f.v) AS s "
        "FROM fact f GROUP BY f.ikey ORDER BY c DESC, f.ikey LIMIT 20"
    ),
}

# the SPLIT arm runs in its own group: a hot-bucket ratio over the
# median is structurally unreachable at the default 4 buckets (a
# 4-sample median tracks the peak), so this group plans at 16 buckets
# with the broadcast rule silenced to isolate the split behavior
_AQE_SPLIT_QUERIES = {
    "skew_split": (
        "SELECT h.hattr, count(*) AS c, sum(f.v) AS s "
        "FROM fact f JOIN hdim h ON f.hkey = h.hkey "
        "GROUP BY h.hattr ORDER BY h.hattr"
    ),
}


def run_aqe_suite() -> dict:
    """BENCH_AQE=1: adaptive-vs-static on seeded skewed/misestimated
    data (docs/aqe.md). Two arms on identical 2-executor standalone
    clusters over the same seeded dataset:

    - **static** — ``ballista.tpu.aqe=false``: one warmup pass
      (compile caches), then ITERS measured warm passes.
    - **adaptive** — ``ballista.tpu.aqe=true`` with a FRESH strategy
      store: pass 1 observes and learns (its decisions are recorded as
      the learning trace), then ITERS measured warm passes that apply
      the learned strategies from submission — the fresh-process
      adaptive-planning story, measured.

    Per query the artifact records static/adaptive wall times, the
    speedup, per-outcome adaptation counts (applied/rejected/learned/
    reverted by op), and an arm-parity check (multiset-exact: float
    aggregates compare to 1e-9 relative — the certificate class).
    A TPC-H q1/q3/q5/q6/q18 warm guardrail (AQE on vs off) rides along:
    well-estimated plans must not regress.

    Env: BENCH_AQE_SEED (7), BENCH_AQE_FACT_ROWS (1.5M),
    BENCH_AQE_TPCH_SF (0.05), BENCH_ITERS. Writes BENCH_AQE.json.
    """
    import numpy as np  # noqa: F401 — dataset gen
    import pandas as pd

    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.scheduler import aqe as aqe_mod
    from ballista_tpu.tpch import gen_all

    seed = int(os.environ.get("BENCH_AQE_SEED", "7"))
    n_fact = int(os.environ.get("BENCH_AQE_FACT_ROWS", "1500000"))
    tpch_sf = float(os.environ.get("BENCH_AQE_TPCH_SF", "0.05"))
    iters = max(2, ITERS)
    # hermetic strategy persistence: without this the suite would read
    # AND write the developer's real plan_hints.json — arms would
    # inherit each other's (and previous runs') learned strategies, and
    # bench-learned strategies for real TPC-H classes would silently
    # change later AQE-on runs in this environment
    import tempfile

    hint_dir = tempfile.mkdtemp(prefix="bench_aqe_hints_")
    prev_hint = os.environ.get("BALLISTA_TPU_HINT_CACHE")
    os.environ["BALLISTA_TPU_HINT_CACHE"] = hint_dir
    try:
        return _run_aqe_suite_hermetic(
            seed, n_fact, tpch_sf, iters, hint_dir
        )
    finally:
        # the env override must not outlive the suite even on an error
        # path — anything the process does afterward would otherwise
        # persist its real hints into the throwaway temp dir
        if prev_hint is None:
            os.environ.pop("BALLISTA_TPU_HINT_CACHE", None)
        else:
            os.environ["BALLISTA_TPU_HINT_CACHE"] = prev_hint


def _run_aqe_suite_hermetic(
    seed: int, n_fact: int, tpch_sf: float, iters: int, hint_dir: str
) -> dict:
    import tempfile

    import pandas as pd

    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.scheduler import aqe as aqe_mod
    from ballista_tpu.tpch import gen_all

    tables = _aqe_tables(seed, n_fact, n_dim=2000, n_keys=50000)

    def make_cfg(aqe_on: bool, extra: dict | None = None) -> BallistaConfig:
        cfg = (
            BallistaConfig()
            .with_setting("ballista.shuffle.partitions", "4")
            # shared with the skew monitor; 2 keeps the split rule
            # meaningful at moderate bucket counts (max > 4 x median is
            # nearly unreachable at small n)
            .with_setting("ballista.tpu.skew_ratio", "2")
            .with_setting(
                "ballista.tpu.aqe", "true" if aqe_on else "false"
            )
        )
        for k, v in (extra or {}).items():
            cfg = cfg.with_setting(k, v)
        for kv in os.environ.get("BENCH_CONFIG", "").split(","):
            if kv.strip():
                k, v = kv.split("=", 1)
                cfg = cfg.with_setting(k.strip(), v.strip())
        return cfg

    def outcome_counts(jobs) -> dict:
        agg: dict = {}
        for j in jobs:
            for d in j.aqe_decisions:
                agg.setdefault(d["outcome"], {})
                agg[d["outcome"]][d["op"]] = (
                    agg[d["outcome"]].get(d["op"], 0) + 1
                )
        return agg

    def run_arm(
        aqe_on: bool, queries: dict, data: dict, extra: dict | None = None
    ) -> dict:
        """One cluster, all queries: two warmup passes (for the adaptive
        arm: the learning pass, then the FIRST adapted pass — which pays
        the rewritten shapes' compiles exactly once), then measured warm
        passes. Both arms warm up twice so the comparison is steady
        state vs steady state. Returns per-query timings + decisions +
        results + the measured passes' retrace count (must be 0: an
        adapted query re-submitted must hit the closed compile
        vocabulary, never re-trace)."""
        from ballista_tpu.compilecache import metrics as cc_metrics

        # fresh persistence root per ARM (static ones too): the
        # in-memory store reset alone would reload a previous arm's
        # strategies from a shared hint file, and static arms must not
        # inherit an adaptive arm's executor plan hints either — every
        # arm starts from the same blank-hint state
        os.environ["BALLISTA_TPU_HINT_CACHE"] = tempfile.mkdtemp(
            dir=hint_dir
        )
        if aqe_on:
            aqe_mod.reset_store()
        ctx = BallistaContext.standalone(
            make_cfg(aqe_on, extra), n_executors=2
        )
        sched = ctx._standalone_cluster.scheduler
        # the adaptation tally below reads job.aqe_decisions after every
        # pass completed; the default obs-retention window (50 terminal
        # jobs) strips decision logs, which would silently zero the
        # counts at higher BENCH_ITERS
        sched.obs_retained_jobs = 100_000
        arm: dict = {}
        try:
            for name, t in data.items():
                ctx.register_table(name, t)
            for qn, sql in queries.items():
                jobs_of_q = []

                def one_pass():
                    t0 = time.perf_counter()
                    res = ctx.sql(sql).collect()
                    dt = time.perf_counter() - t0
                    with sched._lock:
                        job = max(
                            sched.jobs.values(),
                            key=lambda j: j.submitted_s,
                        )
                    jobs_of_q.append(job)
                    return dt, res
                learn_s, result = one_pass()
                # adaptive convergence: applying pass 1's strategies
                # re-shapes the plan, which can expose NEW signals
                # (different stages become observable) — keep passing
                # until the class's strategy set stops changing, so the
                # measured passes replay ONE stable adapted plan (and
                # its compiles happened in the convergence passes).
                # Static arms get the matching second warmup.
                adapted_first_s, result = one_pass()
                prev_specs = None
                for _ in range(5 if aqe_on else 0):
                    with sched._lock:
                        job = max(
                            sched.jobs.values(),
                            key=lambda j: j.submitted_s,
                        )
                    specs = aqe_mod.strategy_store().get(job.query_class)
                    if specs == prev_specs:
                        break
                    prev_specs = specs
                    _, result = one_pass()
                t_before = cc_metrics.snapshot().get("traces", 0)
                times = []
                for _ in range(iters):
                    dt, result = one_pass()
                    times.append(dt)
                retraces = cc_metrics.snapshot().get("traces", 0) - t_before
                arm[qn] = {
                    "first_pass_s": round(learn_s, 4),
                    "adapted_first_pass_s": round(adapted_first_s, 4),
                    "warm_s": round(sum(times) / len(times), 4),
                    "warm_best_s": round(min(times), 4),
                    "warm_retraces": int(retraces),
                    "adaptations": outcome_counts(jobs_of_q),
                    "rewrites_last_run": jobs_of_q[-1].total_rewrites,
                    "skew_flags_last_run": len(jobs_of_q[-1].skew_flags),
                    "_result": result.to_pandas(),
                }
        finally:
            ctx.close()
        return arm

    out: dict = {
        "seed": seed,
        "fact_rows": n_fact,
        "iters": iters,
        "queries": {},
    }
    split_extra = {
        "ballista.shuffle.partitions": "16",
        "ballista.tpu.aqe_broadcast_threshold_mb": "0",
    }
    static = run_arm(False, _AQE_QUERIES, tables)
    static.update(run_arm(False, _AQE_SPLIT_QUERIES, tables, split_extra))
    adaptive = run_arm(True, _AQE_QUERIES, tables)
    adaptive.update(run_arm(True, _AQE_SPLIT_QUERIES, tables, split_extra))
    for qn in list(_AQE_QUERIES) + list(_AQE_SPLIT_QUERIES):
        s, a = static[qn], adaptive[qn]
        sr, ar = s.pop("_result"), a.pop("_result")
        cols = list(sr.columns)
        sr = sr.sort_values(cols).reset_index(drop=True)
        ar = ar.sort_values(cols).reset_index(drop=True)
        parity = True
        try:
            pd.testing.assert_frame_equal(
                sr, ar, check_exact=False, rtol=1e-9
            )
        except AssertionError:
            parity = False
        out["queries"][qn] = {
            "static_warm_s": s["warm_s"],
            "adaptive_warm_s": a["warm_s"],
            "speedup": round(s["warm_s"] / max(a["warm_s"], 1e-9), 3),
            "static_best_s": s["warm_best_s"],
            "adaptive_best_s": a["warm_best_s"],
            "speedup_best": round(
                s["warm_best_s"] / max(a["warm_best_s"], 1e-9), 3
            ),
            "learning_pass_s": a["first_pass_s"],
            "adapted_first_pass_s": a["adapted_first_pass_s"],
            "adaptations": a["adaptations"],
            "rewrites_per_adapted_run": a["rewrites_last_run"],
            "skew_flags": a["skew_flags_last_run"],
            "warm_retraces": a["warm_retraces"],
            "parity_multiset_exact": parity,
        }
    out["skewed_join_speedup_ok"] = (
        out["queries"]["skewed_join"]["speedup"] >= 1.2
    )

    # -- TPC-H guardrail: well-estimated plans must not regress --------------
    tpch = gen_all(scale=tpch_sf)
    tq = {
        qn: (QDIR / f"{qn}.sql").read_text()
        for qn in ("q1", "q3", "q5", "q6", "q18")
    }
    g_static = run_arm(False, tq, tpch)
    g_adapt = run_arm(True, tq, tpch)
    guard: dict = {}
    for qn in tq:
        s, a = g_static[qn], g_adapt[qn]
        s.pop("_result"), a.pop("_result")
        guard[qn] = {
            "aqe_off_warm_s": s["warm_s"],
            "aqe_on_warm_s": a["warm_s"],
            "ratio_on_over_off": round(
                a["warm_s"] / max(s["warm_s"], 1e-9), 3
            ),
            "adaptations": a["adaptations"],
            # closed-vocabulary proof: repeat submissions of the
            # adapted query must not re-trace
            "warm_retraces": a["warm_retraces"],
        }
    out["tpch_guardrail"] = {
        "sf": tpch_sf,
        "queries": guard,
        # pass = AQE on is never a real regression (>15% slower) on any
        # tracked well-estimated query; faster is fine (tiny-SF buckets
        # legitimately coalesce)
        "no_regression": all(
            g["ratio_on_over_off"] <= 1.15 for g in guard.values()
        ),
    }
    return out


def _scrape_hist_quantiles(text: str, class_token: dict, qfn) -> dict:
    """p50/p90/p99 per query class from scraped ``_bucket`` samples —
    computed with the same interpolation the in-process histograms use."""
    import math
    import re

    bucket_re = re.compile(
        r"^(ballista_[a-z_]+_seconds)_bucket\{([^}]*)\} ([0-9.e+-]+|\+?Inf)$",
        re.M,
    )
    series: dict = {}
    for m in bucket_re.finditer(text):
        name, labels, value = m.group(1), m.group(2), float(m.group(3))
        lab = dict(
            kv.split("=", 1) for kv in labels.split(",") if "=" in kv
        )
        le_raw = lab.get("le", "").strip('"')
        le = math.inf if le_raw == "+Inf" else float(le_raw)
        cls = lab.get("class", "").strip('"')
        series.setdefault((name, cls), []).append((le, value))
    token_class = {v: k for k, v in class_token.items()}
    out: dict = {"job_latency": {}, "queue_wait": {}}
    for (name, cls), pairs in sorted(series.items()):
        if name == "ballista_job_latency_seconds":
            label = token_class.get(cls)
            if label:
                out["job_latency"][label] = {
                    "p50": round(qfn(pairs, 0.50), 4),
                    "p99": round(qfn(pairs, 0.99), 4),
                    "count": int(max(v for _le, v in pairs)),
                }
        elif name == "ballista_queue_wait_seconds":
            merged = out["queue_wait"].setdefault("_pairs", {})
            for le, v in pairs:
                merged[le] = merged.get(le, 0.0) + v
    merged = out["queue_wait"].pop("_pairs", {})
    pairs = sorted(merged.items())
    out["queue_wait"]["all"] = {
        "p50": round(qfn(pairs, 0.50), 4),
        "p90": round(qfn(pairs, 0.90), 4),
        "p99": round(qfn(pairs, 0.99), 4),
        "count": int(max((v for _le, v in pairs), default=0)),
    }
    return out


def run_compile_suite() -> dict:
    """BENCH_COMPILE=1: the cold-start suite (ISSUE 7 /
    docs/compile_cache.md). Measures, per tracked query and for the whole
    subset, what a FRESH PROCESS pays before its first result with the
    compile-latency subsystem on (prewarm + persistent XLA cache + shared
    trace cache), against three baselines:

    - **cold_first** — empty persistent cache, prewarm on: the first-ever
      run on a machine (every XLA compile real). Queries share one cache
      dir, in order, so later queries already benefit from overlapping
      programs — exactly as a fresh deployment would.
    - **cold_warm_cache** — same cache kept, fresh process per the whole
      subset: cold_s is trace + persistent-cache retrieval only (the
      production executor-restart story), warm_s the in-process steady
      state. The headline acceptance ratio is
      ``sum(cold_s) / sum(warm_best_s)``.
    - **vocabulary** — distinct-signature counts: fresh-process subset
      trace count under the default capacity ladder vs a coarser
      ``2048:4`` ladder (shape canonicalization shrinking the compiled
      vocabulary), and first-pass vs repeat-pass trace counts at git HEAD
      vs this tree (the shared trace cache killing repeat-submission
      re-traces).

    Env: BENCH_SF (default 1), BENCH_QUERIES, BENCH_ITERS,
    BENCH_COMPILE_TIMEOUT (default 1800 per child),
    BENCH_COMPILE_SKIP_HEAD=1. Writes BENCH_COMPILE.json.
    """
    import shutil

    cache_root = HERE / ".bench_compile_cache"
    timeout = int(os.environ.get("BENCH_COMPILE_TIMEOUT", 1800))

    base_env = dict(os.environ)
    base_env["PYTHONPATH"] = os.pathsep.join(
        [str(HERE)]
        + ([os.environ["PYTHONPATH"]] if os.environ.get("PYTHONPATH") else [])
    )
    base_env.pop("BENCH_COMPILE", None)
    # parquet tables: generated once, shared by every child (registration
    # and file generation are outside the query timings)
    base_env["BENCH_PARQUET"] = "1"

    def child(cache_dir, iters, extra_cfg="", queries=QUERIES, label=""):
        env = dict(base_env)
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
        cfg = "ballista.tpu.prewarm=on"
        if os.environ.get("BENCH_CONFIG"):
            cfg = os.environ["BENCH_CONFIG"] + "," + cfg
        if extra_cfg:
            cfg += "," + extra_cfg
        env["BENCH_CONFIG"] = cfg
        env["BENCH_QUERIES"] = ",".join(queries)
        return _run_child(env, iters, timeout, label or "compile")

    subset_dir = cache_root / "subset"
    shutil.rmtree(subset_dir, ignore_errors=True)
    subset_dir.mkdir(parents=True, exist_ok=True)

    out = {
        "sf": SF,
        "queries": list(QUERIES),
        "iters": ITERS,
        "head_reference": {
            # the motivating numbers (BENCH_r04, round 4, SF=1): compile
            # latency dominated cold runs before this subsystem
            "q18_cold_s": 42.1342,
            "q18_warm_s": 1.6501,
            "ratio": 25.5,
        },
    }

    # -- phase A: first-ever run, empty cache --------------------------------
    first = child(subset_dir, 1, label="compile cold-first")
    if first is None:
        raise SystemExit(1)
    out["backend"] = first["backend"]
    out["cold_first"] = {
        qn: {
            "cold_s": q["cold_s"],
            "n_signatures": q.get("n_signatures"),
            "compile_seconds": q.get("compile_seconds"),
        }
        for qn, q in first["queries"].items()
    }
    out["cold_first"]["total_cold_s"] = round(
        sum(q["cold_s"] for q in first["queries"].values()), 4
    )
    out["cold_first"]["persistent_cache_misses"] = first.get(
        "persistent_cache_misses"
    )

    # -- phase B: fresh process, kept cache (executor restart) ---------------
    warm = child(subset_dir, ITERS, label="compile warm-cache")
    if warm is None:
        raise SystemExit(1)
    qsec = {}
    for qn, q in warm["queries"].items():
        qsec[qn] = {
            "cold_s": q["cold_s"],
            "warm_s": q["warm_s"],
            "warm_best_s": q["warm_best_s"],
            "ratio": round(q["cold_s"] / max(q["warm_best_s"], 1e-9), 3),
            "n_signatures": q.get("n_signatures"),
            "compile_seconds": q.get("compile_seconds"),
            "warm_retraces": q.get("warm_retraces"),
            # tracked cost fields (docs/observability.md)
            "cpu_seconds": q.get("cpu_seconds"),
            "shuffle_bytes": q.get("shuffle_bytes"),
            "spill_bytes": q.get("spill_bytes"),
        }
    cold_total = round(
        sum(q["cold_s"] for q in warm["queries"].values()), 4
    )
    warm_total = round(
        sum(q["warm_best_s"] for q in warm["queries"].values()), 4
    )
    out["cold_warm_cache"] = qsec
    out["aggregate"] = {
        "cold_total_s": cold_total,
        "warm_total_s": warm_total,
        "ratio": round(cold_total / max(warm_total, 1e-9), 3),
        "persistent_cache_hits": warm.get("persistent_cache_hits"),
        "persistent_cache_misses": warm.get("persistent_cache_misses"),
    }

    # -- vocabulary: canonicalization + trace-cache A/Bs ---------------------
    # per-query sums (NOT the child's process total, which also counts the
    # prewarm pass's own traces — reported separately)
    n_sub = sum(
        q.get("n_signatures", 0) for q in first["queries"].values()
    )
    vocab = {
        "n_signatures_subset": n_sub,
        # process total minus per-query cold sums: prewarm plus table
        # registration/upload plus phase-A warm-pass traces — everything
        # the child traced OUTSIDE the tracked cold passes
        "non_query_traces": max(0, first.get("n_signatures", 0) - n_sub),
        "warm_retraces_subset": sum(
            q.get("warm_retraces", 0) for q in warm["queries"].values()
        ),
    }
    coarse_dir = cache_root / "coarse"
    shutil.rmtree(coarse_dir, ignore_errors=True)
    coarse_dir.mkdir(parents=True, exist_ok=True)
    coarse = child(
        coarse_dir, 1,
        extra_cfg="ballista.tpu.capacity_buckets=2048:4",
        label="compile coarse-ladder",
    )
    if coarse is not None:
        vocab["n_signatures_subset_coarse_ladder"] = sum(
            q.get("n_signatures", 0)
            for q in coarse["queries"].values()
        )
        vocab["coarse_ladder"] = "2048:4"

    # HEAD comparison: the same subset through the PR-base tree, counting
    # first-pass and repeat-pass traces — repeat-pass is what the shared
    # trace cache eliminates (fresh plan instances used to re-trace every
    # instance-held jit on every submission)
    if not os.environ.get("BENCH_COMPILE_SKIP_HEAD"):
        head = _head_trace_counts(base_env, subset_dir, timeout)
        if head is not None:
            vocab["head"] = head
            # per-query subset sum, NOT the child's process total: head
            # runs without prewarm, so including the prewarm pass's own
            # traces here would misread as a vocabulary regression
            vocab["tree"] = {
                "first_pass_traces": n_sub,
                "repeat_pass_traces": vocab["warm_retraces_subset"],
            }
    out["vocabulary"] = vocab
    return out


_HEAD_TRACE_SCRIPT = r"""
import json, os, sys, time, pathlib
import jax.monitoring
counts = {"traces": 0}
def _on(event, duration, **kw):
    if event == "/jax/core/compile/jaxpr_trace_duration":
        counts["traces"] += 1
jax.monitoring.register_event_duration_secs_listener(_on)
from ballista_tpu.exec.context import TpuContext
from ballista_tpu.config import BallistaConfig
here = pathlib.Path(os.environ["BENCH_HERE"])
qdir = here / "benchmarks" / "queries"
pdir = pathlib.Path(os.environ["BENCH_PARQUET_DIR_ABS"])
cfg = BallistaConfig().with_setting("ballista.shuffle.partitions", "1")
ctx = TpuContext(cfg)
from ballista_tpu.tpch import all_schemas
for name in all_schemas():
    ctx.register_parquet(name, str(pdir / f"{name}.parquet"))
queries = os.environ["BENCH_QUERIES"].split(",")
first = repeat = 0
for qn in queries:
    sql = (qdir / f"{qn}.sql").read_text()
    b = counts["traces"]; ctx.sql(sql).collect()
    first += counts["traces"] - b
    b = counts["traces"]; ctx.sql(sql).collect()
    repeat += counts["traces"] - b
print(json.dumps({"first_pass_traces": first,
                  "repeat_pass_traces": repeat}))
"""


def _head_trace_counts(base_env, cache_dir, timeout):
    """Trace counts for the subset at git HEAD (the PR base), measured in
    a worktree inside the repo — best-effort: None on any failure."""
    wt = HERE / ".bench_head_worktree"
    try:
        # a killed prior run can leave the path registered (its `finally`
        # never ran), which makes a plain `worktree add` fail — clear any
        # stale registration first
        subprocess.run(
            ["git", "-C", str(HERE), "worktree", "remove", "--force",
             str(wt)],
            capture_output=True, timeout=120,
        )
        subprocess.run(
            ["git", "-C", str(HERE), "worktree", "prune"],
            capture_output=True, timeout=120,
        )
        subprocess.run(
            ["git", "-C", str(HERE), "worktree", "add", "--force",
             str(wt), "HEAD"],
            capture_output=True, text=True, timeout=120, check=True,
        )
        env = dict(base_env)
        env["PYTHONPATH"] = os.pathsep.join(
            [str(wt)]
            + ([base_env["PYTHONPATH"]]
               if base_env.get("PYTHONPATH") else [])
        )
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
        env["BENCH_QUERIES"] = ",".join(QUERIES)
        env["BENCH_HERE"] = str(HERE)
        env["BENCH_PARQUET_DIR_ABS"] = str(
            pathlib.Path(
                os.environ.get("BENCH_PARQUET_DIR", HERE / "bench_data")
            ) / f"sf{SF:g}"
        )
        proc = subprocess.run(
            [sys.executable, "-c", _HEAD_TRACE_SCRIPT],
            env=env, capture_output=True, text=True, timeout=timeout,
            cwd=str(wt),
        )
        if proc.returncode != 0:
            print(
                f"head trace measurement failed:\n{proc.stderr[-2000:]}",
                file=sys.stderr,
            )
            return None
        for line in reversed(proc.stdout.strip().splitlines()):
            if line.startswith("{"):
                return json.loads(line)
        return None
    except Exception as e:  # noqa: BLE001 — strictly best-effort
        print(f"head trace measurement skipped: {e}", file=sys.stderr)
        return None
    finally:
        subprocess.run(
            ["git", "-C", str(HERE), "worktree", "remove", "--force",
             str(wt)],
            capture_output=True, timeout=120,
        )


def _run_child(env: dict, iters: int, timeout: int, label: str):
    """Run one suite in a child process, returning its parsed result dict
    or None. Shared by the device and CPU phases; captures partial output
    on timeout (the wedged-TPU diagnosis) and tolerates trailing non-JSON
    stdout noise from library atexit handlers."""
    env = dict(env)
    env.update(
        {
            "BENCH_CHILD": "1",
            "BENCH_SF": str(SF),
            "BENCH_ITERS": str(iters),
        }
    )
    # callers (run_compile_suite's child()) may pre-set a query subset;
    # only default it so that actually takes effect
    env.setdefault("BENCH_QUERIES", ",".join(QUERIES))
    try:
        proc = subprocess.run(
            [sys.executable, str(HERE / "bench.py")],
            env=env,
            capture_output=True,
            text=True,
            timeout=timeout,
        )
    except subprocess.TimeoutExpired as e:
        tail = e.stderr or ""
        if isinstance(tail, bytes):
            tail = tail.decode(errors="replace")
        print(
            f"{label} suite exceeded {timeout}s (wedged TPU runtime?); "
            f"partial stderr:\n{tail[-3000:]}",
            file=sys.stderr,
        )
        return None
    if proc.returncode != 0:
        print(f"{label} suite failed:\n{proc.stderr[-4000:]}", file=sys.stderr)
        return None
    for line in reversed(proc.stdout.strip().splitlines() or [""]):
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    print(f"{label} suite produced no JSON:\n{proc.stdout[-2000:]}",
          file=sys.stderr)
    return None


def main() -> None:
    if os.environ.get("BENCH_AQE"):
        # adaptive-vs-static on seeded skewed/misestimated data
        # (docs/aqe.md): in-process standalone clusters, one arm each
        sys.path.insert(0, str(HERE))
        res = run_aqe_suite()
        (HERE / "BENCH_AQE.json").write_text(json.dumps(res, indent=2))
        print(json.dumps(res, indent=2), file=sys.stderr)
        print(json.dumps({
            "metric": f"aqe_skewed_join_speedup_seed{res['seed']}",
            "value": res["queries"]["skewed_join"]["speedup"],
            "unit": "x",
            "skewed_join_speedup_ok": res["skewed_join_speedup_ok"],
            "tpch_no_regression": res["tpch_guardrail"]["no_regression"],
            "adaptations": res["queries"]["skewed_join"]["adaptations"],
        }))
        return
    if os.environ.get("BENCH_SERVE"):
        # serving fast-path suite (docs/serving.md): result cache,
        # single-stage bypass, batched grants — each alone + the
        # open-loop mixed sweep with ablation arms
        sys.path.insert(0, str(HERE))
        res = run_serve_suite()
        (HERE / "BENCH_SERVE.json").write_text(json.dumps(res, indent=2))
        print(json.dumps(res, indent=2), file=sys.stderr)
        print(json.dumps({
            "metric": f"serve_sf{res['sf']:g}_qps{res['qps']:g}",
            "value": res["result_cache"]["speedup"],
            "unit": "cache_hit_speedup_x",
            "pass": res["verdicts"]["pass"],
            "bypass_speedup_p50": res["saturated"]["bypass_speedup_p50"],
            "sat_qps": res["saturated"]["base"]["queries_per_sec"],
            "sat_batch1_qps": res["saturated"]["batch_1"][
                "queries_per_sec"
            ],
            "full_qps": res["sweep"]["full"]["queries_per_sec"],
            "dispatch_lag_p99_s": res["sweep"]["full"][
                "dispatch_lag_p99_s"
            ],
            "cache_hit_ratio": res["verdicts"]["cache_hit_ratio_full"],
        }))
        return
    if os.environ.get("BENCH_SLO"):
        # sustained-QPS SLO harness (docs/observability.md): in-process
        # standalone cluster + open-loop load + /api/metrics verdicts
        sys.path.insert(0, str(HERE))
        res = run_slo_suite()
        (HERE / "BENCH_SLO.json").write_text(json.dumps(res, indent=2))
        print(json.dumps(res, indent=2), file=sys.stderr)
        print(json.dumps({
            "metric": (
                f"slo_sf{res['sf']:g}_qps{res['qps']:g}_"
                f"{res['mix']['small']}_{res['mix']['large']}"
            ),
            "value": res["slo"]["large_p99_s"],
            "unit": "p99_seconds",
            "slo_pass": res["slo"]["pass"],
            "queue_wait_p90_s": res["slo"]["queue_wait_p90_s"],
            "spans_dropped_total": res["spans_dropped_total"],
        }))
        return
    if os.environ.get("BENCH_SF100"):
        # the flagship artifact toward the SF100 north-star: headline
        # queries/sec + achieved shuffle GB/s + push-vs-pull wire A/B
        sys.path.insert(0, str(HERE))
        res = run_sf100_suite()
        (HERE / "BENCH_SF100.json").write_text(json.dumps(res, indent=2))
        print(json.dumps(res, indent=2), file=sys.stderr)
        print(json.dumps({
            "metric": f"tpch_sf{res['sf']:g}_flagship_queries_per_sec",
            "value": res["headline"]["queries_per_sec"],
            "unit": "queries/s",
            "push_vs_pull_dataplane_speedup": res["push_vs_pull_dataplane"][
                "speedup"
            ],
            "shuffle_gb_s_achieved": res["shuffle_gb_s"][
                "achieved_during_headline"
            ],
        }))
        return
    if os.environ.get("BENCH_SHUFFLE"):
        # shuffle data-plane suite: self-contained, host-path dominated —
        # runs in-process and writes its own artifact
        sys.path.insert(0, str(HERE))
        res = run_shuffle_suite()
        (HERE / "BENCH_SHUFFLE.json").write_text(json.dumps(res, indent=2))
        print(json.dumps(res, indent=2), file=sys.stderr)
        best_q = max(
            res["query_ab"], key=lambda q: res["query_ab"][q]["speedup"]
        )
        print(json.dumps({
            "metric": (
                f"shuffle_pipeline_speedup_{best_q}_"
                f"nic{res['emulated_nic_gbps']:g}gbps"
            ),
            "value": res["query_ab"][best_q]["speedup"],
            "unit": "x",
            "shuffle_gb_s_fanin": res["reader_fanin"]["overlapped_none"][
                "shuffle_gb_s"
            ],
        }))
        return
    if os.environ.get("BENCH_CHILD"):
        print(json.dumps(run_suite()))
        return
    if os.environ.get("BENCH_COMPILE"):
        # cold-start suite: subprocess-per-phase (cold = a fresh process
        # by definition), writes its own artifact
        res = run_compile_suite()
        (HERE / "BENCH_COMPILE.json").write_text(json.dumps(res, indent=2))
        print(json.dumps(res, indent=2), file=sys.stderr)
        print(json.dumps({
            "metric": (
                f"tpch_sf{res['sf']:g}_cold_over_warm_"
                + "_".join(res["queries"]) + f"_{res['backend']}"
            ),
            "value": res["aggregate"]["ratio"],
            "unit": "x",
            "cold_total_s": res["aggregate"]["cold_total_s"],
            "warm_total_s": res["aggregate"]["warm_total_s"],
            "n_signatures": res["vocabulary"]["n_signatures_subset"],
        }))
        return

    # The device suite runs in a SUBPROCESS with a hard timeout: a hung
    # device op must fail this harness loudly instead of hanging the
    # driver forever. This parent stays off JAX, so the child is the
    # chip's only owner while it lives.
    device_env = dict(os.environ)
    device_env["PYTHONPATH"] = os.pathsep.join(
        [str(HERE)]
        + ([os.environ["PYTHONPATH"]] if os.environ.get("PYTHONPATH") else [])
    )
    # the device run is never a CPU run: run_suite then refuses any
    # platform but the TPU
    device_env.pop("JAX_PLATFORMS", None)
    device_run = _run_child(
        device_env,
        ITERS,
        int(os.environ.get("BENCH_DEVICE_TIMEOUT", 2700)),
        "device",
    )
    if device_run is None:
        raise SystemExit(1)

    cpu_run = None
    if not os.environ.get("BENCH_SKIP_CPU"):
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(HERE)})
        # Same warm-iteration count as the device so best-of-N variance
        # treats both backends identically. A ratio without its
        # denominator is no result: a failed CPU child fails the run.
        cpu_run = _run_child(
            env, ITERS, int(os.environ.get("BENCH_CPU_TIMEOUT", 3600)),
            "cpu",
        )
        if cpu_run is None:
            raise SystemExit(1)

    detail = {"device": device_run, "cpu": cpu_run}

    # Pinned denominator: a frozen, committed CPU-baseline artifact so
    # round-over-round ratios measure the DEVICE, not drift in a shared
    # host's CPU timings (observed ±30% swings across rounds). Freeze the
    # current live CPU suite with BENCH_FREEZE=1. Frozen baselines are
    # KEYED BY SCALE FACTOR (one file per SF) so SF=10/SF=100 runs report
    # vs_frozen_cpu against their own denominator instead of silently
    # falling back to the live CPU ratio; the legacy un-keyed file is
    # still honored for SF=1 readers of old artifacts.
    frozen_path = HERE / f"BENCH_BASELINE_SF{SF:g}.json"
    legacy_path = HERE / "BENCH_BASELINE.json"
    vs_frozen = None
    if cpu_run is not None and os.environ.get("BENCH_FREEZE"):
        frozen_path.write_text(
            json.dumps(
                {"sf": SF, "queries": sorted(QUERIES), "cpu": cpu_run},
                indent=2,
            )
        )
    for path in (frozen_path, legacy_path):
        if not path.exists():
            continue
        try:
            frozen = json.loads(path.read_text())
            if frozen.get("sf") == SF and frozen.get("queries") == sorted(
                QUERIES
            ):
                ft = sum(
                    q["warm_best_s"]
                    for q in frozen["cpu"]["queries"].values()
                )
                vs_frozen = round(ft / device_run["warm_total_s"], 3)
                detail["frozen_cpu_total_s"] = round(ft, 4)
                break
        except (json.JSONDecodeError, KeyError, TypeError):
            pass

    detail_path = HERE / (
        "BENCH_DETAIL.json" if SF == 1 else f"BENCH_SF{SF:g}_DETAIL.json"
    )
    detail_path.write_text(json.dumps(detail, indent=2))
    print(json.dumps(detail, indent=2), file=sys.stderr)

    line = {
        "metric": (
            f"tpch_sf{SF}_warm_throughput_"
            + "_".join(QUERIES)
            + f"_{device_run['backend']}"
        ),
        "value": device_run["queries_per_s"],
        "unit": "queries/sec",
    }
    if cpu_run is not None:
        # speedup on identical warm work: cpu_total / device_total
        cpu_total = sum(q["warm_best_s"] for q in cpu_run["queries"].values())
        line["vs_baseline"] = round(
            cpu_total / device_run["warm_total_s"], 3
        )
    if vs_frozen is not None:
        line["vs_frozen_cpu"] = vs_frozen
    print(json.dumps(line))


if __name__ == "__main__":
    main()
