#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the served SQL path starts,
compiles and answers correctly on the attached TPU.

    python chip_smoke.py             # one chip: phases `standalone`, `daemons`
    python chip_smoke.py --chips 4   # four chips: phase `mesh` and nothing else

Data is TPC-H SF=1 made from ``--seed`` by ``ballista_tpu.tpch`` (all eight
tables at their full column widths, nothing projected away). Every result is
compared, outside the timed region, with a plain pandas computation of the
same query over the same Arrow tables (``REFERENCES`` below, independent of
``ballista_tpu``): keys, counts and the ORDER BY order exactly, float sums
and averages to ``RTOL``.

One chip belongs to one process at a time, so this parent never imports JAX.
It runs each phase in a child that is the chip's only owner while it lives:

- ``standalone``: ``BallistaContext.standalone()`` (in-proc scheduler and
  executor over real gRPC and Flight) with the default session config;
  q1, q6 and q3, cold then warm (q18 does not fit: see STANDALONE_QUERIES).
  q1 must have gone through the Pallas one-hot kernel (trace counter, not
  ``available()``).
- ``daemons``: the README quick start as written: ``python -m
  ballista_tpu.scheduler``, ``python -m ballista_tpu.executor``, then a client
  using ``BallistaContext.remote`` over parquet files of the same data; q6
  and q3. Only the executor may own the chip: scheduler and client are started
  with ``JAX_PLATFORMS`` set to a name that is no backend, so that any
  initialisation kills them (the smoke's assertion, not how users keep them
  off the chip), and the executor's log must show platform ``tpu``.
- ``mesh`` (``--chips 4`` only): one process drives four chips; q1 and q3
  through ``BallistaContext.standalone()``, whose executor advertises four
  devices and runs the scheduler's mesh stage-chains on a mesh over all of
  them. Stage inputs and outputs must hold live rows on four distinct
  devices, and the compiled stage programs must contain the all-to-all.

Each phase prints one JSON object per line; the LAST line of standard output
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
and is printed only when every phase passed on platform ``tpu``. Any failure,
a timeout or another platform exits non-zero with the reason on stderr.
Seconds printed here are smoke observations, not benchmark results.

``--rehearse SF`` is the builder's CPU rehearsal (tiny data, any platform,
no Pallas requirement). It can never pass for a chip run: its last line says
``"ok": false, "rehearsal": true``.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import pathlib
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

HERE = pathlib.Path(__file__).resolve().parent
QDIR = HERE / "benchmarks" / "queries"
TABLES = (
    "part", "supplier", "partsupp", "customer", "orders", "lineitem",
    "nation", "region",
)
SF = 1.0
# Float sums and averages agree with the pandas reference to this relative
# tolerance. The dense q1 path sums through an f32-split matmul measured at
# ~2e-8 on the chip (ops/pallas_agg.py), so anything near 1e-9 would refuse a
# correct answer; the observed error is printed with every query.
RTOL = 1e-6
# The contract gives 1200 s, compilation included. Each phase is cut at what
# is left of this budget.
BUDGET_S = 1140.0
NO_BACKEND = "no_such_platform"
# q18 is not here: cold, its ~410 programs (31 more sort programs) cost the
# chip's host over 1000 s to compile, past the client's own 600 s job deadline
# and most of the 1200 s this script has (chip run of PR 21, PERF.md). The
# data stays SF=1; `ref_q18` stays for the PR that makes sorts cheap to compile.
STANDALONE_QUERIES = ("q1", "q6", "q3")
DAEMON_QUERIES = ("q6", "q3")
MESH_QUERIES = ("q1", "q3")


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def die(msg: str, code: int = 1) -> "NoReturn":  # noqa: F821
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


class SmokeFailure(Exception):
    """A check of this script failed. Never caught: the phase's child dies
    with it and the parent exits non-zero."""


def check(ok, msg: str) -> None:
    """``assert`` that survives ``python -O``."""
    if not ok:
        raise SmokeFailure(msg)


# -- plain pandas references (independent of ballista_tpu) -------------------

_D = datetime.date


def _rev(df):
    return df.l_extendedprice * (1 - df.l_discount)


def ref_q1(f):
    d = f["lineitem"]
    d = d[d.l_shipdate <= _D(1998, 12, 1) - datetime.timedelta(days=90)].copy()
    d["disc_price"] = _rev(d)
    d["charge"] = d.disc_price * (1 + d.l_tax)
    return (
        d.groupby(["l_returnflag", "l_linestatus"])
        .agg(
            sum_qty=("l_quantity", "sum"),
            sum_base_price=("l_extendedprice", "sum"),
            sum_disc_price=("disc_price", "sum"),
            sum_charge=("charge", "sum"),
            avg_qty=("l_quantity", "mean"),
            avg_price=("l_extendedprice", "mean"),
            avg_disc=("l_discount", "mean"),
            count_order=("l_quantity", "count"),
        )
        .reset_index()
        .sort_values(["l_returnflag", "l_linestatus"])
        .reset_index(drop=True)
    )


def ref_q6(f):
    import pandas as pd

    d = f["lineitem"]
    d = d[
        (d.l_shipdate >= _D(1994, 1, 1))
        & (d.l_shipdate < _D(1995, 1, 1))
        # BETWEEN 0.05 AND 0.07 over two-decimal values
        & (d.l_discount >= 0.05)
        & (d.l_discount <= 0.07)
        & (d.l_quantity < 24)
    ]
    return pd.DataFrame(
        {"revenue": [(d.l_extendedprice * d.l_discount).sum()]}
    )


def ref_q3(f):
    c, o, li = f["customer"], f["orders"], f["lineitem"]
    j = c[c.c_mktsegment == "BUILDING"].merge(
        o[o.o_orderdate < _D(1995, 3, 15)],
        left_on="c_custkey", right_on="o_custkey",
    )
    j = j.merge(
        li[li.l_shipdate > _D(1995, 3, 15)],
        left_on="o_orderkey", right_on="l_orderkey",
    )
    j["revenue"] = _rev(j)
    return (
        j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])
        .revenue.sum()
        .reset_index()
        .sort_values(
            ["revenue", "o_orderdate", "l_orderkey"],
            ascending=[False, True, True],
        )
        .head(10)[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]]
        .reset_index(drop=True)
    )


def ref_q18(f):
    c, o, li = f["customer"], f["orders"], f["lineitem"]
    qty = li.groupby("l_orderkey").l_quantity.sum()
    big = qty[qty > 300]
    j = o[o.o_orderkey.isin(big.index)].merge(
        c, left_on="o_custkey", right_on="c_custkey"
    )
    j["sum_qty"] = j.o_orderkey.map(big)
    return (
        j.sort_values(
            ["o_totalprice", "o_orderdate", "o_orderkey"],
            ascending=[False, True, True],
        )
        .head(100)[
            ["c_name", "c_custkey", "o_orderkey", "o_orderdate",
             "o_totalprice", "sum_qty"]
        ]
        .reset_index(drop=True)
    )


# query -> (reference, ORDER BY as (column position, ascending))
REFERENCES = {
    "q1": (ref_q1, [(0, True), (1, True)]),
    "q6": (ref_q6, []),
    "q3": (ref_q3, [(1, False), (2, True)]),
    "q18": (ref_q18, [(4, False), (3, True)]),
}
REF_COLUMNS = {
    "lineitem": [
        "l_orderkey", "l_quantity", "l_extendedprice", "l_discount",
        "l_tax", "l_returnflag", "l_linestatus", "l_shipdate",
    ],
    "orders": [
        "o_orderkey", "o_custkey", "o_orderdate", "o_shippriority",
        "o_totalprice",
    ],
    "customer": ["c_custkey", "c_name", "c_mktsegment"],
}


def frames(tables: dict) -> dict:
    """pandas frames of the columns the references read."""
    return {
        t: tables[t].select(cols).to_pandas() for t, cols in REF_COLUMNS.items()
    }


def compare(qn: str, got, want) -> float:
    """Hold ``got`` (pandas frame of the engine's answer) to the reference.
    Keys, counts and ORDER BY order exact; floats to RTOL. Returns the
    largest relative float error seen. Raises SmokeFailure otherwise."""
    import numpy as np
    from pandas.api.types import is_float_dtype

    order = REFERENCES[qn][1]
    check(len(got) == len(want), f"{qn}: {len(got)} rows, want {len(want)}")
    check(got.shape[1] == want.shape[1], f"{qn}: column count")
    # the order the query's ORDER BY fixes, on the answer as delivered
    for i in range(1, len(got)):
        for pos, asc in order:
            a, b = got.iloc[i - 1, pos], got.iloc[i, pos]
            if a == b:
                continue
            check((a < b) == asc, f"{qn}: row {i} breaks ORDER BY col {pos}")
            break
    # ties under ORDER BY leave row order open: align both sides on a total
    # order over every non-float column before comparing values
    def canon(df):
        df = df.copy()
        df.columns = range(df.shape[1])
        ordered = [p for p, _ in order]
        by = ordered + [
            i for i in df.columns
            if i not in ordered and not is_float_dtype(df[i])
        ]
        if not by:
            return df
        return df.sort_values(by, kind="stable").reset_index(drop=True)

    g, w = canon(got), canon(want)
    worst = 0.0
    for i in g.columns:
        a, b = g[i], w[i]
        if is_float_dtype(b):
            a = a.to_numpy(dtype=float)
            b = b.to_numpy(dtype=float)
            check(np.all(np.isfinite(a)), f"{qn}: col {i} not finite")
            err = np.abs(a - b) / np.maximum(np.abs(b), 1e-300)
            worst = max(worst, float(err.max()) if len(err) else 0.0)
            check(
                np.all(err <= RTOL),
                f"{qn}: col {i} relative error {err.max():.3g} > {RTOL}",
            )
        else:
            check(list(a) == list(b), f"{qn}: col {i} differs")
    return worst


# -- children that own the chip ---------------------------------------------


def device_info(rehearse: bool, want_count: int) -> dict:
    """The device as JAX reports it. Anything but a TPU of the expected
    count ends the phase at once (code 3), before any data is made."""
    import jax

    devs = jax.devices()
    info = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if not rehearse:
        if info["platform"] != "tpu":
            die(f"JAX found platform {info['platform']!r}, not a TPU", 3)
        if info["count"] != want_count:
            die(f"JAX sees {info['count']} devices, want {want_count}", 3)
    return info


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def delta(a: dict, b: dict, k: str) -> float:
    return round(b.get(k, 0) - a.get(k, 0), 4)


def run_queries(
    ctx, phase: str, queries, fr, snapshot, rehearse: bool
) -> list[dict]:
    """cold + warm per query, counters around each, comparison outside the
    timed region."""
    out = []
    for qn in queries:
        sql = (QDIR / f"{qn}.sql").read_text()
        before = snapshot()
        t0 = time.time()
        cold_t = ctx.sql(sql).collect()
        cold = time.time() - t0
        mid = snapshot()
        t0 = time.time()
        warm_t = ctx.sql(sql).collect()
        warm = time.time() - t0
        after = snapshot()
        want = REFERENCES[qn][0](fr)
        err = max(
            compare(qn, cold_t.to_pandas(), want),
            compare(qn, warm_t.to_pandas(), want),
        )
        rec = {
            "phase": phase,
            "query": qn,
            "rows": warm_t.num_rows,
            "cold_s": round(cold, 3),
            "warm_s": round(warm, 3),
            "max_rel_err": err,
            "rtol": RTOL,
            "cold_compile_s": delta(before, mid, "compile_seconds"),
            "cold_cache_hits": int(delta(before, mid, "persistent_cache_hits")),
            "cold_cache_misses": int(
                delta(before, mid, "persistent_cache_misses")
            ),
            "warm_compile_s": delta(mid, after, "compile_seconds"),
            "warm_cache_misses": int(
                delta(mid, after, "persistent_cache_misses")
            ),
            "pallas_onehot_traces": int(
                delta(before, after, "pallas_onehot_traces")
            ),
        }
        if qn == "q1" and not rehearse:
            check(
                rec["pallas_onehot_traces"] > 0,
                "q1 did not lower through the Pallas one-hot kernel",
            )
        emit(rec)
        out.append(rec)
    return out


def gen_tables(sf: float, seed: int) -> tuple[dict, float]:
    from ballista_tpu.tpch import gen_all

    t0 = time.time()
    tables = gen_all(sf, seed)
    return tables, time.time() - t0


def phase_standalone(args) -> None:
    rehearse = args.rehearse is not None
    dev = device_info(rehearse, 1)
    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.compilecache import metrics

    sf = args.rehearse or SF
    tables, gen_s = gen_tables(sf, args.seed)
    data = pathlib.Path(args.out) / "data"
    data.mkdir(parents=True, exist_ok=True)
    import pyarrow.parquet as papq

    for name, t in tables.items():  # the daemons phase reads these
        papq.write_table(t, data / f"{name}.parquet")
    fr = frames(tables)
    emit({
        "phase": "standalone", "device": dev, "sf": sf,
        "seed": args.seed, "gen_s": round(gen_s, 1),
        "rows": {n: t.num_rows for n, t in tables.items()},
    })
    ctx = BallistaContext.standalone()
    try:
        for name, t in tables.items():
            ctx.register_table(name, t)
        run_queries(
            ctx, "standalone", STANDALONE_QUERIES, fr, metrics.snapshot,
            rehearse,
        )
    finally:
        ctx.close()
    emit({
        "phase": "standalone", "ok": True, "device": dev,
        "peak_bytes_in_use": peak_bytes(),
    })


def phase_client(args) -> None:
    """The remote client of the daemons phase. Started with a platform name
    that is no backend: reaching the end proves it never initialised one."""
    import pyarrow.parquet as papq

    from ballista_tpu.client.context import BallistaContext

    data = pathlib.Path(args.out) / "data"
    fr = {
        t: papq.read_table(data / f"{t}.parquet", columns=cols).to_pandas()
        for t, cols in REF_COLUMNS.items()
    }
    ctx = BallistaContext.remote("127.0.0.1", args.scheduler_port)
    try:
        for name in TABLES:
            ctx.register_parquet(name, str(data / f"{name}.parquet"))

        def snapshot() -> dict:
            time.sleep(0.5)  # counters ride the executor's 0.1 s poll
            state = json.load(urllib.request.urlopen(
                f"http://127.0.0.1:{args.rest_port}/api/state", timeout=10
            ))
            return {
                k: float(v)
                for k, v in (state["executors"][0]["compile"] or {}).items()
            }

        run_queries(ctx, "daemons", DAEMON_QUERIES, fr, snapshot, True)
    finally:
        ctx.close()
    import jax._src.xla_bridge as xb

    check(not xb._backends, "the client initialised a JAX backend")
    emit({"phase": "daemons", "client_backend_initialised": False})


def phase_mesh(args) -> None:
    rehearse = args.rehearse is not None
    dev = device_info(rehearse, 4)
    import jax

    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.compilecache import metrics
    from ballista_tpu.parallel import stage

    sf = args.rehearse or SF
    tables, gen_s = gen_tables(sf, args.seed)
    fr = frames(tables)
    emit({
        "phase": "mesh", "device": dev, "sf": sf,
        "seed": args.seed, "gen_s": round(gen_s, 1),
    })

    # Observe every mesh stage from outside: the live rows each device holds
    # of its input and output batches, and whether the compiled program
    # holds the all-to-all.
    seen: list[dict] = []
    texts: dict[str, int] = {}

    def live_rows(batch) -> list[int]:
        """Live rows per device id; a device that holds no shard is absent."""
        per: dict[int, int] = {}
        for s in batch.valid.addressable_shards:
            per[s.device.id] = per.get(s.device.id, 0) + int(s.data.sum())
        return [per[k] for k in sorted(per)]

    def watch_stage(name):
        run = getattr(stage.MeshStageRunner, name)

        def wrapped(self, *a, **kw):
            out = run(self, *a, **kw)
            batches = [x for x in a if hasattr(x, "valid")]
            seen.append({
                "stage": name,
                "in_live_rows_per_device": [live_rows(b) for b in batches],
                "out_live_rows_per_device": live_rows(out),
            })
            return out

        setattr(stage.MeshStageRunner, name, wrapped)

    def watch_program(name):
        build = getattr(stage.MeshStageRunner, name)

        def wrapped(self, *a, **kw):
            prog = build(self, *a, **kw)

            def run(*pargs):
                if name not in texts:  # the first program of each kind
                    text = prog.lower(*pargs).compile().as_text()
                    texts[name] = text.count("all-to-all")
                return prog(*pargs)

            return run

        setattr(stage.MeshStageRunner, name, wrapped)

    for name in ("aggregate", "join", "topk", "sort_full", "window"):
        watch_stage(name)
        watch_program(f"_{name}_program")

    ctx = BallistaContext.standalone()
    try:
        cluster = ctx._standalone_cluster
        # the scheduler plans mesh stage-chains only for an executor that
        # has advertised its devices: a query racing the registration is
        # planned for the file-shuffle tier
        deadline = time.time() + 60
        while not any(
            (em.specification.n_devices or 1) == dev["count"]
            for em in cluster.scheduler.executor_manager.all_executors()
        ):
            if time.time() > deadline:
                die(f"no executor advertised {dev['count']} devices")
            time.sleep(0.1)
        for name, t in tables.items():
            ctx.register_table(name, t)
        run_queries(ctx, "mesh", MESH_QUERIES, fr, metrics.snapshot, True)
        # the executor decoded the scheduler's mesh stage-chains against
        # its own mesh over every device this process sees
        rt = cluster.executor.codec.mesh_runtime
        check(rt is not None, "the executor built no mesh runtime")
        check(rt.runner.n_dev == dev["count"], f"mesh of {rt.runner.n_dev}")
        for job in cluster.scheduler.jobs.values():
            plan = "\n".join(st.plan.display() for st in job.stages.values())
            check(
                "Mesh" in plan, f"job {job.job_id} was not a mesh plan:\n{plan}"
            )
    finally:
        ctx.close()
    n_dev = dev["count"]
    check(seen, "no mesh stage ran")
    for rec in seen:
        emit({"phase": "mesh", **rec})
        spreads = rec["in_live_rows_per_device"] + [
            rec["out_live_rows_per_device"]
        ]
        for rows in spreads:
            check(
                len(rows) == n_dev,
                f"{rec['stage']}: shards on {len(rows)} devices, not {n_dev}",
            )
            # small results (a top-k, four q1 groups) may leave a device
            # empty; a batch of thousands of rows on ONE device is the bug
            check(
                sum(rows) < 1000 or max(rows) < sum(rows),
                f"{rec['stage']}: {rows} landed wholesale on one device",
            )
    emit({"phase": "mesh", "all_to_all_ops_in_compiled_program": texts})
    check(
        any(n > 0 for n in texts.values()),
        "no compiled stage program contains an all-to-all",
    )
    emit({
        "phase": "mesh", "ok": True, "device": dev,
        "stages": sorted({r["stage"] for r in seen}),
        "peak_bytes_in_use": peak_bytes(),
    })


# -- the parent: never touches JAX ------------------------------------------


class Children:
    """Every process the smoke starts, each in its own session so that the
    whole group dies with it; reaped on every exit path."""

    def __init__(self) -> None:
        self.procs: list[subprocess.Popen] = []

    def start(self, argv, env, stdout, stderr) -> subprocess.Popen:
        p = subprocess.Popen(
            argv, env=env, stdout=stdout, stderr=stderr, text=True,
            start_new_session=True, cwd=str(HERE),
        )
        self.procs.append(p)
        return p

    def stop(self, p: subprocess.Popen, grace: float = 20.0) -> None:
        if p.poll() is None:
            try:
                os.killpg(p.pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
            try:
                p.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        p.wait()

    def stop_all(self) -> None:
        for p in reversed(self.procs):
            self.stop(p, grace=5.0)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(**extra) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(HERE)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    env.update(extra)
    return env


def run_phase_child(kids, phase, args, deadline, env, extra=()) -> list[dict]:
    """Run one ``--phase`` child to its end, passing its JSON lines through.
    Returns them parsed. Non-zero exit or the deadline fails the smoke."""
    argv = [
        sys.executable, str(HERE / "chip_smoke.py"), "--phase", phase,
        "--out", str(args.out), "--seed", str(args.seed), *extra,
    ]
    if args.rehearse is not None:
        argv += ["--rehearse", str(args.rehearse)]
    log = open(pathlib.Path(args.out) / f"{phase}.stderr.log", "w")
    p = kids.start(argv, env, subprocess.PIPE, log)
    lines: list[dict] = []

    def pump():
        for line in p.stdout:
            line = line.rstrip("\n")
            if line.startswith("{"):
                try:
                    lines.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
            print(line, flush=True)

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        rc = p.wait(timeout=max(1.0, deadline - time.time()))
    except subprocess.TimeoutExpired:
        kids.stop(p)
        die(f"phase {phase} ran past the {BUDGET_S:.0f} s budget")
    finally:
        log.close()
    t.join(timeout=10)
    if rc != 0:
        tail = (pathlib.Path(args.out) / f"{phase}.stderr.log").read_text()
        die(f"phase {phase} exited {rc}:\n{tail[-4000:]}", rc if rc > 0 else 1)
    return lines


def wait_for(what: str, probe, deadline: float, procs) -> None:
    while True:
        for name, p, logpath in procs:
            if p.poll() is not None:
                die(
                    f"{name} exited {p.returncode} while waiting for {what}:\n"
                    + pathlib.Path(logpath).read_text()[-4000:]
                )
        if probe():
            return
        if time.time() > deadline:
            die(f"timed out waiting for {what}")
        time.sleep(0.5)


def daemons_phase(kids: Children, args, deadline: float) -> dict:
    out = pathlib.Path(args.out)
    sched_port, rest_port = free_port(), free_port()
    flight_port, grpc_port = free_port(), free_port()
    work = out / "work"
    work.mkdir(parents=True, exist_ok=True)
    sched_log, exec_log = out / "scheduler.log", out / "executor.log"
    # Scheduler and client get a platform name that is no backend: any JAX
    # initialisation kills them. The executor gets the machine's default.
    off_chip = child_env(JAX_PLATFORMS=NO_BACKEND)
    with open(sched_log, "w") as sl, open(exec_log, "w") as el:
        sched = kids.start(
            [sys.executable, "-m", "ballista_tpu.scheduler",
             "--bind-host", "127.0.0.1", "--bind-port", str(sched_port),
             "--rest-port", str(rest_port)],
            off_chip, sl, subprocess.STDOUT,
        )
        watch = [("scheduler", sched, sched_log)]

        def rest_up() -> bool:
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{rest_port}/api/state", timeout=2
                ).read()
                return True
            except OSError:
                return False

        wait_for("the scheduler's REST port", rest_up, deadline, watch)
        executor = kids.start(
            [sys.executable, "-m", "ballista_tpu.executor",
             "--bind-host", "127.0.0.1", "--external-host", "127.0.0.1",
             "--bind-port", str(flight_port),
             "--bind-grpc-port", str(grpc_port),
             "--scheduler-host", "127.0.0.1",
             "--scheduler-port", str(sched_port),
             "--work-dir", str(work)],
            child_env(), el, subprocess.STDOUT,
        )
        watch.append(("executor", executor, exec_log))

        def registered() -> bool:
            state = json.load(urllib.request.urlopen(
                f"http://127.0.0.1:{rest_port}/api/state", timeout=5
            ))
            return len(state["executors"]) == 1

        wait_for("the executor to register", registered, deadline, watch)
        run_phase_child(
            kids, "client", args, deadline, off_chip,
            ("--scheduler-port", str(sched_port),
             "--rest-port", str(rest_port)),
        )
        for name, p, logpath in watch:
            if p.poll() is not None:
                die(f"{name} died during the phase (exit {p.returncode}):\n"
                    + pathlib.Path(logpath).read_text()[-4000:])
        kids.stop(executor)  # SIGTERM: it logs its device's peak memory
        kids.stop(sched)
    elog = exec_log.read_text()
    slog = sched_log.read_text()
    if "Unable to initialize backend" in slog:
        die("the scheduler tried to initialise a JAX backend:\n" + slog[-3000:])
    m = re.search(r" devices: platform=(\S+) count=(\d+) kind=(.*)", elog)
    if m is None:
        die("the executor's log names no device:\n" + elog[-3000:])
    dev = {"platform": m[1], "count": int(m[2]), "kind": m[3]}
    m = re.search(r" device peak_bytes_in_use=(\d+)", elog)
    peak = int(m[1]) if m else None
    if args.rehearse is None and dev["platform"] != "tpu":
        die(f"the executor ran on {dev['platform']!r}, not the TPU")
    rec = {
        "phase": "daemons", "ok": True, "device": dev,
        "scheduler_backend_initialised": False,
        "peak_bytes_in_use": peak,
    }
    emit(rec)
    return rec


def parent(args) -> None:
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    deadline = time.time() + BUDGET_S
    kids = Children()
    try:
        if args.chips == 4:
            lines = run_phase_child(kids, "mesh", args, deadline, child_env())
        else:
            lines = run_phase_child(
                kids, "standalone", args, deadline, child_env()
            )
            lines.append(daemons_phase(kids, args, deadline))
    finally:
        kids.stop_all()
        # SF=1 parquet and shuffle files are made anew by every run
        shutil.rmtree(out / "data", ignore_errors=True)
        shutil.rmtree(out / "work", ignore_errors=True)
    done = [r for r in lines if r.get("ok") is True and "device" in r]
    want = 1 if args.chips == 4 else 2
    if len(done) != want:
        die(f"{len(done)} of {want} phases reported ok")
    dev = done[0]["device"]
    rehearsal = args.rehearse is not None
    if not rehearsal and any(
        r["device"]["platform"] != "tpu" or r["device"]["count"] != args.chips
        for r in done
    ):
        die(f"a phase ran on another device than {args.chips} TPU chip(s)")
    final = {"ok": not rehearsal, "device": dev}
    if rehearsal:
        final["rehearsal"] = True
    emit(final)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument(
        "--out", default=str(HERE / "chiprun_out" / "chip_smoke"),
        help="logs; SF=1 parquet and shuffle files live here during a run",
    )
    ap.add_argument(
        "--rehearse", type=float, metavar="SF", default=None,
        help="CPU rehearsal at a tiny scale; never reports ok",
    )
    ap.add_argument("--phase", help=argparse.SUPPRESS)
    ap.add_argument("--scheduler-port", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--rest-port", type=int, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase is None:
        parent(args)
        return
    sys.path.insert(0, str(HERE))
    {"standalone": phase_standalone, "client": phase_client,
     "mesh": phase_mesh}[args.phase](args)


if __name__ == "__main__":
    main()
