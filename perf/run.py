#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about the cell comes from data: ``BENCHMARK.json`` names the
cell's configuration and traffic mix and the metrics it reports;
``perf/configs/<config>.json`` says how the system is deployed, over which
data set (``perf/datasets/<dataset>.py``) and under which session settings,
``perf/traffic/<traffic>.json`` what the clients send, ``perf/queries/`` holds
the templates with their plain references, ``perf/layers/<metric>.py`` the
reader of each per-layer metric. ``perf/README.md`` says how to add one.

A run makes the data from ``--seed``, deploys, warms up every query the
window may send (set-up, with all compilation), drives the closed loop for
``--seconds``, then frees the system and compares every answer the window
returned with its reference. The last line of standard output is the result;
standard error says where the run's seconds went, of the 360 it may take.
It refuses to report on anything but a TPU; ``--rehearse-sf`` runs the whole
of it on whatever JAX finds, at a small scale, and always says ``correct:
false``.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(ROOT), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import dataset  # noqa: E402
import deployments  # noqa: E402
import traffic  # noqa: E402
import verify  # noqa: E402


def say(msg: str) -> None:
    print(f"perf: {msg}", file=sys.stderr, flush=True)


def die(msg: str, code: int = 1):
    say(msg)
    raise SystemExit(code)


# the driver stops a run that is still going after this many seconds, the
# first of a checkout, which compiles, included (perf/README.md)
RUN_LIMIT_S = 360


class Stages:
    """Where the run's seconds went: each stage ends where the next begins,
    from the start of the process, so they add up to the run."""

    def __init__(self, t0: float):
        self.seconds: dict[str, float] = {}
        self._t0 = self._at = t0

    def end(self, name: str) -> float:
        """The seconds since the last stage ended, counted under ``name``."""
        now = time.time()
        took, self._at = now - self._at, now
        self.seconds[name] = self.seconds.get(name, 0.0) + took
        return took

    def line(self) -> str:
        each = ", ".join(f"{n} {s:.1f}" for n, s in self.seconds.items())
        return (f"stages: {each}; total {time.time() - self._t0:.1f} s "
                f"of {RUN_LIMIT_S}")


# -- the cell, from BENCHMARK.json ------------------------------------------


def load_cell(name: str, bench_file: pathlib.Path) -> dict:
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        die(f"no workload {name!r} in {bench_file.name}; there are "
            f"{sorted(cells)}", 2)
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]

    return {
        "name": name,
        "chips": cell["chips"],
        "config": json.loads((ROOT / config["file"]).read_text()),
        "traffic": traffic.load(cell["traffic"]),
        "end_to_end": mine(bench["end_to_end"]),
        "per_layer": mine(bench["per_layer"]),
    }


# -- the closed loop ----------------------------------------------------------


def client_loop(client, ctx, walker, templates, pool, deadline, out) -> None:
    """Send, wait for the reply, send the next. A round that was begun
    before the deadline is finished, so the window closes with the last
    reply of the last round."""
    for round_ in walker:
        if time.time() >= deadline:
            break
        for template, k in round_:
            sql = templates[template].SQL.format(**pool[template][k])
            rec = {"client": client, "template": template, "k": k,
                   "t0": time.time(), "answer": None, "error": None}
            try:
                rec["answer"] = ctx.sql(sql).collect()
            except Exception as e:  # a failed query is a result, no crash
                rec["error"] = f"{type(e).__name__}: {e}"[:500]
            rec["t1"] = time.time()
            out.append(rec)


def drive(ctx, walkers, templates, pool, seconds=math.inf) -> list:
    """One client per walker (an iterator of rounds), each until its walker
    ends or ``seconds`` have passed; returns every query's record in the
    order they completed."""
    out: list[dict] = []
    deadline = time.time() + seconds
    threads = [
        threading.Thread(
            target=client_loop,
            args=(c, ctx, w, templates, pool, deadline, out),
        )
        for c, w in enumerate(walkers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted(out, key=lambda r: r["t1"])


def warm_up(ctx, mix, templates, pool) -> int:
    """Every query the window may send, once, from one client; then, where
    there are several, one round from all clients at once, as the window
    sends them. Returns how many failed."""
    names = list(dict.fromkeys(mix["templates"]))
    every = [(t, k) for t in names for k in range(mix["pool"])]
    records = drive(ctx, [iter([every])], templates, pool)
    if mix["clients"] > 1:
        together = [(t, 0) for t in names]
        records += drive(
            ctx, [iter([together]) for _ in range(mix["clients"])],
            templates, pool,
        )
    for r in records:
        if r["error"]:
            say(f"warm-up {r['template']}[{r['k']}]: {r['error']}")
    return sum(1 for r in records if r["error"])


# -- end-to-end metrics -------------------------------------------------------


def end_to_end(name: str, obs: dict):
    done = [r for r in obs["queries"] if r["error"] is None]
    if name == "setup_s":
        return obs["setup_s"]
    if not done:
        return None
    if name == "queries_per_s":
        return len(done) / obs["window_s"]
    if name == "geomean_ms":
        means = [
            statistics.fmean((r["t1"] - r["t0"]) * 1e3
                             for r in done if r["template"] == t)
            for t in sorted({r["template"] for r in done})
        ]
        return math.exp(statistics.fmean(math.log(m) for m in means))
    die(f"BENCHMARK.json names an end-to-end metric {name!r} that "
        "perf/run.py cannot compute", 2)


# -- one run ------------------------------------------------------------------


def run_cell(args, bench_file=ROOT / "BENCHMARK.json") -> dict:
    """One run; returns the result line as a dict. With ``args.rehearse_sf``
    the look for a chip is skipped and the data is that small: ``correct`` is
    then what the comparison said, and ``main`` never prints it as such.
    ``bench_file`` is the table of cells, for a test to hand in its own."""
    rehearse = args.rehearse_sf is not None
    if not (ROOT / "ballista_tpu" / "__init__.py").exists():
        die("the system under test (ballista_tpu/) is not in this checkout")
    stages = Stages(T_PROCESS)
    cell = load_cell(args.workload, bench_file)
    cfg, mix = cell["config"], cell["traffic"]
    data = dataset.load(cfg)
    templates = traffic.load_templates(dict.fromkeys(mix["templates"]))
    pool = traffic.pool(mix, templates)
    peaks = json.loads((HERE / "peaks.json").read_text())

    dep = deployments.KINDS[cfg["deployment"]](
        cfg, cell["chips"], rehearse, bool(args.trace)
    )
    try:
        try:
            dep.start()  # standalone: finds the chip or refuses, before any work
            stages.end("start")
            tables = data.tables(cfg, args.seed, args.rehearse_sf)
            rows = {n: t.num_rows for n, t in tables.items()}
            say(f"data: {dataset.name_of(cfg)} from seed {args.seed} in "
                f"{stages.end('data'):.1f} s: "
                + ", ".join(f"{n} {r} rows" for n, r in rows.items()))
            dep.load(tables)  # daemons: the executor names its device here
        except deployments.NoChip as e:
            die(str(e), 3)
        if not rehearse and dep.device["kind"] not in peaks:
            die(f"no peaks for device kind {dep.device['kind']!r} in "
                "perf/peaks.json", 3)
        say(f"deployed {cfg['deployment']} on {dep.device} in "
            f"{stages.end('deploy'):.1f} s")
        warm_failed = warm_up(dep.ctx, mix, templates, pool)
        say(f"warm-up: {stages.end('warm-up'):.1f} s, {warm_failed} failed")

        # -- the window -------------------------------------------------
        before = dep.counters()
        traced = None
        if args.trace:
            dep.trace_start()
            traced = {"t0": time.time()}
        window_t0 = time.time()
        setup_s = window_t0 - T_PROCESS
        queries = drive(
            dep.ctx,
            [traffic.walk(mix, args.seed, c) for c in range(mix["clients"])],
            templates, pool, args.seconds,
        )
        window_t1 = max([r["t1"] for r in queries], default=time.time())
        xplane = None
        if args.trace:
            traced["t1"] = time.time()
            xplane = dep.trace_stop()
        stages.end("window")
        after = dep.counters()
        history = {}
        for table in ("system.queries", "system.task_attempts"):
            try:
                history[table] = dep.history(table)
            except Exception as e:
                say(f"{table} not readable: {type(e).__name__}: {e}")
                history[table] = None
        session = getattr(dep.ctx, "session_id", None)
        # the references need the tables as frames: taken before the
        # deployment goes, computed after it
        frames = verify.frames(tables, templates)
        del tables
        dep.stop()
        peak = dep.peak_bytes()
        stages.end("stop and history")

        # -- correct? every answer of the window, system freed ------------
        failed = sum(1 for r in queries if r["error"] is not None)
        for r in queries:
            if r["error"]:
                say(f"failed {r['template']}[{r['k']}]: {r['error']}")
        answers = [(r["template"], r["k"], r["answer"])
                   for r in queries if r["error"] is None]
        references = {
            key: templates[key[0]].reference(frames, pool[key[0]][key[1]])
            for key in sorted({(t, k) for t, k, _ in answers})
        }
        verdict = verify.judge(answers, templates, references,
                               failed + warm_failed)
        say(f"verified {len(answers)} answers against {len(references)} "
            f"references in {stages.end('verification'):.1f} s")

        obs = {
            "queries": queries, "setup_s": setup_s,
            "window_t0": window_t0, "window_t1": window_t1,
            "window_s": window_t1 - window_t0, "session_id": session,
            "jobs": history["system.queries"],
            "attempts": history["system.task_attempts"],
            "counters_before": before, "counters_after": after,
            "rows": rows, "templates": templates, "peak_bytes": peak,
            "peaks": peaks.get(dep.device["kind"]), "trace": None,
        }
        device = dict(dep.device, memory_peak_bytes=peak)
        result = {"correct": bool(verdict["correct"]),
                  "attempted": len(queries), "failed": failed,
                  "metrics": {}, "device": device}
        if args.trace:
            import reduce_trace

            if xplane is None:
                die("the profiler wrote no trace")
            obs["trace"] = reduce_trace.reduce(
                xplane, traced["t0"], traced["t1"], queries
            )
            say(f"reduced the trace in {stages.end('trace reduction'):.1f} s")
            device["busy_s"] = obs["trace"]["busy_s"]
            device["window_s"] = obs["trace"]["window_s"]
            if obs["trace"]["breakdown"]:
                result["breakdown"] = obs["trace"]["breakdown"]
            for m in cell["per_layer"]:
                reader = importlib.import_module(f"layers.{m['name']}")
                value = reader.read(obs)
                if value is not None:
                    result["metrics"][m["name"]] = {
                        "value": value, "unit": m["unit"]}
            stages.end("trace reduction")
        else:
            for m in cell["end_to_end"]:
                value = end_to_end(m["name"], obs)
                if value is not None:
                    result["metrics"][m["name"]] = {
                        "value": value, "unit": m["unit"]}
        result["compared"] = verdict["numbers"]
        say(stages.line())
        if verdict["first_mismatch"]:
            say(f"first mismatch: {verdict['first_mismatch']}")
        say(f"largest relative error by column: {verdict['by_column']}")
        for name, n in verdict["numbers"].items():
            bound = (f"limit {n['limit']}" if "limit" in n
                     else f"at least {n['at_least']}")
            say(f"compared {name} = {n['value']} ({bound})")
        return result
    finally:
        dep.cleanup()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse-sf", type=float, default=None, metavar="SF",
        help="the builder's rehearsal: any platform, this scale factor, "
        "and a last line that always says correct: false",
    )
    args = ap.parse_args()
    result = run_cell(args)
    if args.rehearse_sf is not None:
        compared = result.pop("compared")
        result.update(correct=False, rehearsal=True, compared=compared)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        sys.exit(1)
