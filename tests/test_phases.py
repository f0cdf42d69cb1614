"""Host phases and program names (docs/observability.md, PR 25).

``obs.trace.phase`` is one call with three outputs: a profiler annotation,
the ``phase.*`` counters in ``compilecache/metrics.py`` and, under
``ballista.tpu.trace``, the enclosing ``task_attempt`` span's attrs. The
unit tests here hold its contract (a closed list, leaves that never nest,
nothing minted with tracing off, counters that add up across threads); the
served-path tests run q1, q6 and q3 at SF 0.002 through a standalone cluster
in ONE subprocess (a clean process: every program compiles, and is named,
there) and assert on what it printed.
"""

import json
import os
import subprocess
import sys
import threading

import jax.numpy as jnp
import pytest

from ballista_tpu.compilecache import metrics
from ballista_tpu.obs import trace as obs_trace


def _phase_counters() -> dict:
    return {k: v for k, v in metrics.snapshot().items()
            if k.startswith("phase.")}


def _delta(before: dict, after: dict) -> dict:
    return {k: round(v - before.get(k, 0), 6) for k, v in after.items()
            if v != before.get(k, 0)}


# -- the contract of one phase ------------------------------------------------


def test_phases_are_a_closed_list():
    assert len(set(obs_trace.PHASES)) == len(obs_trace.PHASES) == 19
    with pytest.raises(ValueError, match="not in obs.trace.PHASES"):
        obs_trace.phase("task.something_new")


@pytest.mark.parametrize(
    "outer,inner",
    [("task.d2h", "task.d2h"), ("task.shuffle_write", "task.d2h"),
     ("client.submit", "scheduler.plan")],
)
def test_phases_never_nest(outer, inner):
    """A phase inside a phase would take every label under it on a trace
    (perf/reduce_trace.py gives a gap to the event that covers most of it):
    under the tests that is an error at the site that did it."""
    with obs_trace.phase(outer):
        with pytest.raises(AssertionError, match="phases are leaves"):
            with obs_trace.phase(inner):
                pass
    # the failed entry left the thread clean: the next phase enters
    with obs_trace.phase(inner):
        pass


def test_nested_phase_outside_the_tests_counts_and_does_nothing(monkeypatch):
    monkeypatch.delenv("PYTEST_CURRENT_TEST")
    before = _phase_counters()
    with obs_trace.phase("task.shuffle_write"):
        with obs_trace.phase("task.d2h", nbytes=8, site="nested"):
            pass
    d = _delta(before, _phase_counters())
    assert d["phase.nested"] == 1
    assert d["phase.task.shuffle_write.count"] == 1
    assert not any(k.startswith("phase.task.d2h") for k in d), d


def test_phase_with_tracing_off_mints_no_span():
    obs_trace.clear()
    assert obs_trace.current() is None
    before = _phase_counters()
    with obs_trace.phase("task.h2d", nbytes=100):
        pass
    assert obs_trace.ring_size() == 0 and obs_trace.snapshot() == []
    assert obs_trace.drain_outbox() == []
    d = _delta(before, _phase_counters())
    assert d["phase.task.h2d.count"] == 1 and d["phase.task.h2d.bytes"] == 100
    # an empty phase is under the snapshot's 1e-4 s rounding
    assert d.get("phase.task.h2d.seconds", 0) >= 0


def test_phase_counters_add_up_across_threads():
    """More threads than cores and a short switch interval: a lost update
    under the counter lock would break the totals."""
    n_threads, n_each = 16, 400
    before = _phase_counters()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work():
            for _ in range(n_each):
                with obs_trace.phase("task.d2h", nbytes=3, site="threads"):
                    pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    d = _delta(before, _phase_counters())
    total = n_threads * n_each
    assert d["phase.task.d2h.count"] == total
    assert d["phase.task.d2h.count:threads"] == total
    assert d["phase.task.d2h.bytes"] == 3 * total
    assert d["phase.task.d2h.bytes:threads"] == 3 * total


def test_only_d2h_is_counted_by_site():
    before = _phase_counters()
    with obs_trace.phase("task.shuffle_fetch", site="labels-only"):
        pass
    d = _delta(before, _phase_counters())
    assert d["phase.task.shuffle_fetch.count"] == 1
    assert not any(":" in k for k in d), d


def test_phase_adds_its_seconds_to_the_task_attempt_span():
    """Under an ambient trace the nearest ``task_attempt`` gets
    ``phase.<name>_s``, through spans opened in between; no span a phase."""
    obs_trace.clear()
    tid = obs_trace.new_trace_id()
    with obs_trace.span("task_attempt", trace_id=tid) as attempt:
        with obs_trace.phase("task.d2h", site="a"):
            pass
        with obs_trace.span("spill"):
            with obs_trace.phase("task.d2h", site="b"):
                pass
            with obs_trace.phase("task.h2d"):
                pass
    assert set(attempt.attrs) == {"phase.task.d2h_s", "phase.task.h2d_s"}
    assert attempt.attrs["phase.task.d2h_s"] >= 0
    assert [s.name for s in obs_trace.snapshot()] == ["spill", "task_attempt"]
    # a span that is no task attempt collects nothing
    with obs_trace.span("explain_analyze", trace_id=tid) as other:
        with obs_trace.phase("task.d2h", site="a"):
            pass
    assert other.attrs == {}
    obs_trace.clear()


@pytest.mark.parametrize("how", ["fetch_arrays", "read_array", "to_host"])
def test_device_reads_are_d2h_phases_with_a_site(how):
    from ballista_tpu.ops.fetch import fetch_arrays, read_array

    before = _phase_counters()
    if how == "fetch_arrays":
        out = fetch_arrays([jnp.arange(4), jnp.ones(2)], site="t.fetch")
        assert [a.tolist() for a in out] == [[0, 1, 2, 3], [1.0, 1.0]]
        site, nbytes = "t.fetch", 6 * 8  # one f64 buffer for both
    elif how == "read_array":
        assert read_array(jnp.arange(3, dtype=jnp.int32), "t.read").sum() == 3
        site, nbytes = "t.read", 12
    else:
        import pyarrow as pa

        from ballista_tpu.columnar.arrow_interop import (
            batch_from_arrow,
            batch_to_arrow,
        )

        b = batch_from_arrow(pa.table({"a": pa.array([1, 2, 3])}))
        assert batch_to_arrow(b, site="t.rows").num_rows == 3
        site, nbytes = "t.rows", None
    d = _delta(before, _phase_counters())
    assert d["phase.task.d2h.count"] == d[f"phase.task.d2h.count:{site}"] == 1
    if nbytes is not None:
        assert d[f"phase.task.d2h.bytes:{site}"] == nbytes
    else:
        assert d["phase.task.scan_host.count"] == 1
        assert d["phase.task.h2d.count"] == 1 and d["phase.task.h2d.bytes"] > 0


# -- the served path, once, in a clean process --------------------------------

SERVED = r"""
import collections, json, logging, re, threading

import jax

jax.config.update("jax_log_compiles", True)
compiled = []


class Names(logging.Handler):
    def emit(self, record):
        m = re.match(r"Compiling (\S+) with global shapes", record.getMessage())
        if m:
            compiled.append(m[1])


for name in ("jax._src.interpreters.pxla", "jax._src.dispatch"):
    lg = logging.getLogger(name)
    lg.addHandler(Names())
    lg.propagate = False

from ballista_tpu import tpch
from ballista_tpu.client.context import BallistaContext
from ballista_tpu.compilecache import metrics
from ballista_tpu.config import BallistaConfig
from ballista_tpu.obs import trace as obs_trace

# every blocking read of a device value, and whether a task.d2h phase
# brackets it (jax resolves np.asarray / int() / device_get through _value)
from jax._src import array as jarray

bare = collections.Counter()
_value = jarray.ArrayImpl._value.fget


def watched(self):
    if self._npy_value is None and getattr(
        obs_trace._TLS, "in_phase", None
    ) != "task.d2h":
        import traceback

        ours = [f for f in traceback.extract_stack()
                if "/ballista_tpu/" in f.filename]
        bare[f"{ours[-1].filename.split('/ballista_tpu/')[1]}:"
             f"{ours[-1].lineno}" if ours else "outside"] += 1
    return _value(self)


jarray.ArrayImpl._value = property(watched)


def phases():
    return {k: v for k, v in metrics.snapshot().items()
            if k.startswith("phase.")}


cfg = BallistaConfig().with_setting("ballista.tpu.trace", "on")
ctx = BallistaContext.standalone(cfg, concurrent_tasks=4)
for name, table in tpch.gen_all(0.002, 7).items():
    ctx.register_table(name, table)
sched = ctx._standalone_cluster.scheduler
sql = {q: open(f"benchmarks/queries/{q}.sql").read() for q in ("q1", "q6", "q3", "q13")}
rounds = []
for _ in range(3):  # the first compiles and learns; two more repeat
    before = phases()
    rows = {q: ctx.sql(text).collect().num_rows for q, text in sql.items()}
    after = phases()
    rounds.append({"rows": rows, "delta": {
        k: round(v - before.get(k, 0), 6) for k, v in after.items()
        if v != before.get(k, 0)}})
attempts = ctx._system_table_rows("system.task_attempts")
spans = [s for job_id in list(sched.jobs) for s in sched.job_trace(job_id)]
ctx.close()
print("RESULT " + json.dumps({
    "compiled": compiled, "bare_reads": dict(bare), "rounds": rounds,
    "counters": phases(), "attempts": attempts,
    "spans": [s for s in spans if s["name"] == "task_attempt"],
}))
"""


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    # hint persistence on (the tests' default is off), or no mark would
    # start the store's writer and its phase would never be reached
    env.update(PYTHONPATH=root, JAX_PLATFORMS="cpu",
               BALLISTA_TPU_HINT_CACHE=str(tmp_path_factory.mktemp("hints")))
    proc = subprocess.run(
        [sys.executable, "-c", SERVED], env=env, cwd=root,
        capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("RESULT "))
    out = json.loads(line[len("RESULT "):])
    assert [r["rows"] for r in out["rounds"]] == [
        {"q1": 4, "q6": 1, "q3": 10, "q13": 19}] * 3
    return out


@pytest.mark.parametrize("name", obs_trace.PHASES)
def test_served_path_reaches_every_phase(served, name):
    """Standalone, pull-staged, four templates: client, scheduler, poll
    loop, scan, both transfers, shuffle both ways, hints and report; q13
    for the string predicate over a dictionary."""
    assert served["counters"].get(f"phase.{name}.count", 0) > 0
    assert served["counters"].get(f"phase.{name}.seconds", 0) >= 0


@pytest.mark.parametrize(
    "name", ["client.fetch_results", "task.decode", "task.scan_host",
             "task.h2d", "task.d2h", "task.shuffle_write"])
def test_served_path_counts_bytes(served, name):
    assert served["counters"][f"phase.{name}.bytes"] > 0


def test_d2h_sites_cover_every_read_and_sum_to_the_total(served):
    c = served["counters"]
    sites = {k.split(":", 1)[1]: v for k, v in c.items()
             if k.startswith("phase.task.d2h.count:")}
    assert sum(sites.values()) == c["phase.task.d2h.count"]
    assert {"shuffle_write.rows", "deferred_checks", "operator_metrics",
            "join.build_flags"} <= set(sites), sites
    # the hash split reads ids, rows and null masks in one round trip
    assert not {"shuffle_write.pids", "shuffle_write.valid"} & set(sites)
    assert "fetch" not in sites, "a fetch_arrays caller gave no site"


def test_no_device_read_outside_a_d2h_phase(served):
    assert served["bare_reads"] == {}


def test_d2h_reads_repeat_exactly(served):
    """The round trips a warm query makes are a property of its plan: the
    second and third rounds read the same sites the same number of times."""
    def reads(r):
        return {k: v for k, v in r["delta"].items()
                if k.startswith("phase.task.d2h.count")}

    assert reads(served["rounds"][1]) == reads(served["rounds"][2])
    assert reads(served["rounds"][1])["phase.task.d2h.count"] > 0


def test_no_compiled_program_is_anonymous(served):
    names = {n.removeprefix("jit(").removesuffix(")")
             for n in served["compiled"]}
    assert len(names) >= 20, names
    bad = {n for n in names
           if n in ("_lambda", "_lambda_", "f", "run", "fn", "<lambda>")
           or "lambda" in n}
    assert not bad, bad
    # the three templates' own programs, by the names the trace will show
    assert {"pipeline_filter_project", "join_probe_counts", "join_expand",
            "repartition_hash", "fetch_concat_f64", "sort_argsort",
            "_build_finish", "_dense_agg"} <= names, names


def test_task_attempt_spans_carry_phase_seconds_within_wall(served):
    inside = ("task.scan_host", "task.h2d", "task.d2h",
              "task.shuffle_write", "task.shuffle_fetch")
    wall = {(a["job_id"], int(a["stage_id"]), int(a["partition"])):
            float((a.get("cost") or a)["wall_seconds"])
            for a in served["attempts"]}
    spans = served["spans"]
    assert spans and wall
    with_phases = 0
    for s in spans:
        attrs = s["attrs"]
        named = sum(float(attrs.get(f"phase.{p}_s", 0)) for p in inside)
        assert named <= float(s["end_s"]) - float(s["start_s"]) + 1e-3, s
        key = (attrs["job_id"], int(attrs["stage_id"]),
               int(attrs["partition"]))
        if key in wall:
            assert named <= wall[key] + 1e-3, (s, wall[key])
        with_phases += any(k.startswith("phase.task.") for k in attrs)
    assert with_phases >= len(spans) // 2
    assert any("phase.task.d2h_s" in s["attrs"] for s in spans)
