"""Query-level observability (docs/observability.md, PR 10).

Tier-1 coverage for the obs/ subsystem: tracer semantics (off = no-op,
ambient nesting, ring bound, JSONL export, outbox exactly-once
discipline, SpanP round-trip), the pinned Metrics.summary()/display
format, per-operator plan instrumentation, EXPLAIN ANALYZE, the
Prometheus text renderer (parser-level validity), and — in a CPU
subprocess, like the other distributed tests — the REST API surface
(/api/state, /api/job/<id> incl. the 404 JSON body, /api/metrics) after
a real distributed run with the shipping collector + tracing on.
"""

import json
import re
import subprocess
import sys

import pytest

from ballista_tpu.obs import profile as obs_profile
from ballista_tpu.obs import prometheus as prom
from ballista_tpu.obs import trace as obs_trace

from tests.conftest import CPU_MESH_ENV


@pytest.fixture(autouse=True)
def _clean_tracer():
    obs_trace.clear()
    obs_trace.configure("off")
    obs_trace.enable_shipping(False)
    yield
    obs_trace.clear()
    obs_trace.configure("off")
    obs_trace.enable_shipping(False)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_span_without_context_is_noop():
    with obs_trace.span("anything") as s:
        assert s is None
    assert obs_trace.event("anything") is None
    assert obs_trace.snapshot() == []
    assert obs_trace.current() is None


def test_span_nesting_and_error_outcome():
    tid = obs_trace.new_trace_id()
    with obs_trace.span("root", trace_id=tid) as root:
        assert obs_trace.current() == (tid, root.span_id)
        with obs_trace.span("child", attrs={"k": 1}) as child:
            assert child.trace_id == tid
            assert child.parent_id == root.span_id
        ev = obs_trace.event("point")
        assert ev.parent_id == root.span_id and ev.start_s == ev.end_s
    assert obs_trace.current() is None
    with pytest.raises(ValueError):
        with obs_trace.span("boom", trace_id=tid):
            raise ValueError("x")
    spans = {s.name: s for s in obs_trace.snapshot()}
    assert set(spans) == {"root", "child", "point", "boom"}
    assert spans["boom"].outcome == "error"
    assert spans["boom"].attrs["error"] == "ValueError"
    assert spans["root"].outcome == "ok"
    assert spans["child"].end_s >= spans["child"].start_s


def test_ring_is_bounded():
    tid = obs_trace.new_trace_id()
    for i in range(obs_trace._RING_CAP + 50):
        obs_trace.event(f"e{i}", trace_id=tid)
    assert len(obs_trace.snapshot()) == obs_trace._RING_CAP


def test_jsonl_export(tmp_path):
    path = tmp_path / "trace.jsonl"
    obs_trace.configure(str(path))
    tid = obs_trace.new_trace_id()
    with obs_trace.span("a", trace_id=tid, attrs={"n": 3}):
        pass
    obs_trace.event("b", trace_id=tid)
    lines = path.read_text().splitlines()
    assert len(lines) == 2
    recs = [json.loads(l) for l in lines]
    assert {r["name"] for r in recs} == {"a", "b"}
    assert all(r["trace_id"] == tid for r in recs)
    assert recs[0]["status"] == "ok"
    # an unwritable path must not fail the query
    obs_trace.configure(str(tmp_path / "no" / "such" / "dir" / "t.jsonl"))
    obs_trace.event("c", trace_id=tid)  # does not raise


def test_outbox_ships_exactly_once_and_requeues():
    obs_trace.enable_shipping(True)
    tid = obs_trace.new_trace_id()
    obs_trace.event("one", trace_id=tid)
    obs_trace.event("two", trace_id=tid)
    drained = obs_trace.drain_outbox()
    assert [s.name for s in drained] == ["one", "two"]
    assert obs_trace.drain_outbox() == []
    # failed RPC path: requeue preserves order ahead of new spans
    obs_trace.requeue_outbox(drained)
    obs_trace.event("three", trace_id=tid)
    assert [s.name for s in obs_trace.drain_outbox()] == [
        "one", "two", "three"
    ]


def test_span_proto_roundtrip():
    s = obs_trace.Span(
        trace_id="t" * 32, span_id="s" * 16, parent_id="p" * 16,
        name="task_attempt", start_s=12.5, end_s=13.75,
        outcome="error", attrs={"attempt": 2, "job_id": "j1"},
    )
    p = obs_trace.span_to_proto(s)
    s2 = obs_trace.span_from_proto(p)
    assert s2.trace_id == s.trace_id and s2.span_id == s.span_id
    assert s2.parent_id == s.parent_id and s2.name == s.name
    assert s2.start_s == s.start_s and s2.end_s == s.end_s
    assert s2.outcome == "error"
    assert s2.attrs == {"attempt": "2", "job_id": "j1"}  # stringified


# ---------------------------------------------------------------------------
# pinned metrics format (satellite: stable units + sorted key order)
# ---------------------------------------------------------------------------


def test_metrics_summary_sorted_and_stable_units():
    from ballista_tpu.exec.base import Metrics

    m = Metrics()
    m.add("zebra", 2)
    m.add("alpha", 40)
    m.timers["write_time"] = 1.23456789
    m.timers["a_time"] = 0.5
    s = m.summary()
    assert list(s) == sorted(s)
    assert s["write_time"] == 1.234568  # microsecond precision, float s
    assert isinstance(s["alpha"], int) and s["alpha"] == 40


def test_metrics_display_format_pinned():
    from ballista_tpu.exec.base import ExecutionPlan, Metrics

    m = Metrics()
    m.add("output_rows", 7)
    m.add("batches", 2)
    m.timers["agg_time"] = 0.25
    # THE pinned format: sorted k=v pairs, timers suffixed with 's'
    assert m.format() == "[agg_time=0.25s, batches=2, output_rows=7]"

    class Node(ExecutionPlan):
        def describe(self):
            return "Node"

    n = Node()
    n.metrics = m
    assert n.display(with_metrics=True) == (
        "Node  metrics=[agg_time=0.25s, batches=2, output_rows=7]"
    )


def test_metrics_summary_resolves_device_scalars():
    import numpy as np

    from ballista_tpu.exec.base import Metrics

    m = Metrics()
    m.add("output_rows", np.int64(3))
    m.add("output_rows", np.int64(4))
    assert m.summary()["output_rows"] == 7


# ---------------------------------------------------------------------------
# plan instrumentation + EXPLAIN ANALYZE
# ---------------------------------------------------------------------------


def _small_ctx():
    import pyarrow as pa

    from ballista_tpu.exec.context import TpuContext

    ctx = TpuContext()
    ctx.register_table(
        "t",
        pa.table(
            {
                "k": pa.array([1, 2, 1, 3, 2, 1], type=pa.int64()),
                "v": pa.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0]),
            }
        ),
    )
    return ctx


def test_instrument_plan_meters_every_operator():
    ctx = _small_ctx()
    df = ctx.sql("select k, sum(v) as sv from t where v > 1 group by k")
    phys = ctx.create_physical_plan(df.logical, sql=None)
    obs_profile.instrument_plan(phys)
    obs_profile.instrument_plan(phys)  # idempotent
    df.collect()
    recs = obs_profile.operator_metrics(phys)
    assert len(recs) >= 3
    paths = [r["path"] for r in recs]
    assert paths[0] == "0" and len(set(paths)) == len(paths)
    for r in recs:
        if r["counters"].get("output_batches"):
            assert r["counters"]["output_rows"] > 0
            assert r["counters"]["output_bytes"] > 0
            # the shipping path's timer is the operator's own time, and
            # does not sync
            assert r["counters"]["self_s"] > 0
            assert not [k for k in r["counters"] if k.startswith("dispatch")]
            assert "elapsed" not in r["counters"]
    # the root produced the query's rows
    root = recs[0]["counters"]
    assert root["output_rows"] == 3


def test_operator_metrics_proto_roundtrip():
    recs = [
        {
            "path": "0.1",
            "operator": "FilterExec",
            "describe": "FilterExec: v > 1",
            "counters": {"output_rows": 5, "elapsed": 0.125},
        }
    ]
    back = obs_profile.metrics_from_proto(obs_profile.metrics_to_proto(recs))
    assert back == recs


def test_explain_analyze_annotates_every_operator():
    ctx = _small_ctx()
    t = ctx.sql(
        "explain analyze select k, sum(v) as sv from t where v > 1 "
        "group by k order by k"
    ).collect()
    kinds = t.column("plan_type").to_pylist()
    # "aqe" rides along since PR 15: the class token + learned-strategy
    # narration (docs/aqe.md, pinned in tests/test_aqe.py)
    assert kinds == ["physical_plan (analyzed)", "analyze_summary", "aqe"]
    body = t.column("plan").to_pylist()[0]
    for line in body.splitlines():
        assert "rows=" in line and "elapsed=" in line and "bytes=" in line, (
            f"operator line missing measured metrics: {line!r}"
        )
    summary = t.column("plan").to_pylist()[1]
    assert "total_elapsed=" in summary
    # plain EXPLAIN still works and does NOT execute
    t2 = ctx.sql("explain select k from t").collect()
    assert t2.column("plan_type").to_pylist() == [
        "logical_plan", "optimized_plan"
    ]


# ---------------------------------------------------------------------------
# prometheus text rendering (parser-level validity)
# ---------------------------------------------------------------------------

_HELP_RE = re.compile(r"^# HELP [a-zA-Z_:][a-zA-Z0-9_:]* .+$")
_TYPE_RE = re.compile(
    r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (gauge|counter|histogram)$"
)
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\")*\})?"
    r" -?[0-9.e+-]+$"
)


def parse_prometheus(text: str) -> dict:
    """Strict exposition-format parser: every line must be a valid HELP/
    TYPE header or sample; returns {metric: [(labels-str, value)]}."""
    out: dict = {}
    assert text.endswith("\n")
    for line in text.splitlines():
        if line.startswith("# HELP"):
            assert _HELP_RE.match(line), line
            continue
        if line.startswith("# TYPE"):
            assert _TYPE_RE.match(line), line
            continue
        assert _SAMPLE_RE.match(line), f"invalid sample line: {line!r}"
        name = re.split(r"[{ ]", line, 1)[0]
        out.setdefault(name, []).append(line)
    return out


def test_render_families_is_valid_exposition():
    fams = [
        ("my_gauge", "gauge", "a gauge", [({}, 1.5)]),
        ("my_counter_total", "counter", "with labels",
         [({"executor": "e-1", "counter": "x"}, 3),
          ({"executor": "e\"2\nx", "counter": "y"}, 4.25)]),
        ("weird name!", "gauge", "sanitized", [({}, 0)]),
    ]
    text = prom.render(fams)
    parsed = parse_prometheus(text)
    assert parsed["my_gauge"] == ["my_gauge 1.5"]
    assert len(parsed["my_counter_total"]) == 2
    assert "weird_name_" in parsed  # name sanitized


def test_executor_families_render():
    text = prom.render(prom.executor_families())
    parsed = parse_prometheus(text)
    assert "ballista_trace_ring_spans" in parsed


def test_metrics_server_endpoint():
    import urllib.error
    import urllib.request

    httpd, port = prom.start_metrics_server(
        prom.executor_families, "127.0.0.1", 0
    )
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/metrics"
        ).read().decode()
        parse_prometheus(body)
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope")
    finally:
        prom.stop_metrics_server(httpd)


# ---------------------------------------------------------------------------
# pluggable collector (satellite)
# ---------------------------------------------------------------------------


def test_collector_selection_from_config():
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.executor.metrics import (
        LoggingMetricsCollector,
        ShippingMetricsCollector,
        collector_for,
    )

    assert isinstance(
        collector_for(BallistaConfig()), ShippingMetricsCollector
    )
    assert collector_for(BallistaConfig()).wants_instrumentation()
    logging_cfg = BallistaConfig(
        {"ballista.tpu.metrics_collector": "logging"}
    )
    assert isinstance(collector_for(logging_cfg), LoggingMetricsCollector)
    assert not collector_for(logging_cfg).wants_instrumentation()
    override = LoggingMetricsCollector()
    assert collector_for(BallistaConfig(), override) is override
    with pytest.raises(Exception):
        BallistaConfig({"ballista.tpu.metrics_collector": "nope"})


def test_trace_config_is_case_insensitive_for_modes():
    from ballista_tpu.config import BallistaConfig

    assert BallistaConfig({"ballista.tpu.trace": "OFF"}).trace() == "off"
    assert BallistaConfig({"ballista.tpu.trace": "On"}).trace() == "on"
    assert BallistaConfig(
        {"ballista.tpu.trace": "/tmp/t.jsonl"}
    ).trace() == "/tmp/t.jsonl"
    assert BallistaConfig().trace() == "off"


def test_terminal_job_obs_payloads_are_bounded():
    """The newest N terminal jobs keep spans/op_metrics/stage_stats;
    older ones are stripped back to light JobInfo records (a long-lived
    scheduler with the default shipping collector must not grow without
    bound)."""
    from ballista_tpu.scheduler.server import JobInfo, SchedulerServer

    server = SchedulerServer(provider=None, expiry_check_interval_s=3600)
    try:
        server.obs_retained_jobs = 2
        for i in range(4):
            job = JobInfo(job_id=f"j{i}", session_id="s")
            job.trace_id = f"trace{i}"
            job.spans = {"sp": object()}
            job.op_metrics = {(1, 0): [{"counters": {}}]}
            job.stage_stats = [{"stage_id": 1}]
            with server._lock:
                server.jobs[job.job_id] = job
                server._traces[job.trace_id] = job.job_id
            server._retain_job_obs(job)
        assert not server.jobs["j0"].spans
        assert not server.jobs["j0"].op_metrics
        assert server.jobs["j0"].stage_stats is None
        assert "trace0" not in server._traces
        assert server.jobs["j3"].spans and server.jobs["j3"].stage_stats
        assert "trace3" in server._traces
    finally:
        server.shutdown()


def test_explain_analyze_parses_and_verify_still_works():
    from ballista_tpu.sql import ast
    from ballista_tpu.sql.parser import parse_sql

    stmt = parse_sql("explain analyze select 1")
    assert isinstance(stmt, ast.Explain) and stmt.analyze and not stmt.verify
    stmt = parse_sql("explain verify select 1")
    assert stmt.verify and not stmt.analyze
    stmt = parse_sql("explain select 1")
    assert not stmt.verify and not stmt.analyze


# ---------------------------------------------------------------------------
# REST surface after a real distributed run (CPU subprocess)
# ---------------------------------------------------------------------------

REST_SCRIPT = r"""
import json, urllib.error, urllib.request

import numpy as np
import pyarrow as pa

from ballista_tpu.client.context import BallistaContext
from ballista_tpu.config import BallistaConfig
from ballista_tpu.scheduler.rest import start_rest_server, stop_rest_server

# file-shuffle tier pinned: on the virtual mesh the query fuses into one
# stage, which the single-stage bypass runs with no stage bookkeeping, and
# this test is about the per-stage stats of a multi-stage run
cfg = (BallistaConfig()
       .with_setting("ballista.shuffle.partitions", "2")
       .with_setting("ballista.tpu.collective_shuffle", "false")
       .with_setting("ballista.tpu.trace", "on"))
ctx = BallistaContext.standalone(cfg, n_executors=2)
n = 4000
r = np.random.default_rng(7)
ctx.register_table("pts", pa.table({
    "k": pa.array((np.arange(n) % 5).astype(np.int64)),
    "v": pa.array(r.uniform(0, 10, n)),
}))
sched = ctx._standalone_cluster.scheduler
httpd, port = start_rest_server(sched, "127.0.0.1", 0)
base = f"http://127.0.0.1:{port}"

t = ctx.sql("select k, sum(v) s from pts group by k order by k").collect()
assert t.num_rows == 5

# /api/state: uptime_s + per-executor last_heartbeat_age_s
state = json.load(urllib.request.urlopen(base + "/api/state"))
assert isinstance(state["uptime_s"], (int, float)) and state["uptime_s"] >= 0
assert len(state["executors"]) == 2
for e in state["executors"]:
    assert e["last_heartbeat_age_s"] is not None

# /api/job/<id>: stats + operator metrics + span tree
job_id = next(iter(sched.jobs))
detail = json.load(urllib.request.urlopen(base + f"/api/job/{job_id}"))
assert detail["status"] == "completed"
assert detail["trace_id"]
# the DAG view (status UI) keeps its shape...
assert all("plan" in st and "depends_on" in st for st in detail["stages"])
# ...and the stats view serves per-stage / per-task rows+bytes+attempts
stats = detail["stage_stats"]
assert stats and all("tasks" in st for st in stats)
final = [st for st in stats if st["stage_id"] == detail["final_stage_id"]]
assert final and sum(
    tk["output_rows"] for tk in final[0]["tasks"]
) == 5  # per-partition rows served
assert detail["operator_metrics"], "no shipped operator metrics"
some = next(iter(detail["operator_metrics"].values()))
assert any("output_rows" in r["counters"] for r in some)
# each operator's own time, the task's root too (obs.trace.stretch)
for recs in detail["operator_metrics"].values():
    assert not [k for r in recs for k in r["counters"]
                if k.startswith("dispatch")]
    assert recs[0]["operator"] == "ShuffleWriterExec"
    assert recs[0]["counters"]["self_s"] > 0, recs[0]
spans = detail["spans"]
names = {s["name"] for s in spans}
assert {"job", "stage", "task_attempt"} <= names, names
ids = {s["span_id"] for s in spans}
assert all((not s["parent_id"]) or s["parent_id"] in ids for s in spans)
assert len({s["trace_id"] for s in spans}) == 1

# unknown job: 404 with a JSON body
try:
    urllib.request.urlopen(base + "/api/job/doesnotexist")
    raise SystemExit("expected 404")
except urllib.error.HTTPError as e:
    assert e.code == 404
    body = json.loads(e.read().decode())
    assert body["error"] == "unknown job" and body["job_id"] == "doesnotexist"

# unknown path: 404 JSON too
try:
    urllib.request.urlopen(base + "/api/nope")
    raise SystemExit("expected 404")
except urllib.error.HTTPError as e:
    assert e.code == 404 and json.loads(e.read().decode())["error"] == "not found"

# /api/metrics: valid Prometheus exposition incl. the required series
res = urllib.request.urlopen(base + "/api/metrics")
assert res.headers["Content-Type"].startswith("text/plain")
text = res.read().decode()
print("METRICS-BEGIN")
print(text, end="")
print("METRICS-END")
stop_rest_server(httpd)
ctx.close()
print("REST-OK")
"""


def test_rest_api_after_distributed_run():
    proc = subprocess.run(
        [sys.executable, "-c", REST_SCRIPT],
        env=CPU_MESH_ENV,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "REST-OK" in proc.stdout
    # parser-level validation of the scraped exposition text, HERE in the
    # test process (the acceptance bar: /api/metrics serves VALID
    # Prometheus text including compile/shuffle/retry/queue-depth series)
    text = proc.stdout.split("METRICS-BEGIN\n", 1)[1].split("METRICS-END", 1)[0]
    parsed = parse_prometheus(text)
    for required in (
        "ballista_uptime_seconds",
        "ballista_executors_alive",
        "ballista_task_slots",
        "ballista_jobs",
        "ballista_task_retries_total",
        "ballista_recomputes_total",
        "ballista_event_queue_depth",
        "ballista_inflight_tasks",
        "ballista_executor_compile",
        "ballista_task_counter_total",
    ):
        assert required in parsed, f"missing series {required}"
    # shuffle counters made it through task-metric aggregation
    assert any(
        'counter="write_time"' in l or 'counter="fetched_bytes"' in l
        for l in parsed["ballista_task_counter_total"]
    ), parsed["ballista_task_counter_total"]
