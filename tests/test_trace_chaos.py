"""Chaos trace acceptance (docs/observability.md): recovery has a SHAPE.

A two-executor standalone cluster runs TPC-H q3 with tracing ON while a
map-output producer dies mid-query (producer_kill breaks one shuffle
stream mid-file, then the same executor is killed outright — loops
stopped, Flight down, shuffle files deleted). The bit-exactness of that
recovery is proven by tests/test_chaos_recovery.py / test_chaos_eager.py;
THIS test asserts what the trace says about it: one trace_id connects
submit -> stage -> task attempts (including the post-kill re-runs, which
carry the SAME trace_id with new attempt spans) -> recompute -> promote,
the span tree is fully connected, and eager-shuffle polling spans nest
under their consumer task span.

Runs in a subprocess (cleaned JAX-on-CPU env, single device so stage
plans keep real shuffle boundaries) like the other distributed tests;
fault rules are installed programmatically inside it — the conftest
guard keeps the pytest process injection-free.
"""

import subprocess
import sys

import pytest

from tests.conftest import CPU_MESH_ENV

SCRIPT = r"""
import pathlib
import threading
import time

from ballista_tpu.client.context import BallistaContext
from ballista_tpu.config import BallistaConfig
from ballista_tpu.testing import faults
from ballista_tpu.tpch import gen_all

QDIR = pathlib.Path("benchmarks/queries")
SF = 0.01
data = gen_all(scale=SF)

cfg = BallistaConfig()
for k, v in {
    "ballista.shuffle.partitions": "2",
    "ballista.tpu.fetch_backoff_ms": "10",
    # small device batches + coalescing OFF -> multi-batch shuffle
    # files/streams, so producer_kill breaks a stream genuinely mid-file
    # (the kill window is then a real in-flight position, not a race
    # against sub-second warm queries)
    "ballista.tpu.batch_rows": "4096",
    "ballista.tpu.shuffle_target_batch_mb": "0",
    "ballista.tpu.trace": "on",
}.items():
    cfg = cfg.with_setting(k, v)
ctx = BallistaContext.standalone(
    cfg, n_executors=2, executor_timeout_s=2.0,
    expiry_check_interval_s=0.5,
)
for name, t in data.items():
    ctx.register_table(name, t)
cluster = ctx._standalone_cluster
sched = cluster.scheduler

# warm pass: compiles land in the jit/XLA caches so the CHAOS run below
# spends its time executing, not compiling (test_chaos_recovery warms the
# same way via its clean pass)
warm = ctx.sql((QDIR / "q3.sql").read_text()).collect()
assert warm.num_rows > 0
warm_jobs = set(sched.jobs)

# ONE map-output stream breaks after >= 1 batch flowed to a consumer; the
# slow-fetch rule stretches the shuffle phase so the follow-up executor
# kill lands mid-query deterministically (same shape as test_chaos_eager)
faults.install(
    [
        {"point": "producer_kill", "after_batches": 1, "max_fires": 1},
        {"point": "fetch_slow", "delay_s": 0.03},
    ],
    seed=11,
)

results = {}
errors = []


def drive():
    try:
        results["q3"] = ctx.sql(
            (QDIR / "q3.sql").read_text()
        ).collect().to_pandas()
    except Exception as e:  # noqa: BLE001
        errors.append(repr(e))


t3 = threading.Thread(target=drive)
t3.start()

# wait for the injected mid-stream break, then kill the executor whose
# file was being served (the path rides in the injection log) — the
# crashed-machine shape: its shuffle files die with it
inj = faults.active()
victim_path = None
deadline = time.time() + 180
while time.time() < deadline and victim_path is None:
    for point, key in list(inj.log):
        if point == "producer_kill":
            victim_path = key[4]
            break
    time.sleep(0.005)
assert victim_path is not None, "producer_kill never fired"
victim_idx = next(
    i for i, h in enumerate(cluster.executors)
    if victim_path.startswith(h.work_dir)
)
job = next(j for jid, j in sched.jobs.items() if jid not in warm_jobs)
assert job.status == "running", f"job finished before the kill ({job.status})"
killed = cluster.kill_executor(victim_idx, lose_shuffle=True)
print("KILLED", victim_idx, killed)
t3.join(timeout=300)
assert not t3.is_alive(), "q3 wedged after executor kill"
assert not errors, errors
assert len(results["q3"]) > 0

jobs = list(sched.jobs.values())
assert all(j.status == "completed" for j in jobs), [
    (j.job_id, j.status, j.error) for j in jobs
]
recovery = sum(j.total_retries + j.total_recomputes for j in jobs)
assert recovery >= 1, "kill left no retry/recompute trace"

# give the surviving executor's next poll a beat to ship the last spans
time.sleep(1.0)

spans = sched.job_trace(job.job_id)
assert spans, "traced job produced no spans"

# (1) ONE trace id over the whole recovery
tids = {s["trace_id"] for s in spans}
assert tids == {job.trace_id}, tids

# (2) the tree is CONNECTED: exactly one root (the job span), and every
# parent_id resolves to a recorded span
ids = {s["span_id"] for s in spans}
roots = [s for s in spans if not s["parent_id"]]
assert [s["name"] for s in roots] == ["job"], roots
orphans = [s for s in spans if s["parent_id"] and s["parent_id"] not in ids]
assert not orphans, [(s["name"], s["parent_id"]) for s in orphans]

names = {s["name"] for s in spans}
# (3) the recovery shape: submit (plan under the job root) -> stage ->
# attempts -> recompute -> promote, all present in ONE tree
for required in ("job", "plan", "stage", "task_attempt", "recompute",
                 "promote"):
    assert required in names, f"missing {required!r} in {sorted(names)}"

# (4) the killed producer's re-run carries the SAME trace_id with a NEW
# attempt span: some (stage, partition) has >= 2 task_attempt spans (the
# kill failed an in-flight attempt and/or invalidated a completed one —
# either way the task re-ran under the same trace)
attempts = {}
for s in spans:
    if s["name"] == "task_attempt":
        key = (s["attrs"]["stage_id"], s["attrs"]["partition"])
        attempts.setdefault(key, []).append(s)
multi = {k: v for k, v in attempts.items() if len(v) >= 2}
assert multi, "no task ran twice despite kill-driven recovery"
for key, sp in multi.items():
    assert len({x["trace_id"] for x in sp}) == 1
    assert len({x["span_id"] for x in sp}) == len(sp)

# (5) task_attempt spans parent to their stage's span
stage_span_ids = {s["span_id"] for s in spans if s["name"] == "stage"}
for s in spans:
    if s["name"] == "task_attempt":
        assert s["parent_id"] in stage_span_ids

# (6) eager-shuffle polling spans nest under the consumer task span
task_span_ids = {s["span_id"] for s in spans if s["name"] == "task_attempt"}
eager = [s for s in spans if s["name"] == "eager_poll"]
for s in eager:
    assert s["parent_id"] in task_span_ids, s

# (7) the recompute span sits under the invalidated producing stage
recomputes = [s for s in spans if s["name"] == "recompute"]
for s in recomputes:
    assert s["parent_id"] in stage_span_ids
    assert int(s["attrs"]["reopened"]) >= 1

# (8) the failed/duplicate attempt is visible: at least one task_attempt
# or shuffle_fetch recorded outcome=error (the broken stream), and the
# root closed ok (the job recovered)
assert any(
    s["status"] == "error"
    for s in spans
    if s["name"] in ("task_attempt", "shuffle_fetch", "flight_serve")
), "no error-outcome span from the broken stream"
assert roots[0]["status"] == "ok"

print("N-SPANS", len(spans))
ctx.close()
faults.install(None)
print("TRACE-CHAOS-OK")
"""


@pytest.mark.chaos
def test_executor_kill_recovery_produces_connected_span_tree():
    # single CPU device: stage plans keep real shuffle boundaries (the
    # virtual-mesh env fuses whole chains into near-instant single-stage
    # plans, leaving no mid-query kill window)
    env = {k: v for k, v in CPU_MESH_ENV.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "TRACE-CHAOS-OK" in proc.stdout
