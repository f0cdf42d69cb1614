"""Runtime lock-order witness under chaos (ISSUE 4).

A standalone two-executor cluster runs a TPC-H join with injected fetch
faults and a mid-query executor kill (``BALLISTA_LOCK_WITNESS=1`` in the
subprocess env, so every control-plane lock is a TracedLock). The kill is
timed the way the chaos acceptance test times it — after a map task
completed, while the job still runs — so lost-shuffle recovery
(``_on_shuffle_lost``'s nested SchedulerServer→StageManager acquisition)
is guaranteed to execute. Afterwards the witnessed acquisition orders
must (1) be non-empty, (2) contain no live inversion, and (3) be
consistent with racelint's static lock-order graph (shared node
vocabulary ``Class._lockfield``).

Marked ``chaos``: fault rules + the witness env are enabled in the
SUBPROCESS only; conftest keeps the pytest process inert.
"""

import pathlib
import subprocess
import sys

import pytest

from tests.conftest import CPU_MESH_ENV

SCRIPT = r"""
import pathlib
import threading
import time

from ballista_tpu.analysis import racelint, witness
from ballista_tpu.client.context import BallistaContext
from ballista_tpu.config import BallistaConfig
from ballista_tpu.testing import faults
from ballista_tpu.tpch import gen_all

assert witness.enabled(), "BALLISTA_LOCK_WITNESS must reach the subprocess"

faults.install(
    [{"point": "fetch_error", "partition": 0, "attempt": [0, 1],
      "max_fires": 2},
     # stretch the shuffle phase so the mid-query kill window is wide
     {"point": "fetch_slow", "delay_s": 0.05}],
    seed=7,
)

cfg = (
    BallistaConfig()
    .with_setting("ballista.tpu.fetch_backoff_ms", "10")
    .with_setting("ballista.shuffle.partitions", "2")
    # force real shuffle stages: under the virtual CPU mesh env the
    # planner would otherwise fuse q3 into ONE mesh stage — no shuffle
    # output to lose, no recovery path for the witness to observe
    .with_setting("ballista.tpu.collective_shuffle", "false")
)
ctx = BallistaContext.standalone(
    cfg, n_executors=2, executor_timeout_s=2.0, expiry_check_interval_s=0.5
)
cluster = ctx._standalone_cluster
sched = cluster.scheduler
for name, t in gen_all(scale=0.01).items():
    ctx.register_table(name, t)

sql = pathlib.Path("benchmarks/queries/q3.sql").read_text()


def attempt_kill_mid_query():
    # returns the job on a landed mid-query kill, None when the query
    # outran the kill window (fast machine) — the caller retries
    result = {}

    def drive():
        result["q3"] = ctx.sql(sql).collect()

    t3 = threading.Thread(target=drive)
    t3.start()
    # wait for a completed map task, then kill its owner while the job
    # runs: the scheduler must invalidate the dead executor's shuffle
    # output (_on_shuffle_lost) — the nested-lock path the witness
    # exists to observe
    victim_id = None
    deadline = time.time() + 120
    while time.time() < deadline and victim_id is None:
        for (job_id, stage_id), stage in list(
            sched.stage_manager._stages.items()
        ):
            for task in stage.tasks:
                if task.state.value == "completed" and task.executor_id:
                    victim_id = task.executor_id
                    break
            if victim_id:
                break
        time.sleep(0.005)
    job = list(sched.jobs.values())[-1]
    if victim_id is None or job.status != "running":
        t3.join(timeout=300)
        return None  # query outran the kill window — retry
    victim_idx = next(
        i for i, h in enumerate(cluster.executors)
        if h.executor.executor_id == victim_id
    )
    cluster.kill_executor(victim_idx, lose_shuffle=True)
    cluster.add_executor()  # keep 2 executors for a possible next round
    t3.join(timeout=300)
    assert not t3.is_alive(), "q3 wedged after executor kill"
    assert result["q3"].num_rows > 0, "q3 returned no rows under chaos"
    assert job.status == "completed", (job.status, job.error)
    return job


job = None
for _round in range(3):
    job = attempt_kill_mid_query()
    if job is not None:
        break
assert job is not None, "kill never landed mid-query in 3 rounds"
assert job.total_retries + job.total_recomputes >= 1, (
    "kill left no recovery trace"
)
ctx.close()

edges = witness.edges()
assert edges, "witness recorded no acquisition orders"
assert any(a == "SchedulerServer._lock" for a, _b in edges), edges
assert witness.violations() == [], witness.violations()
witness.assert_consistent(racelint.lock_order_graph().keys())
print(f"WITNESS-OK edges={sorted(edges)}")
"""


@pytest.mark.chaos
def test_witness_consistent_with_static_graph_under_chaos():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        env={**CPU_MESH_ENV, "BALLISTA_LOCK_WITNESS": "1"},
        capture_output=True,
        text=True,
        timeout=420,
        cwd=str(pathlib.Path(__file__).resolve().parent.parent),
    )
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    assert "WITNESS-OK" in proc.stdout
