"""Process lifecycle: real scheduler + executor processes via the
``python -m`` entrypoints, REST /state, KEDA scaler, shuffle TTL cleanup.

ref scheduler/src/main.rs:65-198, executor/src/main.rs:64-296,
api/handlers.rs:34-57, scheduler_server/external_scaler.rs:31-66.
"""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

from tests.conftest import CPU_MESH_ENV


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_cleanup_ttl(tmp_path):
    """Expired job dirs are deleted; fresh ones survive (ref main.rs:205-257)."""
    from ballista_tpu.executor.cleanup import clean_shuffle_data

    old_job = tmp_path / "job-old" / "1" / "0"
    old_job.mkdir(parents=True)
    (old_job / "data-0.arrow").write_bytes(b"x")
    new_job = tmp_path / "job-new" / "1" / "0"
    new_job.mkdir(parents=True)
    (new_job / "data-0.arrow").write_bytes(b"y")

    stale = time.time() - 3600
    for root, dirs, files in os.walk(tmp_path / "job-old", topdown=False):
        for name in files + dirs:
            os.utime(os.path.join(root, name), (stale, stale))
    os.utime(tmp_path / "job-old", (stale, stale))

    deleted = clean_shuffle_data(str(tmp_path), ttl_seconds=600)
    assert deleted == ["job-old"]
    assert not (tmp_path / "job-old").exists()
    assert (new_job / "data-0.arrow").exists()

    # loose files in work_dir are never touched
    assert clean_shuffle_data(str(tmp_path), ttl_seconds=0) == ["job-new"]


@pytest.fixture
def cluster_procs(tmp_path):
    """Real `python -m` scheduler + executor child processes."""
    sched_port, rest_port = _free_port(), _free_port()
    flight_port, grpc_port = _free_port(), _free_port()
    env = dict(CPU_MESH_ENV)
    procs = []
    # logs go to files, never to a pipe nobody drains: every persistent-cache
    # hit makes XLA:CPU print a ~3 KB "AOT result ... machine type" error, a
    # few dozen of them fill a 64 KB pipe and the daemon blocks in write()
    logs = [open(tmp_path / f"{n}.log", "w+") for n in ("scheduler", "executor")]
    try:
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, "-m", "ballista_tpu.scheduler",
                    "--bind-host", "127.0.0.1",
                    "--bind-port", str(sched_port),
                    "--rest-port", str(rest_port),
                    "--state-backend", "sqlite",
                    "--state-path", str(tmp_path / "state.db"),
                ],
                env=env,
                stdout=logs[0],
                stderr=subprocess.STDOUT,
            )
        )
        time.sleep(2.0)
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, "-m", "ballista_tpu.executor",
                    "--bind-host", "127.0.0.1",
                    "--external-host", "127.0.0.1",
                    "--bind-port", str(flight_port),
                    "--bind-grpc-port", str(grpc_port),
                    "--scheduler-host", "127.0.0.1",
                    "--scheduler-port", str(sched_port),
                    "--work-dir", str(tmp_path / "work"),
                    "--job-data-ttl-seconds", "3600",
                    "--job-data-clean-up-interval-seconds", "1",
                ],
                env=env,
                stdout=logs[1],
                stderr=subprocess.STDOUT,
            )
        )
        yield sched_port, rest_port, logs
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        for f in logs:
            f.close()


def test_process_entrypoints_end_to_end(tmp_path, cluster_procs):
    """A client runs SQL against scheduler+executor child processes over an
    external CSV table (self-contained plan serde — no shared memory)."""
    sched_port, rest_port, daemon_logs = cluster_procs

    csv = tmp_path / "points.csv"
    csv.write_text(
        "k,v\n" + "\n".join(f"{i % 5},{i * 1.5}" for i in range(1000)) + "\n"
    )

    script = f"""
import time
from ballista_tpu.client.context import BallistaContext
from ballista_tpu.config import BallistaConfig

# file-shuffle tier pinned: this test covers the PROCESS lifecycle +
# serde path; on this 1-core host the mesh tier's shard_map compiles
# would dominate (mesh planning is covered by the dryrun and the mesh
# parity test)
cfg = BallistaConfig().with_setting("ballista.tpu.collective_shuffle", "false")
deadline = time.time() + 60
last = None
while True:
    try:
        ctx = BallistaContext.remote("127.0.0.1", {sched_port}, cfg)
        break
    except Exception as e:
        last = e
        if time.time() > deadline:
            raise
        time.sleep(0.5)

ctx.sql(
    "create external table pts (k bigint, v double) "
    "stored as csv with header row location '{csv}'"
)
res = ctx.sql(
    "select k, sum(v) as sv, count(*) as n from pts group by k order by k"
).collect().to_pandas()
assert len(res) == 5, res
assert int(res.n.sum()) == 1000, res
import numpy as np
want = sum(i * 1.5 for i in range(1000))
np.testing.assert_allclose(res.sv.sum(), want, rtol=1e-9)
print("ENTRYPOINT-OK")
"""
    proc = subprocess.run(
        [sys.executable, "-c", script],
        env=CPU_MESH_ENV,
        capture_output=True,
        text=True,
        timeout=300,
    )
    if proc.returncode != 0:
        logs = []
        for f in daemon_logs:
            f.seek(0)
            logs.append(f.read()[-6000:])
        raise AssertionError(
            f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-6000:]}\n"
            "daemons:\n" + "\n---\n".join(logs)
        )
    assert "ENTRYPOINT-OK" in proc.stdout

    # REST /api/state sees the executor and the completed job
    state = json.loads(
        urllib.request.urlopen(
            f"http://127.0.0.1:{rest_port}/api/state", timeout=10
        ).read()
    )
    assert state["version"]
    assert len(state["executors"]) == 1
    # the executor sees the 4-device virtual mesh and clamps to one task
    # slot (executor.effective_task_slots: a mesh is one resource)
    assert state["executors"][0]["total_task_slots"] == 1
    assert any(j["status"] == "completed" for j in state["jobs"]), state
    # every job row carries the per-stage detail array (finished jobs
    # have their stage bookkeeping torn down, so it may be empty)
    assert all("stages" in j for j in state["jobs"]), state

    assert state["executors"][0]["n_devices"] == 4  # virtual mesh advertised

    # /api/job/<id>: stage DAG detail (deps + plan display) for the UI's
    # expandable job rows
    done = [j for j in state["jobs"] if j["status"] == "completed"]
    detail = json.loads(
        urllib.request.urlopen(
            f"http://127.0.0.1:{rest_port}/api/job/{done[0]['job_id']}",
            timeout=10,
        ).read()
    )
    assert detail["status"] == "completed"
    assert detail["stages"], detail
    assert all("plan" in s and "depends_on" in s for s in detail["stages"])

    # the UI page serves
    page = urllib.request.urlopen(
        f"http://127.0.0.1:{rest_port}/", timeout=10
    ).read()
    assert b"ballista-tpu scheduler" in page

    # KEDA external scaler answers on the scheduler's gRPC port
    import grpc

    from ballista_tpu.proto import pb
    from ballista_tpu.scheduler.external_scaler import (
        EXTERNAL_SCALER_METHODS,
        EXTERNAL_SCALER_SERVICE,
    )
    from ballista_tpu.scheduler.rpc import _Stub

    ch = grpc.insecure_channel(f"127.0.0.1:{sched_port}")
    stub = _Stub(ch, EXTERNAL_SCALER_SERVICE, EXTERNAL_SCALER_METHODS)
    spec = stub.GetMetricSpec(pb.ScaledObjectRef(name="x", namespace="d"))
    # PR 12 (docs/observability.md): the scale signal is the composite
    # desired-executor pressure, not the raw inflight count
    assert spec.metricSpecs[0].metricName == "desired_executors"
    assert spec.metricSpecs[0].targetSize == 1
    active = stub.IsActive(pb.ScaledObjectRef(name="x", namespace="d"))
    assert active.result is False  # job finished, nothing running
    metrics = stub.GetMetrics(
        pb.GetMetricsRequest(metricName="desired_executors")
    )
    assert metrics.metricValues[0].metricValue == 0
    ch.close()


# -- one chip owner per process ---------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code: str, **env_overrides) -> subprocess.CompletedProcess:
    env = {**os.environ, "PYTHONPATH": REPO}
    for k, v in env_overrides.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run(
        [sys.executable, "-c", code],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO,
    )


@pytest.mark.parametrize(
    "module",
    [
        "ballista_tpu.scheduler.server",
        "ballista_tpu.scheduler.__main__",
        "ballista_tpu.client.context",
        "ballista_tpu.cli",
    ],
)
def test_import_initialises_no_backend(module):
    """The control plane and the client must be importable without ever
    touching a device: a chip belongs to ONE process, the executor. With a
    platform name that is no backend, any initialisation at import dies."""
    proc = _run_py(
        f"import {module}", JAX_PLATFORMS="no_such_platform"
    )
    assert proc.returncode == 0, proc.stderr[-3000:]


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_dir_rule(placed, tmp_path):
    """docs/compile_cache.md: JAX_COMPILATION_CACHE_DIR set -> that
    directory, and the package itself set nothing (the child also stubs
    jax.config.update to see every write); unset -> <checkout>/.jax_cache."""
    code = (
        "import jax\n"
        "seen = []\n"
        "orig = jax.config.update\n"
        "def spy(k, v):\n"
        "    seen.append(k)\n"
        "    return orig(k, v)\n"
        "jax.config.update = spy\n"
        "import ballista_tpu\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print('jax_compilation_cache_dir' in seen)\n"
        "print(ballista_tpu.resolve_jax_cache_dir())\n"
    )
    want = str(tmp_path) if placed else os.path.join(REPO, ".jax_cache")
    proc = _run_py(
        code,
        JAX_COMPILATION_CACHE_DIR=str(tmp_path) if placed else None,
        BALLISTA_TPU_JAX_CACHE=None,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    cache_dir, set_in_code, resolved = proc.stdout.split()
    assert cache_dir == want and resolved == want
    assert set_in_code == str(not placed)
