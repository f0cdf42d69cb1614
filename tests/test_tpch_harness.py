"""TPC-H harness CLI parity: gen/convert/benchmark(datafusion|ballista).

ref benchmarks/src/bin/tpch.rs:69-260 — the north star requires the
benchmarks/ harness to run against the executor pool with the reference's
CLI shape.
"""

import json
import socket
import subprocess
import sys
import time
from pathlib import Path

from tests.conftest import CPU_MESH_ENV

HARNESS = str(Path(__file__).resolve().parent.parent / "benchmarks" / "tpch.py")

# single-device CPU: the harness exercises the engine CLI, not the mesh
# tier (whose virtual-mesh env is covered by test_mesh_sql)
ENV = {k: v for k, v in CPU_MESH_ENV.items() if k != "XLA_FLAGS"}


def _run(*argv, timeout=300):
    proc = subprocess.run(
        [sys.executable, HARNESS, *argv],
        env=ENV,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    assert proc.returncode == 0, (
        f"{argv}:\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    return proc.stdout


def test_gen_convert_benchmark_local(tmp_path):
    data = tmp_path / "data"
    _run("gen", "--scale", "0.002", "--path", str(data))
    assert (data / "lineitem.csv").exists()

    out = _run(
        "benchmark", "datafusion", "-q", "1", "-p", str(data),
        "-i", "2", "-o", str(tmp_path / "summary"),
    )
    assert "Query 1 best time" in out
    summary = list((tmp_path / "summary").glob("tpch-summary--*.json"))
    assert summary, "summary JSON missing"
    rec = json.loads(summary[0].read_text())
    assert rec["query"] == 1 and len(rec["iterations"]) == 2

    pq = tmp_path / "pq"
    out = _run("convert", "-i", str(data), "-o", str(pq))
    assert (pq / "lineitem.parquet").exists()
    out = _run(
        "benchmark", "datafusion", "-q", "6", "-p", str(pq),
        "-f", "parquet", "-i", "1",
    )
    assert "Query 6 best time" in out


def test_loadtest_local(tmp_path):
    data = tmp_path / "data"
    _run("gen", "--scale", "0.002", "--path", str(data))
    out = _run(
        "loadtest", "ballista", "-q", "1,6", "-p", str(data),
        "-r", "4", "-c", "2",
    )
    assert "loadtest: 4 requests" in out


def test_micro_benchmarks(tmp_path):
    import json
    import subprocess as sp

    micro = str(
        Path(__file__).resolve().parent.parent / "benchmarks" / "micro.py"
    )
    proc = sp.run(
        [sys.executable, micro, "--rows", "20000", "--samples", "2",
         "-o", str(tmp_path / "micro.json")],
        env=ENV, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = json.loads((tmp_path / "micro.json").read_text())
    assert {r["benchmark_name"] for r in recs} >= {
        "stable_argsort_i64", "group_aggregate_sum_count", "join_probe",
    }


def test_benchmark_ballista_remote(tmp_path):
    data = tmp_path / "data"
    _run("gen", "--scale", "0.002", "--path", str(data))

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    # to a file, not to a pipe nobody drains (see test_lifecycle.cluster_procs)
    log = open(tmp_path / "daemons.log", "w")
    try:
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "ballista_tpu.scheduler",
             "--bind-host", "127.0.0.1", "--bind-port", str(port)],
            env=ENV, stdout=log, stderr=subprocess.STDOUT,
        ))
        time.sleep(2)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "ballista_tpu.executor",
             "--bind-host", "127.0.0.1", "--external-host", "127.0.0.1",
             "--bind-port", "0", "--bind-grpc-port", "0",
             "--scheduler-host", "127.0.0.1", "--scheduler-port", str(port)],
            env=ENV, stdout=log, stderr=subprocess.STDOUT,
        ))
        time.sleep(3)
        out = _run(
            "benchmark", "ballista", "-q", "6", "-p", str(data),
            "--host", "127.0.0.1", "--port", str(port), "-i", "1",
        )
        assert "Query 6 best time" in out
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        log.close()
