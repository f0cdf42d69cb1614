"""The harness reports nothing without a TPU, and nothing in a directory that
holds only BENCHMARK.json and the benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys

from conftest import PERF, ROOT

ARGS = ["--workload", "tpch-sf1-mem.power", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(PERF / "run.py"), *ARGS],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_rehearsal_never_passes():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(PERF / "run.py"), *ARGS, "--rehearse-sf", "0.01"],
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["rehearsal"] is True
    assert list(last)[-1] == "compared"


def test_benchmark_alone_reports_nothing(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(PERF, tmp_path / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, str(tmp_path / "perf" / "run.py"),
                        *ARGS], capture_output=True, text=True, timeout=120,
                       cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
