"""Test configuration.

The suite runs on the CPU backend (the driver's command sets
``JAX_PLATFORMS=cpu``); the chip is proven by ``chip_smoke.py``, and the
kernels' TPU lowering by the described-topology compiles in
``tests/test_tpu_compile.py``.

Multi-device (mesh/collective) tests launch subprocesses with
``CPU_MESH_ENV`` to get a virtual 4-device CPU mesh: the size of the
four-chip host that exists, and small enough that XLA:CPU's in-process
collective rendezvous (one thread per device) is not starved on an 8-core
box — at 8 virtual devices it aborted with "Expected 8 threads to join".
"""

import os
import sys

import numpy as np
import pyarrow as pa
import pytest

# Fault-injection hygiene: a stray BALLISTA_FAULTS in the developer's shell
# must NOT poison normal test runs (injected crashes would masquerade as
# real failures). Strip the keys BEFORE CPU_MESH_ENV snapshots os.environ;
# chaos tests (-m chaos) re-add them to their SUBPROCESS envs explicitly.
for _k in ("BALLISTA_FAULTS", "BALLISTA_FAULTS_SEED"):
    os.environ.pop(_k, None)

# Witness hygiene: the lock-order and resource witnesses are debug modes
# that chaos/hygiene tests enable in SUBPROCESS envs; leaked into the
# runner they would instrument every test's locks/channels and make
# tier-1 timing (and witness assertions) nondeterministic.
for _k in (
    "BALLISTA_LOCK_WITNESS",
    "BALLISTA_RESOURCE_WITNESS",
    "BALLISTA_REPLAY_WITNESS",
    "BALLISTA_CACHE_WITNESS",
    "BALLISTA_CACHE_WITNESS_SAMPLE",
    "BALLISTA_DUR_WITNESS",
):
    os.environ.pop(_k, None)

# AQE hygiene: a BALLISTA_AQE* override in the developer's shell would
# force the adaptive policy on (or off) for every in-test scheduler,
# rewriting plans tests expect verbatim. Tests that exercise AQE set
# ballista.tpu.aqe in their own session configs (or the env in their
# SUBPROCESS environments). Stripped BEFORE the CPU_MESH_ENV snapshot.
for _k in [k for k in os.environ if k.startswith("BALLISTA_AQE")]:
    os.environ.pop(_k, None)

# Hermetic plan-hint persistence: without this, every in-test TpuContext/
# Executor would read AND write the developer's real hint file
# (compilecache/hints.py rides the XLA cache dir), making test behavior
# depend on prior runs. Tests that exercise persistence point
# BALLISTA_TPU_HINT_CACHE at a tmp dir themselves. Set BEFORE the
# CPU_MESH_ENV snapshot so subprocess tests inherit the isolation.
os.environ["BALLISTA_TPU_HINT_CACHE"] = "off"

# Environment for subprocesses that need a 4-device virtual CPU mesh.
CPU_MESH_ENV = {
    **os.environ,
    "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
}


# Tier-1 time guard: the tier-1 gate runs `-m 'not slow'` under a hard
# time limit (the driver's command), so any single unmarked test that
# balloons can sink the whole gate. Fail an OTHERWISE-PASSING unmarked
# test that
# exceeds the per-test limit, with a message telling the author to mark
# it `slow`. At-scale tests (SF>=0.05 TPC-H, out-of-core spill runs)
# must carry @pytest.mark.slow. The limit is generous — the box is
# shared, and a contended run can triple a legitimate test's wall time;
# it exists to catch multi-minute at-scale tests, not 90s outliers.
# Override/disable with BALLISTA_TEST_TIME_LIMIT_S (0 disables).
_TEST_TIME_LIMIT_S = float(os.environ.get("BALLISTA_TEST_TIME_LIMIT_S", "300"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    if (
        rep.when == "call"
        and rep.passed
        and _TEST_TIME_LIMIT_S > 0
        and item.get_closest_marker("slow") is None
        and rep.duration > _TEST_TIME_LIMIT_S
    ):
        rep.outcome = "failed"
        rep.longrepr = (
            f"{item.nodeid} took {rep.duration:.1f}s — over the "
            f"{_TEST_TIME_LIMIT_S:.0f}s tier-1 per-test limit. Mark it "
            "@pytest.mark.slow (excluded from the tier-1 gate) or make it "
            "faster; raise BALLISTA_TEST_TIME_LIMIT_S only for slow hosts."
        )


@pytest.fixture(autouse=True)
def _fault_injection_inert():
    """Guard: fault injection must be OFF in the test-runner process for
    every test. Chaos tests only enable it inside subprocess environments;
    if this trips, something leaked BALLISTA_FAULTS into the runner or
    called faults.install() without cleaning up."""
    from ballista_tpu.testing import faults

    assert not faults.enabled(), (
        "fault injection is active in the pytest process; chaos rules must "
        "only be enabled in subprocess envs (BALLISTA_FAULTS) or torn down "
        "with faults.install(None)"
    )
    yield
    assert not faults.enabled(), (
        "test left fault injection installed; call faults.install(None)"
    )


@pytest.fixture(scope="session")
def cpu_mesh_env():
    return dict(CPU_MESH_ENV)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def sample_table() -> pa.Table:
    """A small mixed-type Arrow table used across substrate/ops tests."""
    n = 1000
    r = np.random.default_rng(7)
    return pa.table(
        {
            "id": pa.array(np.arange(n, dtype=np.int64)),
            "grp": pa.array(r.integers(0, 5, n).astype(np.int32)),
            "price": pa.array(r.uniform(0, 100, n)),
            "qty": pa.array(r.integers(1, 50, n).astype(np.int64)),
            "flag": pa.array([["A", "B", "C"][i % 3] for i in range(n)]),
            "ship": pa.array(
                (np.arange(n) % 2000 + 8000).astype("int32"), type=pa.int32()
            ).cast(pa.date32()),
        }
    )
