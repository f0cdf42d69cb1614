"""Multi-chip dryrun body: the full distributed stage pipeline on an
n-device mesh, asserted against a numpy oracle.

Run as ``python -m ballista_tpu.parallel.dryrun N`` in an environment where
jax sees N devices (the driver entry ``__graft_entry__.dryrun_multichip``
launches this module in a subprocess with ``JAX_PLATFORMS=cpu`` and
``--xla_force_host_platform_device_count=N``: a rehearsal on virtual
devices, never a chip result).

The pipeline mirrors the reference's PARTITIONED join + repartitioned
aggregate flow (planner.rs:133-157; shuffle_writer.rs:142-292 <->
shuffle_reader.rs:102-130), compiled as shard_map programs with
``jax.lax.all_to_all`` exchanges over the mesh axis.
"""

from __future__ import annotations

import sys

import numpy as np


def run(n_devices: int) -> None:
    import jax

    import pyarrow as pa

    from ballista_tpu.exec.context import TpuContext

    assert len(jax.devices()) >= n_devices, (
        f"need {n_devices} devices, jax sees {jax.devices()}"
    )

    rng = np.random.default_rng(7)
    n, n_dim = 20_000, 230
    fact = pa.table(
        {
            "k": pa.array(rng.integers(0, n_dim + 20, n)),  # some misses
            "v": pa.array(rng.uniform(0, 10, n)),
        }
    )
    dim = pa.table(
        {
            "id": pa.array(np.arange(n_dim, dtype=np.int64)),
            "grp": pa.array((np.arange(n_dim) % 13).astype(np.int64)),
        }
    )
    ctx = TpuContext()
    rt = ctx.mesh_runtime()
    assert rt is not None, "mesh runtime must be active for the dryrun"
    ctx.register_table("fact", fact)
    ctx.register_table("dim", dim)

    sql = (
        "SELECT grp, SUM(v) AS s, COUNT(*) AS c FROM fact "
        "JOIN dim ON k = id GROUP BY grp ORDER BY grp"
    )
    # the plan must route through the mesh operators (shard_map +
    # all_to_all), not the serial coalesce funnel
    disp = ctx.create_physical_plan(ctx.sql_to_logical(sql)).display()
    assert "MeshJoinExec" in disp and "MeshAggregateExec" in disp, disp

    out = ctx.sql(sql).collect().to_pandas()
    df = fact.to_pandas().merge(dim.to_pandas(), left_on="k", right_on="id")
    want = (
        df.groupby("grp")
        .v.agg(["sum", "count"])
        .reset_index()
        .sort_values("grp")
        .reset_index(drop=True)
    )
    np.testing.assert_array_equal(out.grp.to_numpy(), want.grp.to_numpy())
    np.testing.assert_allclose(
        out.s.to_numpy(), want["sum"].to_numpy(), rtol=1e-9
    )
    np.testing.assert_array_equal(out.c.to_numpy(), want["count"].to_numpy())

    # SCHEDULER PATH (SURVEY build-order #6): the same query through the
    # full distributed control plane — the executor registers n_devices,
    # the scheduler plans a fused mesh stage-chain, the stage plan crosses
    # the serde boundary, and the executor runs it via its own
    # MeshRuntime. Asserts mesh placement in the EXECUTOR-side stage plan
    # and the same oracle values end-to-end over gRPC/Flight.
    import time

    from ballista_tpu.client.context import BallistaContext

    dctx = BallistaContext.standalone()
    try:
        sched = dctx._standalone_cluster.scheduler
        deadline = time.time() + 30
        while time.time() < deadline:
            specs = [
                em.specification
                for em in sched.executor_manager.all_executors()
            ]
            if any((s.n_devices or 1) >= n_devices for s in specs):
                break
            time.sleep(0.1)
        else:
            raise AssertionError(
                f"executor never advertised {n_devices} devices: {specs}"
            )
        dctx.register_table("fact", fact)
        dctx.register_table("dim", dim)
        dout = dctx.sql(sql).collect().to_pandas()
        stage_disp = "\n".join(
            stage.plan.display()
            for job in sched.jobs.values()
            for stage in job.stages.values()
        )
        assert "MeshJoinExec" in stage_disp and (
            "MeshAggregateExec" in stage_disp
        ), f"mesh ops missing from distributed stage plans:\n{stage_disp}"
        np.testing.assert_array_equal(
            dout.grp.to_numpy(), want.grp.to_numpy()
        )
        np.testing.assert_allclose(
            dout.s.to_numpy(), want["sum"].to_numpy(), rtol=1e-9
        )
        np.testing.assert_array_equal(
            dout.c.to_numpy(), want["count"].to_numpy()
        )
    finally:
        dctx._standalone_cluster.stop()


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    run(n)
    # planlint static surface: the per-kernel signature report over
    # ops/ + exec/ — which jit parameters are static (recompile keys) vs
    # traced — printed beside the mesh-placement assertions so a hazard
    # introduced by a kernel change fails the same gate that proves the
    # distributed pipeline.
    from ballista_tpu.analysis.jaxlint import static_signature_report

    report = static_signature_report()
    hazards = [h for k in report.values() for h in k["hazards"]]
    print(f"planlint: {len(report)} jitted kernels, {len(hazards)} hazards")
    for name, info in sorted(report.items()):
        static = ", ".join(info["static"]) or "-"
        print(f"  {name}  static[{static}]")
    for h in hazards:
        print(f"  HAZARD {h}")
    if hazards:
        # not an assert: the gate must hold under `python -O` too
        raise SystemExit(
            f"{len(hazards)} JAX hazards (see planlint output above)"
        )
    # closed-vocabulary gate (docs/compile_cache.md): the same report is
    # the source of truth for compilecache.registry — a jit site that is
    # not registered there is an undeclared cold-start compile surface
    from ballista_tpu.compilecache import registry

    problems = registry.check_vocabulary(report)
    for p in problems:
        print(f"  VOCABULARY {p}")
    if problems:
        raise SystemExit(
            f"{len(problems)} compile-vocabulary findings (see above)"
        )
    print(
        f"compile-vocab: {len(registry.VOCABULARY)} kernels registered, "
        "report closed"
    )
    print(f"dryrun ok on {n} devices")


if __name__ == "__main__":
    main()
