"""The closed compiled-kernel vocabulary: signatures, AOT builders, and
the closed-vocabulary gate.

A JAX/XLA engine pays tracing + XLA compilation per distinct
``(kernel, capacity-bucket, dtype-tuple)`` signature, so cold-start cost
is proportional to the size of the compiled-program vocabulary — which
therefore must be CLOSED (enumerable) and SMALL (docs/compile_cache.md).
This module is the single registry of that vocabulary:

- :data:`VOCABULARY` — every jitted kernel in ``ops/`` + ``exec/``, keyed
  exactly as ``ballista_tpu.analysis.jaxlint.static_signature_report``
  reports them (the source of truth: the report is derived from the
  SOURCE, so a new ``jax.jit`` site shows up there before it can ship).
- :data:`OPERATOR_KERNELS` — which vocabulary kernels each physical
  operator class may dispatch (the plan-level closure map).
- :func:`enumerate_prewarm` — the concrete AOT signature list per
  capacity bucket, as zero-arg compile thunks
  (``jax.jit(...).lower(...).compile()`` for fixed-aval kernels, a
  zeros-execution through the public composition path where index dtypes
  are composition-derived).
- :func:`check_vocabulary` / :func:`check_plan` — the gate wired into
  ``python -m ballista_tpu.analysis``, ``parallel/dryrun.py`` and the
  tier-1 suite: a kernel in the source report but not registered here (or
  an operator class not mapped) fails CI, so the recompile vocabulary
  cannot silently grow in future PRs.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

# -- the kernel vocabulary ---------------------------------------------------
#
# Keys match static_signature_report: "<pkg>.<module>.<jitted function>"
# (factory-inner functions report under their def name; lambda-jitted
# helpers inside the same factories ride the factory's entry). ``aot``
# names the prewarm strategy: "lower" (fixed avals -> lower().compile()),
# "execute" (composition-derived dtypes -> one zeros-execution through the
# public path), None (signature depends on plan content — expressions,
# schemas, static layouts — so it is reachable only from a real plan; the
# persistent XLA cache and the shared trace cache carry those).


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    aot: str | None  # "lower" | "execute" | None
    why: str  # what parameterizes the signature / why not prewarmable


VOCABULARY: dict[str, KernelSpec] = {
    # ops/: the closed data-movement + kernel substrate
    "ops.perm.f": KernelSpec(
        "lower", "argsort / stacked-gather passes per (dtype, capacity)"
    ),
    "ops.concat._concat_device": KernelSpec(
        None, "operand count + per-column dtypes of the concatenated set"
    ),
    "ops.fetch.f": KernelSpec(
        None, "fetched-array count/dtypes (host materialization packing)"
    ),
    "ops.join._build_finish": KernelSpec(
        None, "static key indexes + build mode from the join plan"
    ),
    "ops.join.f": KernelSpec(
        None, "probe key indexes + join kind from the join plan"
    ),
    "ops.aggregate._seg_part1": KernelSpec(
        None, "static op/layout tuples from the aggregate spec"
    ),
    "ops.aggregate._seg_part2": KernelSpec(
        None, "static op/layout tuples from the aggregate spec"
    ),
    "ops.aggregate._dense_agg": KernelSpec(
        None, "static op tuple + dictionary vocab sizes"
    ),
    "ops.aggregate._scalar_agg": KernelSpec(
        None, "static op tuple from the aggregate spec"
    ),
    "ops.pallas_agg.f": KernelSpec(
        None, "pallas segment-reduction tile layout (TPU-only path)"
    ),
    # exec/: operator-level programs (expression/schema parameterized)
    "exec.pipeline.run": KernelSpec(
        None, "fused filter/projection chain expressions + input schema"
    ),
    "exec.repartition.f": KernelSpec(
        None, "hash key indexes + partition count from the plan"
    ),
    "exec.aggregate.f": KernelSpec(
        None, "aggregate spec (ops, state schema, group exprs)"
    ),
    "exec.aggregate.scalar_final": KernelSpec(
        None, "aggregate finals layout"
    ),
    "exec.joins.f": KernelSpec(None, "join keys/kind from the plan"),
    "exec.joins.fn": KernelSpec(
        None, "semi/anti mask + expansion programs (keys, kind, capacity)"
    ),
    "exec.joins.run": KernelSpec(
        None, "expansion-join body (filter expr, kind, output capacity)"
    ),
    "exec.sort.f": KernelSpec(None, "fetch bound from the plan"),
    "exec.shrink.f": KernelSpec(None, "shrink target capacity"),
    "exec.window.f": KernelSpec(None, "window frame/function layout"),
    "exec.percentile.f": KernelSpec(None, "quantile set from the plan"),
}

# Physical operator class -> vocabulary kernels it may dispatch. The gate
# walks every TPC-H physical/stage plan and fails on an operator class
# missing here (a NEW operator cannot ship without declaring its compile
# surface) or a mapping naming an unknown kernel (mappings cannot rot).
_PIPELINE = ("exec.pipeline.run", "exec.shrink.f", "ops.perm.f")
_SCAN = ("ops.perm.f", "ops.concat._concat_device")
_AGG = (
    "exec.aggregate.f", "exec.aggregate.scalar_final",
    "ops.aggregate._seg_part1", "ops.aggregate._seg_part2",
    "ops.aggregate._dense_agg", "ops.aggregate._scalar_agg",
    "ops.pallas_agg.f", "ops.perm.f", "ops.concat._concat_device",
    "ops.fetch.f",
)
_JOIN = (
    "exec.joins.f", "exec.joins.fn", "exec.joins.run",
    "ops.join._build_finish", "ops.join.f", "ops.perm.f",
    "ops.concat._concat_device", "ops.fetch.f",
)

OPERATOR_KERNELS: dict[str, tuple[str, ...]] = {
    # leaf scans (arrow -> DeviceBatch conversion + slice concat)
    "MemoryScanExec": _SCAN,
    "CsvScanExec": _SCAN,
    "ParquetScanExec": _SCAN,
    "AvroScanExec": _SCAN,
    "EmptyExec": (),
    # row pipeline
    "FilterExec": _PIPELINE,
    "ProjectionExec": _PIPELINE,
    "RenameExec": (),
    "CoalescePartitionsExec": (),
    "UnionExec": ("ops.concat._concat_device",),
    # sorts / limits
    "SortExec": ("exec.sort.f", "ops.perm.f", "ops.concat._concat_device"),
    "GlobalLimitExec": ("ops.perm.f",),
    # aggregates / joins / windows
    "HashAggregateExec": _AGG,
    "HashJoinExec": _JOIN,
    "CrossJoinExec": _JOIN,
    "WindowExec": ("exec.window.f", "ops.perm.f"),
    "PercentileExec": ("exec.percentile.f", "ops.perm.f"),
    # exchange boundary
    "HashRepartitionExec": ("exec.repartition.f", "ops.perm.f"),
    "ShuffleWriterExec": (
        "exec.repartition.f", "ops.perm.f", "ops.fetch.f",
        "ops.concat._concat_device",
    ),
    "ShuffleReaderExec": ("ops.perm.f", "ops.concat._concat_device"),
    "UnresolvedShuffleExec": (),
    # mesh tier (shard_map stage programs compile through parallel/stage.py,
    # outside the jaxlint report targets; host-side they reuse ops/)
    "MeshAggregateExec": _AGG,
    "MeshJoinExec": _JOIN,
    "MeshSortExec": ("exec.sort.f", "ops.perm.f"),
    "MeshWindowExec": ("exec.window.f", "ops.perm.f"),
}


# -- AOT prewarm enumeration -------------------------------------------------

# The dtype axis of the data-movement substrate: every TPC-H column lands
# on one of these device dtypes (strings ride int32 dictionary codes,
# dates int32/int64, money float64; bool covers validity/null masks).
PREWARM_DTYPES = ("int64", "float64", "int32", "bool")


@dataclasses.dataclass(frozen=True)
class PrewarmSignature:
    """One concrete AOT-compilable signature."""

    kernel: str
    capacity: int
    dtypes: tuple[str, ...]
    variant: str = ""
    compile: Callable[[], None] = None  # zero-arg thunk

    @property
    def key(self) -> str:
        v = f",{self.variant}" if self.variant else ""
        return f"{self.kernel}[{'+'.join(self.dtypes)}{v},cap={self.capacity}]"


def _warm_argsort(dtype: str, cap: int, descending: bool) -> None:
    """AOT-compile one argsort pass via lower().compile() on the SAME
    lru-cached wrapper the query path dispatches through (ops/perm.py) —
    the jit dispatch cache and the persistent XLA cache both warm."""
    import jax
    import jax.numpy as jnp

    from ballista_tpu.ops.perm import _argsort_program

    is_float = dtype.startswith("float")
    fn = _argsort_program(dtype, cap, descending, is_float)
    fn.lower(jax.ShapeDtypeStruct((cap,), jnp.dtype(dtype))).compile()


def _warm_sort_pass(dtype: str, cap: int) -> None:
    """Warm the take/gather programs of one radix pass by executing it on
    zeros: index dtypes there are composition-derived (argsort output vs
    the int32 iota), so an execution through the public path is the only
    way to hit the exact runtime signatures."""
    import jax
    import jax.numpy as jnp

    from ballista_tpu.ops.perm import multi_key_perm

    col = jnp.zeros(cap, dtype=jnp.dtype(dtype))
    jax.block_until_ready(multi_key_perm([(col, False)]))


def _warm_compact(cap: int) -> None:
    """Warm the compaction programs (invalid mask, front-valid rebuild,
    bool argsort, per-dtype gathers) on a representative two-column
    batch."""
    import jax
    import numpy as np

    from ballista_tpu.columnar.batch import DeviceBatch
    from ballista_tpu.datatypes import DataType, Field, Schema
    from ballista_tpu.ops.compact import compact

    schema = Schema(
        [Field("k", DataType.INT64), Field("v", DataType.FLOAT64)]
    )
    b = DeviceBatch.from_host(
        schema,
        [np.zeros(0, np.int64), np.zeros(0, np.float64)],
        0,
        capacity=cap,
    )
    jax.block_until_ready(compact(b).valid)


def enumerate_prewarm(
    buckets, dtypes: tuple[str, ...] = PREWARM_DTYPES
) -> list[PrewarmSignature]:
    """The concrete prewarm signature list over ``buckets`` (capacity
    ladder points, see CapacityLadder.buckets_upto)."""
    sigs: list[PrewarmSignature] = []
    for cap in buckets:
        for dt in dtypes:
            # flags sort through the int32 program (ops/perm.stable_argsort)
            for desc in (False, True) if dt != "bool" else ():
                sigs.append(PrewarmSignature(
                    "ops.perm.f", cap, (dt,),
                    variant=f"argsort,desc={int(desc)}",
                    compile=(
                        lambda dt=dt, cap=cap, desc=desc:
                        _warm_argsort(dt, cap, desc)
                    ),
                ))
            sigs.append(PrewarmSignature(
                "ops.perm.f", cap, (dt,), variant="take",
                compile=lambda dt=dt, cap=cap: _warm_sort_pass(dt, cap),
            ))
        sigs.append(PrewarmSignature(
            "ops.perm.f", cap, ("int64", "float64"), variant="compact",
            compile=lambda cap=cap: _warm_compact(cap),
        ))
    return sigs


# -- the closed-vocabulary gate ----------------------------------------------

def check_vocabulary(report: dict | None = None) -> list[str]:
    """Compare the source-derived kernel report against VOCABULARY; any
    asymmetric difference is a finding (new jit site unregistered, or a
    registry entry whose kernel no longer exists)."""
    if report is None:
        from ballista_tpu.analysis.jaxlint import static_signature_report

        report = static_signature_report()
    problems = []
    for k in sorted(report):
        if k not in VOCABULARY:
            problems.append(
                f"unregistered kernel {k} ({report[k]['file']}:"
                f"{report[k]['line']}): new jit sites must be added to "
                "compilecache.registry.VOCABULARY (and OPERATOR_KERNELS "
                "for the operators that dispatch them)"
            )
    for k in sorted(VOCABULARY):
        if k not in report:
            problems.append(
                f"stale registry entry {k}: kernel no longer in the "
                "static signature report"
            )
    for op, kernels in sorted(OPERATOR_KERNELS.items()):
        for k in kernels:
            if k not in VOCABULARY:
                problems.append(
                    f"OPERATOR_KERNELS[{op}] names unknown kernel {k}"
                )
    return problems


def check_plan(plan) -> list[str]:
    """Walk a physical plan; every operator class must be mapped in
    OPERATOR_KERNELS (the plan-level closure: an unmapped operator is an
    undeclared compile surface)."""
    problems = []
    seen = set()

    def walk(p) -> None:
        name = type(p).__name__
        if name not in seen:
            seen.add(name)
            if name not in OPERATOR_KERNELS:
                problems.append(
                    f"operator {name} not mapped in "
                    "compilecache.registry.OPERATOR_KERNELS"
                )
        for c in p.children():
            walk(c)

    walk(plan)
    return problems


def plan_kernels(plan) -> set[str]:
    """The vocabulary slice a plan may dispatch (observability: bench and
    the REST surface report it as the plan's compile surface)."""
    out: set[str] = set()

    def walk(p) -> None:
        out.update(OPERATOR_KERNELS.get(type(p).__name__, ()))
        for c in p.children():
            walk(c)

    walk(plan)
    return out
