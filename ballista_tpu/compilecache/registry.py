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
# Keys match static_signature_report: "<pkg>.<module>.<jitted function>".
# The function's name is also the program's name on a device trace
# (``jit_<name>`` on the ``XLA Modules`` line, docs/observability.md), so
# every jitted function says operator and step and none is a lambda. ``aot``
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
    "ops.perm.sort_argsort": KernelSpec(
        "lower", "one stable argsort pass per (dtype, capacity)"
    ),
    "ops.perm.perm_take": KernelSpec(
        "execute", "one-column gather per (dtype, capacity)"
    ),
    "ops.perm.perm_take_batch": KernelSpec(
        None, "stacked gather of a column set (dtypes + null layout)"
    ),
    "ops.perm.sort_f64_keys": KernelSpec(
        None, "a float64 sort key as two int32 keys (TPU), per capacity"
    ),
    "ops.perm.sort_i64_keys": KernelSpec(
        None, "an int64 sort key as two int32 keys (TPU), per capacity"
    ),
    "ops.perm.holistic_sort_pass": KernelSpec(
        None, "a window/percentile sort pass (gather, argsort, gather) "
        "per (dtype, capacity): reached at the capacity of a whole input"
    ),
    "ops.perm.holistic_take": KernelSpec(
        None, "a window/percentile operator's stacked gather of its keys "
        "(dtypes + null layout)"
    ),
    "ops.compact.compact_invalid": KernelSpec(
        "execute", "invalid flags for the compaction sort, per capacity"
    ),
    "ops.compact.compact_front_valid": KernelSpec(
        "execute", "front-packed valid mask, per capacity"
    ),
    "ops.concat._concat_device": KernelSpec(
        None, "operand count + per-column dtypes of the concatenated set"
    ),
    "ops.fetch.fetch_flat": KernelSpec(
        None, "one fetched array flattened (host materialization)"
    ),
    "ops.fetch.fetch_concat": KernelSpec(
        None, "fetched-array count of one dtype (host materialization)"
    ),
    "ops.fetch.fetch_concat_f64": KernelSpec(
        None, "fetched-array count/dtypes widened into one f64 buffer"
    ),
    "ops.join._build_finish": KernelSpec(
        None, "static key indexes + build mode from the join plan"
    ),
    "ops.join.join_build_prep": KernelSpec(
        None, "build key indexes + pack mode: the sort-pass operands"
    ),
    "ops.join.join_exact2_range": KernelSpec(
        None, "two-int-key pack range check, per capacity"
    ),
    "ops.join.join_lut": KernelSpec(
        None, "direct-address probe table (key range, build capacity)"
    ),
    "ops.join.join_sorted_rows": KernelSpec(
        None, "a build's payload gathered into sorted order (dtypes + null "
        "layout)"
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
    "ops.aggregate.agg_zero_null_keys": KernelSpec(
        None, "NULL-key zeroing per (key dtype, capacity)"
    ),
    "ops.aggregate.agg_invalid": KernelSpec(
        None, "invalid flags for the group sort, per capacity"
    ),
    "ops.pallas_agg.agg_onehot_sums": KernelSpec(
        None, "pallas segment-reduction tile layout (TPU-only path)"
    ),
    # exec/: operator-level programs (expression/schema parameterized)
    "exec.pipeline.pipeline_filter": KernelSpec(
        None, "one filter predicate + input schema"
    ),
    "exec.pipeline.pipeline_project": KernelSpec(
        None, "one projection's expressions + input schema"
    ),
    "exec.pipeline.pipeline_fused": KernelSpec(
        None, "fused filter/projection chain expressions + input schema"
    ),
    "exec.repartition.repartition_hash": KernelSpec(
        None, "hash key indexes + partition count from the plan"
    ),
    "exec.repartition.repartition_mask": KernelSpec(
        None, "hash key indexes + partition count; one partition's mask"
    ),
    "exec.repartition.repartition_bucket_counts": KernelSpec(
        None, "partition count from the plan, per capacity"
    ),
    "exec.aggregate.agg_ones": KernelSpec(None, "count column, per capacity"),
    "exec.aggregate.agg_dec_learn": KernelSpec(
        None, "decimal-scale discovery per (capacity, null layout)"
    ),
    "exec.aggregate.agg_dec_scale": KernelSpec(
        None, "decimal scaling to int64 per (capacity, scale)"
    ),
    "exec.aggregate.agg_dec_unscale": KernelSpec(
        None, "scaled sum columns back to value units, per layout"
    ),
    "exec.aggregate.agg_state_bounds": KernelSpec(
        None, "key bounds of a single-int-key state"
    ),
    "exec.aggregate.agg_boundary_merge": KernelSpec(
        None, "merge of two clustered states' boundary group"
    ),
    "exec.aggregate.agg_state_batch": KernelSpec(
        None, "aggregate spec (ops, state schema, group exprs)"
    ),
    "exec.aggregate.agg_scalar_state": KernelSpec(
        None, "scalar aggregate slots + input schema"
    ),
    "exec.aggregate.agg_scalar_final": KernelSpec(
        None, "aggregate finals layout"
    ),
    "exec.joins.join_probe": KernelSpec(
        None, "probe key indexes + join kind from the plan"
    ),
    "exec.joins.join_probe_counts": KernelSpec(
        None, "probe key indexes: matches per probe row"
    ),
    "exec.joins.join_expand_total": KernelSpec(
        None, "expansion output rows (LEFT keeps unmatched)"
    ),
    "exec.joins.join_semi_mask": KernelSpec(
        None, "semi/anti mask from match counts (keys, kind)"
    ),
    "exec.joins.join_unmatched_rows": KernelSpec(
        None, "running unmatched rows of a LEFT/ANTI output batch"
    ),
    "exec.joins.join_expand": KernelSpec(
        None, "expansion-join body (filter expr, kind, output capacity)"
    ),
    "exec.joins.join_probe_filter": KernelSpec(
        None, "probe with a residual filter (keys, kind, filter expr)"
    ),
    "exec.sort.limit_mask": KernelSpec(None, "fetch bound from the plan"),
    "exec.shrink.shrink_compact": KernelSpec(None, "shrink target capacity"),
    "exec.window.window_rank": KernelSpec(
        None, "ranking function + partition/order null layout"
    ),
    "exec.window.window_frame": KernelSpec(
        None, "window frame/function layout"
    ),
    "exec.percentile.percentile_interp": KernelSpec(
        None, "quantile set from the plan"
    ),
}

# Physical operator class -> vocabulary kernels it may dispatch. The gate
# walks every TPC-H physical/stage plan and fails on an operator class
# missing here (a NEW operator cannot ship without declaring its compile
# surface) or a mapping naming an unknown kernel (mappings cannot rot).
_PERM = (
    "ops.perm.sort_argsort", "ops.perm.perm_take", "ops.perm.perm_take_batch",
    "ops.perm.sort_f64_keys", "ops.perm.sort_i64_keys",
    "ops.compact.compact_invalid", "ops.compact.compact_front_valid",
)
_HOLISTIC = ("ops.perm.holistic_sort_pass", "ops.perm.holistic_take")
_FETCH = (
    "ops.fetch.fetch_flat", "ops.fetch.fetch_concat",
    "ops.fetch.fetch_concat_f64",
)
_CONCAT = ("ops.concat._concat_device",)
_PIPELINE = (
    "exec.pipeline.pipeline_filter", "exec.pipeline.pipeline_project",
    "exec.pipeline.pipeline_fused", "exec.shrink.shrink_compact",
) + _PERM
_SCAN = _PERM + _CONCAT
_AGG = (
    "exec.aggregate.agg_ones", "exec.aggregate.agg_dec_learn",
    "exec.aggregate.agg_dec_scale", "exec.aggregate.agg_dec_unscale",
    "exec.aggregate.agg_state_bounds", "exec.aggregate.agg_boundary_merge",
    "exec.aggregate.agg_state_batch", "exec.aggregate.agg_scalar_state",
    "exec.aggregate.agg_scalar_final",
    "ops.aggregate._seg_part1", "ops.aggregate._seg_part2",
    "ops.aggregate._dense_agg", "ops.aggregate._scalar_agg",
    "ops.aggregate.agg_zero_null_keys", "ops.aggregate.agg_invalid",
    "ops.pallas_agg.agg_onehot_sums",
) + _PERM + _CONCAT + _FETCH
_JOIN = (
    "exec.joins.join_probe", "exec.joins.join_probe_counts",
    "exec.joins.join_expand_total", "exec.joins.join_semi_mask",
    "exec.joins.join_unmatched_rows",
    "exec.joins.join_expand", "exec.joins.join_probe_filter",
    "ops.join._build_finish", "ops.join.join_build_prep",
    "ops.join.join_exact2_range", "ops.join.join_lut",
    "ops.join.join_sorted_rows",
) + _PERM + _CONCAT + _FETCH
_REPARTITION = (
    "exec.repartition.repartition_hash", "exec.repartition.repartition_mask",
    "exec.repartition.repartition_bucket_counts",
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
    "UnionExec": _CONCAT,
    # sorts / limits
    "SortExec": ("exec.sort.limit_mask",) + _PERM + _CONCAT,
    "GlobalLimitExec": ("exec.sort.limit_mask",) + _PERM,
    # aggregates / joins / windows
    "HashAggregateExec": _AGG,
    "HashJoinExec": _JOIN,
    "CrossJoinExec": _JOIN,
    "WindowExec": (
        "exec.window.window_rank", "exec.window.window_frame",
    ) + _HOLISTIC + _PERM,
    "PercentileExec": (
        ("exec.percentile.percentile_interp",) + _HOLISTIC + _PERM
    ),
    # exchange boundary
    "HashRepartitionExec": _REPARTITION + _PERM,
    "ShuffleWriterExec": _REPARTITION + _PERM + _FETCH + _CONCAT,
    "ShuffleReaderExec": _PERM + _CONCAT,
    "UnresolvedShuffleExec": (),
    # mesh tier (shard_map stage programs compile through parallel/stage.py,
    # outside the jaxlint report targets; host-side they reuse ops/)
    "MeshAggregateExec": _AGG,
    "MeshJoinExec": _JOIN,
    "MeshSortExec": ("exec.sort.limit_mask",) + _PERM,
    "MeshWindowExec": (
        "exec.window.window_rank", "exec.window.window_frame",
    ) + _HOLISTIC + _PERM,
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

    fn = _argsort_program(dtype, cap, descending)
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
                    "ops.perm.sort_argsort", cap, (dt,),
                    variant=f"argsort,desc={int(desc)}",
                    compile=(
                        lambda dt=dt, cap=cap, desc=desc:
                        _warm_argsort(dt, cap, desc)
                    ),
                ))
            sigs.append(PrewarmSignature(
                "ops.perm.perm_take", cap, (dt,), variant="take",
                compile=lambda dt=dt, cap=cap: _warm_sort_pass(dt, cap),
            ))
        sigs.append(PrewarmSignature(
            "ops.compact.compact_invalid", cap, ("int64", "float64"),
            variant="compact",
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
    """The vocabulary slice a plan may dispatch (the analysis gate counts
    it as the plan's compile surface)."""
    out: set[str] = set()

    def walk(p) -> None:
        out.update(OPERATOR_KERNELS.get(type(p).__name__, ()))
        for c in p.children():
            walk(c)

    walk(plan)
    return out
