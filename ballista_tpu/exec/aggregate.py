"""Hash-aggregate operator (partial / final two-phase).

ref: HashAggregateExecNode with AggregateMode PARTIAL/FINAL
(ballista.proto:446-455 / 275-285, serde physical_plan mod.rs). TPU design:
per input batch, one fused sort-based ``group_aggregate`` kernel produces a
fixed-capacity partial state; partial states concat on device and a final
merge pass re-aggregates with the merge ops. AVG decomposes into SUM+COUNT
partials; COUNT merges by SUM (ops/aggregate.py AggOp.merge_op).

The partial/final split is the distributed repartition boundary: partial
outputs are what the reference's ShuffleWriter hash-partitions by group key
(SURVEY.md §2.5 "Hash repartition").
"""

from __future__ import annotations

import dataclasses
from typing import Iterator

import jax
import jax.numpy as jnp

from ballista_tpu.columnar.batch import DeviceBatch
from ballista_tpu.datatypes import DataType, Field, Schema
from ballista_tpu.errors import PlanError
from ballista_tpu.exec.base import (
    ExecutionPlan,
    TaskContext,
    UnknownPartitioning,
)
from ballista_tpu.expr import logical as L
from ballista_tpu.expr.physical import compile_expr
from ballista_tpu.ops.aggregate import (
    DENSE_AGG_MAX_SLOTS,
    AggOp,
    dense_factored,
    dense_group_aggregate,
    dense_slots,
    group_aggregate,
    scalar_aggregate,
)
from ballista_tpu.compilecache import metrics as compile_metrics
from ballista_tpu.obs import trace as obs_trace
from ballista_tpu.ops.concat import concat_batches
from ballista_tpu.ops.fetch import read_array


@dataclasses.dataclass(frozen=True)
class StateSlot:
    """One partial-state column: its AggOp and source column index in the
    pre-projected input (or None for COUNT(*))."""

    name: str
    op: AggOp
    src: int | None


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """Decomposition of logical aggregate expressions into partial state
    slots + final expressions over the merged state."""

    group_names: tuple[str, ...]
    slots: tuple[StateSlot, ...]
    # final output: (output name, dtype, state slot indices, kind)
    # kind: "id" -> slot value; "avg" -> s/c; "var_samp"/"var_pop"/
    # "stddev_samp"/"stddev_pop" -> (sum, sumsq, count);
    # "corr" -> (sx, sy, sxy, sx2, sy2, count)
    finals: tuple[tuple[str, DataType, tuple[int, ...], str], ...]
    # ordered distinct pre-projection argument expressions (the slots'
    # src indexes point past the group columns into this list) — the
    # single source of truth for the pre-projection, so decompositions
    # can synthesize exprs (x*x, null-masked pairs) no raw arg carries
    arg_exprs: tuple = ()


def decompose_aggregates(
    group_exprs: list[L.Expr],
    agg_exprs: list[L.Expr],
    input_schema: Schema,
) -> AggSpec:
    slots: list[StateSlot] = []
    finals: list[tuple[str, DataType, tuple[int, ...], str]] = []

    def slot_for(op: AggOp, src: int | None, name: str) -> int:
        for i, s in enumerate(slots):
            if s.op == op and s.src == src:
                return i
        slots.append(StateSlot(name, op, src))
        return len(slots) - 1

    # pre-projection layout: group cols first, then distinct agg args
    arg_index: dict[str, int] = {}
    arg_exprs: list[L.Expr] = []
    n_groups = len(group_exprs)

    def arg_slot(e: L.Expr) -> int:
        key = e.name()
        if key not in arg_index:
            arg_index[key] = n_groups + len(arg_exprs)
            arg_exprs.append(e)
        return arg_index[key]

    def _masked(e: L.Expr, other: L.Expr) -> L.Expr:
        """e where BOTH e and other are non-null, else NULL (CORR's
        pairwise-deletion semantics), via CASE over existing expr nodes."""
        cond = L.BinaryExpr(
            L.IsNotNull(e), L.Operator.AND, L.IsNotNull(other)
        )
        return L.Case(((cond, e),), None)

    for e in agg_exprs:
        aggs = L.find_aggregates(e)
        if len(aggs) != 1 or not aggs[0] is e:
            raise PlanError(
                f"aggregate expression {e.name()!r} must be a bare aggregate "
                "(planner rewrites arithmetic over aggregates)"
            )
        a = e
        out_dtype = a.data_type(input_schema)
        if isinstance(a, L.PercentileExpr):
            raise PlanError(
                "percentile aggregates must be split out by the optimizer "
                "(split_percentiles) before physical planning"
            )
        if isinstance(a, L.UdafExpr):
            from ballista_tpu.plugin import lookup_udaf

            udaf = lookup_udaf(a.uname)
            idxs = []
            for suffix, op_s, has_transform in udaf.states:
                arg = a.arg
                if has_transform:
                    arg = L.ScalarFunction(
                        f"__udaf_{a.uname}_{suffix}", (arg,)
                    )
                op = {
                    "sum": AggOp.SUM, "count": AggOp.COUNT,
                    "min": AggOp.MIN, "max": AggOp.MAX,
                }[op_s]
                src = arg_slot(arg)
                idxs.append(
                    slot_for(op, src, f"{a.name()}#{suffix}")
                )
            finals.append(
                (a.name(), out_dtype, tuple(idxs), f"udaf:{a.uname}")
            )
            continue
        if a.func == L.AggFunc.AVG:
            src = arg_slot(a.arg)
            i1 = slot_for(AggOp.SUM, src, f"{a.name()}#sum")
            i2 = slot_for(AggOp.COUNT, src, f"{a.name()}#count")
            finals.append((a.name(), out_dtype, (i1, i2), "avg"))
        elif a.func in (
            L.AggFunc.STDDEV, L.AggFunc.STDDEV_POP,
            L.AggFunc.VARIANCE, L.AggFunc.VAR_POP,
        ):
            x = L.Cast(a.arg, DataType.FLOAT64)
            src = arg_slot(x)
            sq = arg_slot(L.BinaryExpr(x, L.Operator.MULTIPLY, x))
            i1 = slot_for(AggOp.SUM, src, f"{a.name()}#sum")
            i2 = slot_for(AggOp.SUM, sq, f"{a.name()}#sumsq")
            i3 = slot_for(AggOp.COUNT, src, f"{a.name()}#count")
            kind = {
                L.AggFunc.STDDEV: "stddev_samp",
                L.AggFunc.STDDEV_POP: "stddev_pop",
                L.AggFunc.VARIANCE: "var_samp",
                L.AggFunc.VAR_POP: "var_pop",
            }[a.func]
            finals.append((a.name(), out_dtype, (i1, i2, i3), kind))
        elif a.func == L.AggFunc.CORR:
            x = L.Cast(_masked(a.arg, a.arg2), DataType.FLOAT64)
            y = L.Cast(_masked(a.arg2, a.arg), DataType.FLOAT64)
            sx = arg_slot(x)
            sy = arg_slot(y)
            sxy = arg_slot(L.BinaryExpr(x, L.Operator.MULTIPLY, y))
            sx2 = arg_slot(L.BinaryExpr(x, L.Operator.MULTIPLY, x))
            sy2 = arg_slot(L.BinaryExpr(y, L.Operator.MULTIPLY, y))
            i = tuple(
                slot_for(AggOp.SUM, src, f"{a.name()}#{k}")
                for k, src in (
                    ("sx", sx), ("sy", sy), ("sxy", sxy),
                    ("sx2", sx2), ("sy2", sy2),
                )
            ) + (slot_for(AggOp.COUNT, sx, f"{a.name()}#count"),)
            finals.append((a.name(), out_dtype, i, "corr"))
        elif a.func == L.AggFunc.COUNT:
            src = None if isinstance(a.arg, L.Wildcard) else arg_slot(a.arg)
            i = slot_for(AggOp.COUNT, src, f"{a.name()}#count")
            finals.append((a.name(), out_dtype, (i,), "id"))
        else:
            op = {
                L.AggFunc.SUM: AggOp.SUM,
                L.AggFunc.MIN: AggOp.MIN,
                L.AggFunc.MAX: AggOp.MAX,
            }[a.func]
            src = arg_slot(a.arg)
            i = slot_for(op, src, f"{a.name()}#{op.value}")
            finals.append((a.name(), out_dtype, (i,), "id"))

    return AggSpec(
        group_names=tuple(g.name() for g in group_exprs),
        slots=tuple(slots),
        finals=tuple(finals),
        arg_exprs=tuple(arg_exprs),
    )


import functools


@functools.lru_cache(maxsize=None)
def _ones_program(cap: int):
    def agg_ones():
        return jnp.ones(cap, dtype=jnp.int64)

    return jax.jit(agg_ones)


# -- disjoint clustered states (the streaming wide-cardinality path) ---------
#
# A GROUP BY over an input CLUSTERED on an integer key (TPC-H lineitem by
# l_orderkey) produces per-batch partial states whose key RANGES are
# disjoint except for at most the one group spanning each batch boundary.
# Folding such states through the generic merge is quadratic in the number
# of live groups (each incremental fold re-sorts everything seen so far —
# at SF=10 q18 that is 15M groups and ~60s/run). Instead: trim the shared
# boundary group into the previous state, keep every state as-is, and let
# the final stage finalize each state independently after a cheap
# range-disjointness check. No merge at any capacity ever runs.
# (DataFusion's analogue is its order-aware streaming aggregate.)

_INT_KEY_DTYPES = (
    DataType.INT32, DataType.INT64, DataType.DATE32, DataType.TIMESTAMP_US,
)

# -- exact decimal summation (see HashAggregateExec._dec_scaled_sums) --------
# Integrality tolerance: a true decimal's f64 representation deviates from
# integral (at its scale) by <= |v|*10^k*2^-52 ~ 1e-5 for TPC-H magnitudes;
# arbitrary floats deviate ~uniformly up to 0.5.
_DEC_TOL = 1e-3
# Magnitude bound: scaled |values| must SUM below f64's exact-integer range
# (with margin) so every reduction order yields the same exact integer.
_DEC_BOUND = float(1 << 52)


@functools.lru_cache(maxsize=None)
def _dec_learn_program(cap: int, has_null: bool):
    """Smallest scale k in {2,4,6} at which every live value is integral
    and the worst-case sum stays exactly representable; 99 = not decimal.
    int32 so defer_learn's cross-batch MAX picks a scale covering every
    batch (any 99 vetoes)."""

    def agg_dec_learn(col, valid, null):
        live = valid & ~null if has_null else valid
        code = jnp.int32(99)
        for k in (6, 4, 2):  # evaluate big->small so `code` ends smallest
            s = col * float(10 ** k)
            r = jnp.round(s)
            dev = jnp.max(jnp.where(live, jnp.abs(s - r), 0.0))
            total = jnp.sum(jnp.where(live, jnp.abs(r), 0.0))
            ok = (dev <= _DEC_TOL) & (total < _DEC_BOUND)
            code = jnp.where(ok, jnp.int32(k), code)
        return code

    return jax.jit(agg_dec_learn)


@functools.lru_cache(maxsize=None)
def _dec_scale_program(cap: int, has_null: bool, k: int):
    """(col, valid, null) -> (scaled INT64 column, validation ok).

    int64, not integral f64: the TPU's f64 matmul-prefix and the Pallas
    dense kernel accumulate through f32 splits (correctly rounded but not
    exact), while the x64 rewrite's int64 arithmetic is exact integer
    math on every backend — the sums come out bit-identical CPU vs TPU."""

    def agg_dec_scale(col, valid, null):
        live = valid & ~null if has_null else valid
        s = col * float(10 ** k)
        r = jnp.round(s)
        dev = jnp.max(jnp.where(live, jnp.abs(s - r), 0.0))
        total = jnp.sum(jnp.where(live, jnp.abs(r), 0.0))
        ok = (dev <= _DEC_TOL) & (total < _DEC_BOUND)
        return jnp.where(live, r, 0.0).astype(jnp.int64), ok

    return jax.jit(agg_dec_scale)


@functools.lru_cache(maxsize=None)
def _dec_unscale_program(sig: tuple):
    """Divide the scaled sum columns back to value units. sig: tuple of
    (col index, scale) pairs — one fused program per layout."""

    def agg_dec_unscale(cols):
        cols = list(cols)
        for i, scale in sig:
            cols[i] = cols[i] / scale
        return tuple(cols)

    return jax.jit(agg_dec_unscale)


@functools.lru_cache(maxsize=None)
def _bounds_program(cap: int, dtype: str, has_null_mask: bool):
    """(min live key, max live key, live count, has-null-key-group) for a
    single-int-key state — order-independent (reduction, not prefix
    peek). The null flag matters because group_aggregate stores the
    NULL-key group with the key column ZEROED + a null mask: its bounds
    would alias a real key-0 group, so a state carrying one must leave
    the disjoint path."""

    def agg_state_bounds(key_col, valid, key_nulls):
        n = jnp.sum(valid).astype(jnp.int32)
        big = jnp.iinfo(key_col.dtype).max
        kmin = jnp.min(jnp.where(valid, key_col, big))
        kmax = jnp.max(jnp.where(valid, key_col, -big - 1))
        if has_null_mask:
            has_null = jnp.any(valid & key_nulls)
        else:
            has_null = jnp.zeros((), dtype=bool)
        return kmin, kmax, n, has_null

    return jax.jit(agg_state_bounds)


def _state_bounds_dev(st: DeviceBatch):
    """Device bounds tuple for a state's key column (see
    _bounds_program)."""
    kcol = st.columns[0]
    knl = st.nulls[0]
    return _bounds_program(
        st.capacity, str(kcol.dtype), knl is not None
    )(kcol, st.valid, knl if knl is not None else st.valid)


def _slice_state(st: DeviceBatch, n: int) -> DeviceBatch:
    """Slice a front-compacted state down to its live prefix capacity (a
    free device slice — no compaction pass)."""
    from ballista_tpu.columnar.batch import round_capacity

    newcap = round_capacity(max(int(n), 16))
    if newcap >= st.capacity:
        return st
    return DeviceBatch(
        schema=st.schema,
        columns=tuple(c[:newcap] for c in st.columns),
        valid=st.valid[:newcap],
        nulls=tuple(m if m is None else m[:newcap] for m in st.nulls),
        dictionaries=dict(st.dictionaries),
    )


@functools.lru_cache(maxsize=None)
def _boundary_merge_program(
    merge_ops: tuple, prev_sig: tuple, next_sig: tuple,
    prev_nulls_sig: tuple, next_nulls_sig: tuple,
    prev_cap: int, next_cap: int,
):
    """Merge the ONE group shared by two otherwise-disjoint states: fold
    next's row for ``key`` into prev's row for ``key`` with the slot
    merge ops (SUM/MIN/MAX, null = 'no values seen'), then kill next's
    row. Element updates only — no sort, no capacity growth."""

    def merge_val(op: AggOp, a, a_nl, b, b_nl):
        if op == AggOp.SUM:
            v = jnp.where(a_nl, b, jnp.where(b_nl, a, a + b))
        elif op == AggOp.MIN:
            v = jnp.where(a_nl, b, jnp.where(b_nl, a, jnp.minimum(a, b)))
        else:  # MAX (COUNT merges as SUM)
            v = jnp.where(a_nl, b, jnp.where(b_nl, a, jnp.maximum(a, b)))
        return v, a_nl & b_nl

    def agg_boundary_merge(prev_cols, prev_nulls, prev_valid, next_cols,
                           next_nulls, next_valid, key):
        ip = jnp.argmax(prev_valid & (prev_cols[0] == key))
        inx = jnp.argmax(next_valid & (next_cols[0] == key))
        out_cols, out_nulls = [prev_cols[0]], [prev_nulls[0]]
        for j, op in enumerate(merge_ops):
            c = j + 1  # state layout: key, then slot columns
            a, b = prev_cols[c][ip], next_cols[c][inx]
            a_nl = (
                prev_nulls[c][ip] if prev_nulls[c] is not None
                else jnp.zeros((), dtype=bool)
            )
            b_nl = (
                next_nulls[c][inx] if next_nulls[c] is not None
                else jnp.zeros((), dtype=bool)
            )
            v, nl = merge_val(op, a, a_nl, b, b_nl)
            out_cols.append(prev_cols[c].at[ip].set(v.astype(prev_cols[c].dtype)))
            out_nulls.append(
                None if prev_nulls[c] is None
                else prev_nulls[c].at[ip].set(nl)
            )
        nx_valid = next_valid.at[inx].set(False)
        return tuple(out_cols), tuple(out_nulls), nx_valid

    return jax.jit(agg_boundary_merge)


def _merge_boundary(
    prev: DeviceBatch, nxt: DeviceBatch, merge_ops: tuple, key: int
) -> tuple[DeviceBatch, DeviceBatch]:
    prog = _boundary_merge_program(
        merge_ops,
        tuple(str(c.dtype) for c in prev.columns),
        tuple(str(c.dtype) for c in nxt.columns),
        tuple(m is None for m in prev.nulls),
        tuple(m is None for m in nxt.nulls),
        prev.capacity, nxt.capacity,
    )
    p_cols, p_nulls, nx_valid = prog(
        prev.columns, prev.nulls, prev.valid,
        nxt.columns, nxt.nulls, nxt.valid, key,
    )
    return (
        DeviceBatch(schema=prev.schema, columns=p_cols, valid=prev.valid,
                    nulls=p_nulls, dictionaries=dict(prev.dictionaries)),
        nxt.with_valid(nx_valid),
    )


@functools.lru_cache(maxsize=None)
def _state_batch_program(dtypes: tuple):
    """GroupAggResult -> state-shaped DeviceBatch with target dtypes (one
    cheap jitted cast/pack program per layout)."""

    def agg_state_batch(res, state_schema):
        import numpy as np

        cols = list(res.keys) + list(res.values)
        nulls = list(res.key_nulls) + list(res.value_nulls)
        # int32 is a permitted physical form of a logical INT64 column
        # (arrow_interop narrowing) — keep it narrow through agg states so
        # the final merge's sort passes stay 32-bit; mixed-width states
        # promote automatically at concat.
        cols = [
            c
            if c.dtype == f_.dtype.to_np()
            or (f_.dtype.to_np() == np.int64 and c.dtype == np.int32)
            else c.astype(f_.dtype.to_np())
            for c, f_ in zip(cols, state_schema)
        ]
        return DeviceBatch(
            schema=state_schema,
            columns=tuple(cols),
            valid=res.valid,
            nulls=tuple(nulls),
            dictionaries={},
        )

    return jax.jit(agg_state_batch, static_argnames=("state_schema",))


def _stat_final(outs_at, idxs, kind):
    """Shared var/stddev/corr finalization over state slots (``outs_at`` maps
    a slot index -> its merged value array).

    NUMERICAL DOMAIN NOTE: these use raw-moment formulas (sum, sum-of-
    squares); they are accurate while mean^2/variance stays well below
    f64's 2^53 (true for typical measure columns) but suffer catastrophic
    cancellation for huge-mean/tiny-variance data (e.g. raw unix
    timestamps) — variance can collapse toward 0 there. The fix is a
    (count, mean, M2) state with Chan's parallel merge (what DataFusion's
    Welford-based kernels do); that needs joint-slot merge support in the
    state machinery and is tracked for the next round. CORR is clamped to
    [-1, 1] so conditioning errors stay bounded.
    """
    if kind in ("var_samp", "var_pop", "stddev_samp", "stddev_pop"):
        s = outs_at(idxs[0]).astype(jnp.float64)
        s2 = outs_at(idxs[1]).astype(jnp.float64)
        c = outs_at(idxs[2]).astype(jnp.float64)
        pop = kind.endswith("_pop")
        denom = jnp.maximum(c if pop else c - 1, 1.0)
        var = jnp.maximum((s2 - s * s / jnp.maximum(c, 1.0)) / denom, 0.0)
        vals = jnp.sqrt(var) if kind.startswith("stddev") else var
        nl = (c == 0) if pop else (c < 2)
        return vals, nl
    assert kind == "corr"
    sx = outs_at(idxs[0]).astype(jnp.float64)
    sy = outs_at(idxs[1]).astype(jnp.float64)
    sxy = outs_at(idxs[2]).astype(jnp.float64)
    sx2 = outs_at(idxs[3]).astype(jnp.float64)
    sy2 = outs_at(idxs[4]).astype(jnp.float64)
    c = outs_at(idxs[5]).astype(jnp.float64)
    cn = jnp.maximum(c, 1.0)
    cov = sxy - sx * sy / cn
    dd = (sx2 - sx * sx / cn) * (sy2 - sy * sy / cn)
    vals = jnp.clip(cov / jnp.sqrt(jnp.maximum(dd, 1e-300)), -1.0, 1.0)
    nl = (c == 0) | (dd <= 0)
    return vals, nl


def _scalar_state_program(slots, schema: Schema, b: DeviceBatch) -> DeviceBatch:
    """Per-batch scalar (no GROUP BY) partial state. Module-level on
    purpose: the jitted wrapper lives in the process-wide trace cache
    (compilecache/tracecache.py), so it must capture only these small
    derived values — never the HashAggregateExec instance, whose input
    chain reaches scan tables and uploaded device batches."""
    val_cols, val_nulls = [], []
    for s in slots:
        if s.src is None:
            val_cols.append(jnp.ones(b.capacity, dtype=jnp.int64))
            val_nulls.append(None)
        else:
            val_cols.append(b.columns[s.src])
            val_nulls.append(b.nulls[s.src])
    outs, nulls = scalar_aggregate(
        b.valid, val_cols, val_nulls, [s.op for s in slots]
    )
    cols = []
    for v, f in zip(outs, schema):
        arr = jnp.zeros(2048, dtype=f.dtype.to_np()).at[0].set(
            v.astype(f.dtype.to_np())
        )
        cols.append(arr)
    valid = jnp.zeros(2048, dtype=bool).at[0].set(True)
    null_masks = []
    for nl in nulls:
        if nl is None:
            null_masks.append(None)
        else:
            null_masks.append(jnp.zeros(2048, dtype=bool).at[0].set(nl))
    return DeviceBatch(
        schema=schema,
        columns=tuple(cols),
        valid=valid,
        nulls=tuple(null_masks),
        dictionaries={},
    )


def _finalize_scalar_program(finals, schema: Schema, outs, nulls) -> DeviceBatch:
    """Scalar-aggregate finalization (AVG division, statistical finals,
    pass-through) to a 1-valid-row batch. Module-level for the same
    trace-cache capture discipline as _scalar_state_program."""
    cap = 2048
    cols, null_masks = [], []
    for name, dtype, idxs, kind in finals:
        if kind == "avg":
            s, c = outs[idxs[0]], outs[idxs[1]]
            v = s.astype(jnp.float64) / jnp.maximum(c, 1).astype(jnp.float64)
            nl = c == 0
        elif kind in (
            "var_samp", "var_pop", "stddev_samp", "stddev_pop", "corr"
        ):
            v, nl = _stat_final(lambda i: outs[i], idxs, kind)
        else:
            v = outs[idxs[0]]
            nl = nulls[idxs[0]]
        arr = jnp.zeros(cap, dtype=dtype.to_np()).at[0].set(
            v.astype(dtype.to_np())
        )
        cols.append(arr)
        if nl is None:
            null_masks.append(None)
        else:
            null_masks.append(jnp.zeros(cap, dtype=bool).at[0].set(nl))
    valid = jnp.zeros(cap, dtype=bool).at[0].set(True)
    return DeviceBatch(
        schema=schema,
        columns=tuple(cols),
        valid=valid,
        nulls=tuple(null_masks),
        dictionaries={},
    )


def finalize_state(
    state: DeviceBatch, spec: AggSpec, out_schema: Schema
) -> DeviceBatch:
    """Merged state batch (group keys ++ slot values, positional slot
    order) -> final output batch: AVG divides its SUM/COUNT slots, others
    pass through with the output dtype. Shared by the local final aggregate
    and the mesh (shard_map) aggregate, whose state layouts match."""
    n_groups = len(spec.group_names)
    cols = list(state.columns[:n_groups])
    nulls = list(state.nulls[:n_groups])
    dicts = {
        k: v
        for k, v in state.dictionaries.items()
        if any(f.name == k for f in out_schema.fields[:n_groups])
    }
    for name, dtype, idxs, kind in spec.finals:
        if kind == "avg":
            s = state.columns[n_groups + idxs[0]]
            c = state.columns[n_groups + idxs[1]]
            vals = s.astype(jnp.float64) / jnp.maximum(c, 1).astype(
                jnp.float64
            )
            nl = c == 0
            base_null = state.nulls[n_groups + idxs[0]]
            if base_null is not None:
                nl = nl | base_null
        elif kind in (
            "var_samp", "var_pop", "stddev_samp", "stddev_pop", "corr"
        ):
            vals, nl = _stat_final(
                lambda i: state.columns[n_groups + i], idxs, kind
            )
        elif kind.startswith("udaf:"):
            from ballista_tpu.plugin import lookup_udaf

            udaf = lookup_udaf(kind[5:])
            vals = udaf.finalize(
                *(state.columns[n_groups + i] for i in idxs)
            )
            # NULL for groups whose count state saw no live rows; without
            # a count state the finalize result stands as computed
            nl = None
            for (suffix, op_s, _), i in zip(udaf.states, idxs):
                if op_s == "count":
                    nl = state.columns[n_groups + i] == 0
                    break
        else:
            vals = state.columns[n_groups + idxs[0]]
            nl = state.nulls[n_groups + idxs[0]]
            if dtype == DataType.STRING:
                # dictionary rides under the state slot's field name; re-key
                # it to the final output name (MIN/MAX over a coded column)
                slot_name = state.schema.fields[n_groups + idxs[0]].name
                d = state.dictionaries.get(slot_name)
                if d is not None:
                    dicts[name] = d
        want = dtype.to_np()
        if vals.dtype != want:
            vals = vals.astype(want)
        cols.append(vals)
        nulls.append(nl)
    return DeviceBatch(
        schema=out_schema,
        columns=tuple(cols),
        valid=state.valid,
        nulls=tuple(nulls),
        dictionaries=dicts,
    )


_OVERFLOW_MESSAGE = (
    "aggregate exceeded group capacity; raise ballista.tpu.agg_capacity"
)


class HashAggregateExec(ExecutionPlan):
    """mode='partial' emits group keys + state columns per input partition;
    mode='final' merges partial outputs into final values (single output
    partition unless fed by a hash repartition). ``subquery`` marks the
    two halves of an aggregate that decorrelates a scalar subquery
    (``plan.logical.Aggregate.subquery``): the partial counts its live
    input rows (``subquery_rows``) and the executor sums the marked
    operators into ``subquery.*`` as a task ends."""

    # Max per-batch partial states held live before an incremental fold
    # (see _execute_partial): bounds HBM at wide cardinalities.
    _FOLD_WIDTH = 4
    # backpressure async-copy support latch: flipped False on the first
    # platform refusal so later folds skip the raise/except round trip
    _bp_async_ok = True
    # Disjoint-path bounds are settled once per this many batches: one
    # blocking fetch is a full host round trip (cost not measured on the
    # attached chip), while
    # the queued states bound in-flight HBM to ~a chunk of batch pipelines.
    _SETTLE_CHUNK = 8

    def __init__(
        self,
        input: ExecutionPlan,
        group_exprs: list[L.Expr],
        agg_exprs: list[L.Expr],
        mode: str,  # "partial" | "final"
        spec: AggSpec | None = None,
        capacity: int | None = None,
        planned_input_schema: Schema | None = None,
        subquery: bool = False,
    ) -> None:
        super().__init__()
        if mode not in ("partial", "final"):
            raise PlanError(f"bad aggregate mode {mode}")
        self.input = input
        self.group_exprs = list(group_exprs)
        self.agg_exprs = list(agg_exprs)
        self.mode = mode
        self.capacity = capacity
        self.subquery = subquery
        self._jit_cache: dict = {}
        ins = input.schema()
        # Schema the aggregate exprs were planned against (= the partial's
        # input); carried through final mode for plan serde round-trips.
        self.planned_input_schema = (
            planned_input_schema if planned_input_schema is not None else ins
        )
        if mode == "partial":
            self.spec = (
                spec
                if spec is not None
                else decompose_aggregates(group_exprs, agg_exprs, ins)
            )
            # partial input pre-projection: groups then args
            self._pre_exprs = list(group_exprs) + list(self.spec.arg_exprs)
            pre_schema_fields = [
                Field(e.name(), e.data_type(ins), e.nullable(ins))
                for e in self._pre_exprs
            ]
            self._pre_schema = Schema(pre_schema_fields)
            self._schema = self._partial_schema(self._pre_schema)
        else:
            if spec is None:
                raise PlanError("final aggregate requires the partial's spec")
            self.spec = spec
            self._schema = self._final_schema(ins)

    # -- schemas -------------------------------------------------------------
    def _partial_schema(self, pre: Schema) -> Schema:
        fields = [pre.fields[i] for i in range(len(self.spec.group_names))]
        for s in self.spec.slots:
            if s.op == AggOp.COUNT:
                dt = DataType.INT64
            else:
                src_field = pre.fields[s.src]
                dt = src_field.dtype
                if s.op == AggOp.SUM:
                    dt = (
                        DataType.INT64
                        if dt.is_integer or dt == DataType.BOOL
                        else DataType.FLOAT64
                        if dt.is_floating
                        else dt
                    )
            fields.append(Field(s.name, dt, True))
        return Schema(fields)

    def _final_schema(self, partial: Schema) -> Schema:
        ng = len(self.spec.group_names)
        fields = list(partial.fields[:ng])
        for name, dtype, _, _ in self.spec.finals:
            fields.append(Field(name, dtype, True))
        return Schema(fields)

    def schema(self) -> Schema:
        return self._schema

    def children(self) -> list[ExecutionPlan]:
        return [self.input]

    def output_partitioning(self):
        if self.mode == "partial":
            return self.input.output_partitioning()
        # final mode merges per input partition: beneath a coalesce this is
        # the classic 1-partition funnel; beneath a hash repartition (or a
        # resolved shuffle read) it is K parallel merge tasks, each owning
        # the groups of its hash bucket (ref planner.rs:133-157)
        return UnknownPartitioning(self.input.output_partitioning().n)

    def describe(self) -> str:
        g = ", ".join(self.spec.group_names)
        a = ", ".join(s.name for s in self.spec.slots)
        mark = ", subquery" if self.subquery else ""
        return (f"HashAggregateExec(mode={self.mode}{mark}): gby=[{g}], "
                f"aggr=[{a}]")

    # -- execution -----------------------------------------------------------
    def _agg_capacity(self, ctx: TaskContext) -> int:
        # adaptive retry override (set by run_with_capacity_retry after an
        # overflow) wins over both the planned and the configured capacity
        if ctx.agg_capacity_override:
            return max(ctx.agg_capacity_override, self.capacity or 0)
        return self.capacity or ctx.config.agg_capacity()

    def _dec_scaled_sums(
        self, val_cols, val_nulls, ops, batch, ctx, site, from_state
    ):
        """Exact decimal summation: float64 SUM inputs that are decimals
        (TPC-H money/quantity — every value integral at 10^k, k<=6) are
        rounded to INTEGRAL f64 at scale 10^k before the kernel and the
        resulting sums divided back after. Integral-f64 reductions below
        2^52 are exact in ANY order — money sums become order-independent
        and bit-identical across batches, tiers, and backends (CPU vs
        TPU), which float SUM's reduction-order sensitivity breaks
        (VERDICT r4 item 4; ref Decimal128 datafusion.proto:411-420 —
        carried exactly through DataFusion's aggregate kernels).

        k is LEARNED per (site, slot) on the first run (smallest of
        2/4/6 whose integrality and 2^52 magnitude bound hold, 99 = not
        decimal) through the plan cache, and every scaled run re-validates
        on device via a deferred flag — stale data falls back through
        SpeculationMiss like every other learned fast path. Returns
        (val_cols, unscale list aligned with slots)."""
        unscale = [None] * len(val_cols)
        cache = ctx.plan_cache if ctx is not None else None
        if cache is None or site is None:
            return val_cols, unscale
        job = getattr(ctx, "job_id", "")
        out = list(val_cols)
        for j, (vc, vn, op) in enumerate(zip(val_cols, val_nulls, ops)):
            if op != AggOp.SUM or vc.dtype != jnp.float64:
                continue
            # merge sites ("dec_sum_last") REPLACE their learned scale
            # each run instead of max-vetoing: their run-1 inputs are
            # inexact plain-float partial sums and only become integral
            # once the partial pass itself runs scaled (run 2+)
            key = (
                ("dec_sum_last" if from_state else "dec_sum"),
                job, site, j,
            )
            code = cache.get(key)
            live_args = (
                batch.valid,
                vn if vn is not None else batch.valid,
                vn is not None,
            )
            if code is None or (from_state and code not in (2, 4, 6)):
                ctx.defer_learn(
                    key,
                    _dec_learn_program(vc.shape[0], live_args[2])(
                        vc, live_args[0], live_args[1]
                    ),
                )
                continue
            if code not in (2, 4, 6):
                continue
            scaled, ok = _dec_scale_program(
                vc.shape[0], live_args[2], int(code)
            )(vc, live_args[0], live_args[1])
            ctx.defer_speculation(
                ~ok,
                "decimal-sum scaling went stale (values no longer "
                "integral at the learned scale, or sum bound exceeded)",
                [key],
            )
            out[j] = scaled
            unscale[j] = float(10 ** int(code))
        return out, unscale

    def _run_group_agg(
        self,
        batch: DeviceBatch,
        ops: list[AggOp],
        n_groups: int,
        cap: int,
        from_state: bool,
        ctx: TaskContext | None = None,
        site: str | None = None,
    ) -> DeviceBatch:
        """One jitted group_aggregate pass -> state-shaped DeviceBatch.
        ``from_state``: value columns are already state slots (merge pass);
        otherwise they come from the pre-projection via each slot's ``src``
        (first partial pass). The overflow flag is deferred to the task
        boundary (one batched fetch) instead of a per-pass device sync."""
        # group_aggregate host-composes cached sort passes + jitted
        # finishers — do NOT wrap it in another jit (that would re-inline
        # the sorts into one slow-compiling program).
        key_cols = [batch.columns[i] for i in range(n_groups)]
        key_nulls = [batch.nulls[i] for i in range(n_groups)]
        val_cols, val_nulls = [], []
        for j, s in enumerate(self.spec.slots):
            if from_state:
                idx = n_groups + j
                val_cols.append(batch.columns[idx])
                val_nulls.append(batch.nulls[idx])
            elif s.src is None:  # COUNT(*): count valid rows
                val_cols.append(_ones_program(batch.capacity)())
                val_nulls.append(None)
            else:
                val_cols.append(batch.columns[s.src])
                val_nulls.append(batch.nulls[s.src])
        # group count can never exceed the batch's row capacity, so clamp the
        # kernel capacity — keeps small batches cheap even when the session
        # capacity was grown for a big merge
        cap = min(cap, max(batch.capacity, 16))
        # dictionary-coded / boolean keys with a small domain take the dense
        # (sort-free, one fused program) kernel — the q1 shape
        vocab = self._dense_vocab(batch, n_groups)
        # exact decimal summation (sort path only): money/quantity columns
        # sum as scaled int64 (order-independent, bit-exact across tiers);
        # sums divide back below. The dense kernel keeps f64 — up to
        # 2048 slots int64 sums take a scatter where f64 sums ride the
        # one-hot matmul, and its f32-split Pallas matmul is deliberately
        # approximate (~2e-8, ops/pallas_agg.py).
        if vocab is None:
            val_cols, dec_unscale = self._dec_scaled_sums(
                val_cols, val_nulls, ops, batch, ctx, site, from_state
            )
        else:
            dec_unscale = [None] * len(val_cols)
        if vocab is not None:
            compile_metrics.add("agg.dense_passes")
            if dense_factored(dense_slots(vocab)):
                compile_metrics.add("agg.dense_factored_passes")
            res = dense_group_aggregate(
                key_cols, key_nulls, vocab, batch.valid, val_cols,
                val_nulls, list(ops),
            )
        else:
            # Clustered-input speculation: when a prior run LEARNED (off
            # the stable sort's permutation — free) that this site's rows
            # arrive grouped-adjacent on the keys (TPC-H lineitem grouped
            # by l_orderkey; merge passes over concatenated clustered
            # states), skip the sort + gather entirely and validate the
            # assumption with a deferred flag (stale -> SpeculationMiss
            # invalidates + retries, the shrink/join-strategy protocol).
            cache = ctx.plan_cache if ctx is not None else None
            skey = (
                (
                    "agg_sorted",
                    getattr(ctx, "job_id", ""),
                    site,
                    from_state,
                    batch.capacity,
                )
                if (cache is not None and site is not None)
                else None
            )
            cached = cache.get(skey) if skey is not None else None
            compile_metrics.add("agg.sort_passes")
            res = group_aggregate(
                key_cols, key_nulls, batch.valid, val_cols, val_nulls,
                list(ops), cap, presorted=cached is True,
            )
            if cached is True:
                ctx.defer_speculation(
                    ~res.sorted_ok,
                    "clustered-input aggregate speculation went stale "
                    "(rows no longer grouped-adjacent)",
                    [skey],
                )
            elif (
                skey is not None
                and cached is None
                and res.input_was_sorted is not None
            ):
                ctx.defer_learn(skey, res.input_was_sorted)
        if ctx is not None:
            ctx.defer_check(
                res.overflow, _OVERFLOW_MESSAGE, required=res.n_groups
            )
            if vocab is None:
                # a dense state has a slot for every key: only the sort
                # path's can overflow (see _raise_overflow)
                ctx.run_state.setdefault("agg_overflow", []).append(
                    (res.overflow, res.n_groups)
                )
        else:
            res.check_overflow()
        state_schema = batch.schema if from_state else self._schema
        dtypes = tuple(f.dtype.value for f in state_schema)
        out = _state_batch_program(dtypes)(res, state_schema)
        if any(s is not None for s in dec_unscale):
            sig = tuple(
                (n_groups + j, s)
                for j, s in enumerate(dec_unscale)
                if s is not None
            )
            out = DeviceBatch(
                schema=out.schema,
                columns=_dec_unscale_program(sig)(out.columns),
                valid=out.valid,
                nulls=out.nulls,
                dictionaries=dict(out.dictionaries),
            )
        dicts = {
            k: v
            for k, v in batch.dictionaries.items()
            if any(
                f.name == k and f.dtype == DataType.STRING
                for f in state_schema
            )
        }
        if not from_state:
            # STRING value slots (MIN/MAX over a coded column) carry their
            # source column's dictionary under the slot's renamed field
            for j, s in enumerate(self.spec.slots):
                f = state_schema.fields[n_groups + j]
                if f.dtype == DataType.STRING and s.src is not None:
                    d = batch.dictionaries.get(batch.schema.fields[s.src].name)
                    if d is not None:
                        dicts[f.name] = d
        return DeviceBatch(
            schema=out.schema,
            columns=out.columns,
            valid=out.valid,
            nulls=out.nulls,
            dictionaries=dicts,
        )

    @staticmethod
    def _raise_overflow(ctx: TaskContext) -> None:
        """Raise the ``CapacityError`` of a sort-path pass that overflowed,
        before a fold and not at the task's end. An overflowed state is
        truncated, so folding it is work the retry throws away: on a cold
        process, the sort and segment programs of a state size the retry
        never uses (PR 29: the first h2oai group-by of a process compiled
        them at 3 x 65,536 rows and again at 3 x 131,072). The flags were
        computed with their passes and their host copies started then
        (``defer_check``); the read waits for the last pass."""
        pending = ctx.run_state.pop("agg_overflow", None)
        if not pending:
            return
        import numpy as np

        from ballista_tpu.errors import CapacityError

        with obs_trace.phase("task.d2h", site="agg.overflow") as ph:
            read = [(np.asarray(f), np.asarray(r)) for f, r in pending]
            ph.nbytes = sum(f.nbytes + r.nbytes for f, r in read)
        fired = [int(required) for flag, required in read if flag]
        if fired:
            raise CapacityError(_OVERFLOW_MESSAGE, required=max(fired))

    @staticmethod
    def _dense_vocab(batch: DeviceBatch, n_groups: int) -> list[int] | None:
        """Vocab sizes when EVERY group key is dictionary-coded (STRING) or
        BOOL and the dense slot space stays small; None otherwise."""
        if n_groups == 0:
            return None
        vocab: list[int] = []
        slots = 1
        for i in range(n_groups):
            f = batch.schema.fields[i]
            if f.dtype == DataType.STRING:
                d = batch.dictionaries.get(f.name)
                if d is None or len(d.values) == 0:
                    return None
                vocab.append(len(d.values))
            elif f.dtype == DataType.BOOL:
                vocab.append(2)
            else:
                return None
            slots *= vocab[-1] + 1
            if slots > DENSE_AGG_MAX_SLOTS:
                return None
        return vocab

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[DeviceBatch]:
        cap = self._agg_capacity(ctx)
        n_groups = len(self.spec.group_names)
        if self.mode == "partial":
            yield from self._execute_partial(partition, ctx, cap, n_groups)
        else:
            yield from self._execute_final(partition, ctx, cap, n_groups)

    def _execute_partial(
        self, partition: int, ctx: TaskContext, cap: int, n_groups: int
    ) -> Iterator[DeviceBatch]:
        from ballista_tpu.exec.pipeline import ProjectionExec

        # cached on self: a fresh ProjectionExec per call would rebuild
        # (and re-trace) the fused filter+projection chain every
        # partition of every run, defeating the plan cache
        if getattr(self, "_pre_plan", None) is None:
            self._pre_plan = ProjectionExec(self.input, self._pre_exprs)
        pre = self._pre_plan
        ops = [s.op for s in self.spec.slots]

        if n_groups == 0:
            # scalar aggregate: one-row state per partition
            states: list[DeviceBatch] = []
            for b in pre.execute(partition, ctx):
                self._count_subquery_rows(b)
                with self.metrics.time("agg_time"):
                    states.append(self._scalar_state_fn()(b))
            if not states:
                return
            merged = concat_batches(states) if len(states) > 1 else states[0]
            yield merged
            return

        partials: list[DeviceBatch] = []
        site = self.display()
        merge_ops = [s.op.merge_op for s in self.spec.slots]
        bp_prev = None  # previous fold's async-copied backpressure flag

        def fold(states: list[DeviceBatch]) -> DeviceBatch:
            self._raise_overflow(ctx)
            # slice states down to a learned capacity first (they are
            # front-compacted), keeping the fold's row count proportional
            # to actual groups, not capacity
            states = self._slice_states(states, ctx, site, partition)
            return self._run_group_agg(
                concat_batches(states), merge_ops, n_groups, cap,
                from_state=True, ctx=ctx, site=site + "|fold",
            )

        # Disjoint-clustered fast path: single int key and per-batch
        # state ranges that never overlap (clustered source). States are
        # kept individually (sliced to their live prefix) and NO fold ever
        # runs — the final stage sees range-disjoint states, trims the one
        # boundary-spanning group, and finalizes each independently.
        # Bounds are settled in CHUNKS (one batched fetch per
        # _SETTLE_CHUNK batches — each blocking fetch is a full host round
        # trip), and a short input skips the
        # partial-side fetch entirely, deferring resolution to the final
        # stage's own single fetch. The chunk fetch doubles as pipeline
        # backpressure, bounding in-flight upstream work.
        disjoint = (
            n_groups == 1
            and self._schema.fields[0].dtype in _INT_KEY_DTYPES
        )
        prev_last = None
        entries: list = []  # queued (state, device-bounds) pairs

        def settle_entries() -> None:
            """Resolve every queued (state, bounds) pair in ONE batched
            fetch, slicing each state to its live prefix and recording
            host bounds for the final stage. A NULL-key group or a range
            overlap disqualifies the disjoint layout by clearing the
            nonlocal ``disjoint`` (the loop then reverts to the fold
            discipline)."""
            nonlocal prev_last, disjoint
            from ballista_tpu.ops.fetch import fetch_arrays

            if not entries:
                return
            raw = []
            for _, dev, _c in entries:
                raw.extend(dev)
            vals = [int(v) for v in fetch_arrays(raw, site="agg.bounds")]
            ok = disjoint
            for i, (st, _, _c) in enumerate(entries):
                first, last, n, has_null = vals[4 * i : 4 * i + 4]
                if n == 0:
                    continue
                st = _slice_state(st, n)
                if has_null or (
                    ok and prev_last is not None and first < prev_last
                ):
                    # a NULL-key group rides with key 0 + a null mask (its
                    # bounds alias a real key-0 group); a backward first
                    # key means the source is not clustered
                    self.metrics.add("disjoint_break")
                    ok = False
                elif ok:
                    # exactly-touching ranges (first == prev_last) stay on
                    # the disjoint path: the final stage trims the shared
                    # boundary group the same way it does across upstream
                    # partitions
                    st.host_bounds = (first, last, n, 0)
                    prev_last = last
                partials.append(st)
            entries.clear()
            disjoint = ok

        # Fold incrementally (the general path): a wide-cardinality
        # aggregate's per-batch states are capacity-sized device arrays,
        # and holding one per input batch OOMs HBM at scale (SF=10
        # lineitem = ~30 batches x a multi-M-row group capacity blew a
        # 16GB chip). Folding every few batches bounds live states to
        # _FOLD_WIDTH at the cost of re-merging already-folded groups
        # (merge ops are associative).
        for b in pre.execute(partition, ctx):
            self._count_subquery_rows(b)
            with self.metrics.time("agg_time"):
                # per-batch states come out at min(cap, batch capacity)
                # (_run_group_agg clamps internally) — a batch of N rows
                # holds at most N groups
                st = self._run_group_agg(
                    b, ops, n_groups, cap, from_state=False, ctx=ctx,
                    site=site,
                )
                if disjoint:
                    dev = _state_bounds_dev(st)
                    copied = True
                    for a in dev:
                        try:
                            a.copy_to_host_async()
                        except Exception:
                            copied = False
                    entries.append((st, dev, copied))
                    if len(entries) >= self._SETTLE_CHUNK:
                        settle_entries()
                else:
                    partials.append(st)
                if not disjoint and len(partials) >= self._FOLD_WIDTH:
                    partials = [fold(partials)]
                    # BACKPRESSURE: dispatch is asynchronous, so without
                    # a real sync the host enqueues
                    # every batch's whole upstream pipeline and the device
                    # holds buffers for ALL of them — at SF=10 that is ~30
                    # in-flight lineitem batches of HBM. Pipelined drain:
                    # start an async host copy of THIS fold's flag and
                    # block on the PREVIOUS fold's — in-flight work stays
                    # bounded at ~2 fold windows while the round trip
                    # overlaps the next window's dispatch. Folds never
                    # fire below _FOLD_WIDTH batches, so short queries
                    # pay nothing.
                    flag = partials[0].valid[:1]
                    if self._bp_async_ok:
                        try:
                            flag.copy_to_host_async()
                        except Exception:
                            # platform without async copies: latch it so
                            # later folds stop raising per batch — the
                            # asarray below still syncs, just without
                            # copy/dispatch overlap
                            self._bp_async_ok = False
                    if bp_prev is not None:
                        read_array(bp_prev, "agg.backpressure")
                    bp_prev = flag
            self.metrics.add("input_batches")
        if entries:
            with self.metrics.time("agg_time"):
                if not partials:
                    # Short input (every batch still queued): skip the
                    # partial-side bounds fetch entirely. States are
                    # sliced via the learned-capacity speculation (zero
                    # sync) and carry their pre-copied device bounds, so
                    # the final stage resolves disjointness in its OWN
                    # single batched fetch — or, for a lone state, not at
                    # all.
                    sts = [st for st, _, _c in entries]
                    for s2, (_, dev, copied) in zip(
                        self._slice_states(sts, ctx, site, partition),
                        entries,
                    ):
                        if copied:
                            # final resolves these host-side, no fetch
                            s2.dev_bounds = dev
                        partials.append(s2)
                    entries.clear()
                else:
                    settle_entries()
        if not partials:
            return
        # every state this partial emits is key-unique on its own (a
        # per-batch grouping or a fold, both of which dedup) — mark them
        # so the final stage's merge-skip and disjoint paths can trust
        # uniqueness (a reader-concatenated batch carries no mark)
        if len(partials) == 1:
            partials[0].keys_unique = True
            yield partials[0]
            return
        if disjoint:
            # range-disjoint states: the final stage resolves bounds and
            # trims any boundary-spanning group before finalizing
            for st in partials:
                st.keys_unique = True
            yield from partials
            return
        # final fold of this partition's remaining states (bounds shuffle
        # volume: one folded state leaves the partition)
        with self.metrics.time("agg_time"):
            out = fold(partials)
            out.keys_unique = True
            yield out

    def _count_subquery_rows(self, b: DeviceBatch) -> None:
        """A decorrelating partial's live input rows, as a lazy device
        scalar that ``Metrics.summary`` resolves with the task's other
        counters."""
        if self.subquery:
            self.metrics.add("subquery_rows", b.valid.sum())

    def _spec_cache_key(self) -> tuple:
        """Canonical signature of the scalar-aggregate programs: the spec
        decomposition + output schema are everything their closures read
        from the instance, so executor-decoded fresh instances share one
        jit wrapper per signature (compilecache/tracecache.py)."""
        from ballista_tpu.compilecache import expr_key, schema_key

        s = self.spec
        return (
            s.group_names,
            s.slots,
            s.finals,
            tuple(expr_key(e) for e in s.arg_exprs),
            schema_key(self._schema),
        )

    def _scalar_state_fn(self):
        """Jitted per-batch scalar state (one program instead of eager
        per-op dispatches, each of which is a launch of its own)."""
        if getattr(self, "_scalar_jit", None) is None:
            from ballista_tpu.compilecache import shared_callable

            # capture only the small derived values the program reads —
            # a bound method would pin this whole plan subtree (scan
            # tables, uploaded device batches) in the process-wide cache
            slots, schema = self.spec.slots, self._schema

            def build():
                def agg_scalar_state(b):
                    return _scalar_state_program(slots, schema, b)

                return jax.jit(agg_scalar_state)

            self._scalar_jit = shared_callable(
                ("agg_scalar_state",) + self._spec_cache_key(), build
            )
        return self._scalar_jit

    def _execute_final(
        self, partition: int, ctx: TaskContext, cap: int, n_groups: int
    ) -> Iterator[DeviceBatch]:
        # merge ONLY this output partition's input partition: the planner
        # guarantees the input is either a 1-partition coalesce (funnel) or
        # a hash repartition on the group keys (K parallel merges)
        merge_ops = [s.op.merge_op for s in self.spec.slots]
        budget = ctx.config.hbm_budget_mb() << 20
        if budget and n_groups > 0:
            # incremental collection: the moment the running state total
            # crosses the budget, already-resident states drain to host
            # buckets and the rest of the stream follows — the set is
            # never fully device-resident (a list() here would OOM before
            # any budget check could run)
            states, grace = self._collect_states_grace(
                partition, ctx, budget, n_groups
            )
            if grace is not None:
                yield from self._grace_merge(
                    grace, ctx, cap, n_groups, merge_ops, budget
                )
                return
        else:
            states = list(self.input.execute(partition, ctx))
        if not states:
            return
        if n_groups == 0:
            # one jitted program for merge-concat + scalar merge + final
            # (eagerly this is ~15 separate dispatches; their cost on the
            # attached chip is not measured)
            if getattr(self, "_scalar_final_jit", None) is None:
                from ballista_tpu.compilecache import shared_callable

                # close over derived values only (see _scalar_state_fn):
                # the process-wide cache must not pin the plan subtree
                n_slots = len(self.spec.slots)
                finals, schema = self.spec.finals, self._schema

                def build():
                    def agg_scalar_final(sts):
                        merged = (
                            concat_batches(sts) if len(sts) > 1 else sts[0]
                        )
                        outs, nulls = scalar_aggregate(
                            merged.valid,
                            [merged.columns[i] for i in range(n_slots)],
                            [merged.nulls[i] for i in range(n_slots)],
                            merge_ops,
                        )
                        return _finalize_scalar_program(
                            finals, schema, outs, nulls
                        )

                    return jax.jit(agg_scalar_final)

                self._scalar_final_jit = shared_callable(
                    ("agg_scalar_final",) + self._spec_cache_key(), build
                )
            with self.metrics.time("merge_time"):
                yield self._scalar_final_jit(states)
            return
        if len(states) == 1 and getattr(states[0], "keys_unique", False):
            # The partial marks every state IT emits as key-unique (each is
            # one per-batch grouping or a fold — both dedup), and masking
            # repartitions preserve the mark. A lone marked state needs no
            # merge — the merge aggregation would re-sort the full state
            # capacity only to rediscover the same groups. A lone UNMARKED
            # state (e.g. a shuffle reader that concatenated several
            # partial states into one batch — those can share boundary
            # keys, or overlap entirely for short unclustered inputs)
            # falls through to the general merge below.
            # (Timed under merge_time so per-query metric reports stay
            # comparable with the merging shape.)
            with self.metrics.time("merge_time"):
                out = self._finalize(states[0], n_groups)
            yield out
            return
        if (
            n_groups == 1
            and self._schema.fields[0].dtype in _INT_KEY_DTYPES
            # the range-disjoint argument needs keys unique WITHIN each
            # state too — an unmarked state (reader-concatenated partials)
            # can carry internal duplicates that cross-state bounds
            # cannot see
            and all(getattr(st, "keys_unique", False) for st in states)
        ):
            # Range-disjoint states (the clustered partial emission, or
            # any shuffle layout that happens to partition cleanly):
            # finalize each state independently — the merge would re-sort
            # every group only to rediscover that nothing overlaps. One
            # batched bounds fetch decides; overlap falls through to the
            # general merge, so this is an optimization, never a
            # correctness assumption.
            from ballista_tpu.ops.fetch import fetch_arrays

            # the partial attaches host-resolved bounds (settled chunks)
            # or pre-copied device bounds (short inputs); only states
            # carrying neither — e.g. arriving through a shuffle — need
            # fresh device reductions. ONE batched fetch covers whatever
            # is unresolved.
            import numpy as np

            bounds: list = [
                getattr(st, "host_bounds", None) for st in states
            ]
            raw, missing = [], []
            for i, (st, hb) in enumerate(zip(states, bounds)):
                if hb is None:
                    dev = getattr(st, "dev_bounds", None)
                    if dev is not None:
                        # host copy already in flight since the partial
                        # queued it — resolving here costs no round trip
                        with obs_trace.phase(
                            "task.d2h", site="agg.bounds_ready"
                        ):
                            bounds[i] = tuple(
                                int(np.asarray(v)) for v in dev
                            )
                    else:
                        missing.append(i)
                        raw.extend(_state_bounds_dev(st))
            if raw:
                vals = [
                    int(v) for v in fetch_arrays(raw, site="agg.bounds")
                ]
                for j, i in enumerate(missing):
                    bounds[i] = tuple(vals[4 * j : 4 * j + 4])
            live = sorted(
                (b for b in zip(bounds, states) if b[0][2] > 0),
                key=lambda p: p[0][0],
            )
            if not live:
                # every state is empty (short inputs now defer emptiness
                # detection here): nothing to finalize
                return
            # exactly-touching ranges (a group split across two upstream
            # partitions) are trimmed here the same way the partial trims
            # its batch boundaries; only a real overlap — or any state
            # carrying a NULL-key group (stored as key 0 + null mask,
            # aliasing a real key-0 group) — forces the merge
            if not any(b[0][3] for b in live) and all(
                a[0][1] <= b[0][0] for a, b in zip(live, live[1:])
            ):
                merge_ops_t = tuple(merge_ops)
                with self.metrics.time("merge_time"):
                    out_states = []
                    for (lo, hi, n, _hn), st in live:
                        if out_states and out_states[-1][0][1] == lo:
                            pm, st = _merge_boundary(
                                out_states[-1][1], st, merge_ops_t, lo
                            )
                            out_states[-1] = (out_states[-1][0], pm)
                            self.metrics.add("boundary_trims")
                            if n == 1:
                                continue
                        out_states.append(((lo, hi, n), st))
                    self.metrics.add("final_disjoint_skip")
                    # group keys are globally unique across the disjoint
                    # states, so ONE concat + ONE finalize replaces a
                    # per-state finalize (whose varying sliced shapes
                    # would each trace their own program) — and the
                    # downstream pipeline sees a single batch
                    merged = (
                        out_states[0][1]
                        if len(out_states) == 1
                        else concat_batches([st for _, st in out_states])
                    )
                    yield self._finalize(merged, n_groups)
                return
            self.metrics.add("final_disjoint_miss")
        site = self.display()
        states = self._slice_states(states, ctx, site, partition)
        merged = concat_batches(states)
        with self.metrics.time("merge_time"):
            state = self._run_group_agg(
                merged, merge_ops, n_groups, cap, from_state=True, ctx=ctx,
                site=site,
            )
        yield self._finalize(state, n_groups)

    # Bucket fan-out of the spill files; K passes (a power of two dividing
    # this, chosen once the true state total is known) group consecutive
    # buckets — (h % 64) % K == h % K for K | 64, so the routing written
    # before K was known stays aligned at any K.
    _GRACE_BUCKETS = 64

    def _collect_states_grace(
        self, partition: int, ctx: TaskContext, budget: int, n_groups: int
    ) -> tuple:
        """Collect this partition's partial states under the HBM budget.
        Returns (states, None) when they all fit resident, else
        (None, (spill set, total bytes)) with every state hash-spilled by
        group key to host bucket files — the drain-then-spill switch fires
        the moment the running total crosses the budget, so the full set
        is never device-resident. A LONE over-budget state never spills:
        it was already materialized by the child, and the single-state
        finalize shortcuts need it resident anyway."""
        from ballista_tpu.exec.spill import device_nbytes, spill_batch_by_keys

        key_idxs = tuple(range(n_groups))
        states: list[DeviceBatch] = []
        total = 0
        sset = None
        spilled = 0
        for st in self.input.execute(partition, ctx):
            total += device_nbytes(st)
            if sset is None and states and total > budget:
                sset = ctx.spill_manager().new_set(
                    f"agg-{id(self):x}-{partition}", self._GRACE_BUCKETS
                )
                with self.metrics.time("spill_time"):
                    for prev in states:
                        spilled += spill_batch_by_keys(sset, prev, key_idxs)
                states.clear()
            if sset is None:
                states.append(st)
            else:
                with self.metrics.time("spill_time"):
                    spilled += spill_batch_by_keys(sset, st, key_idxs)
        if sset is None:
            return states, None
        sset.finish_writes()
        self.metrics.add("spill_bytes", spilled)
        return None, (sset, total)

    def _grace_merge(
        self,
        grace: tuple,
        ctx: TaskContext,
        cap: int,
        n_groups: int,
        merge_ops: list,
        budget_bytes: int,
    ) -> Iterator[DeviceBatch]:
        """Out-of-core final merge (grace hash): the partial states were
        hash-spilled by group key to host Arrow IPC buckets (the shuffle
        partitioner's routing rule, so strings route by value and NULL
        keys share a bucket — _collect_states_grace); re-load and merge
        one bucket range at a time through the ordinary merge kernel.
        Each range's merged state finalizes independently — group keys
        are unique ACROSS buckets by the hash split, so the concatenated
        outputs are exactly the in-memory result."""
        from ballista_tpu.columnar.arrow_interop import table_from_arrow
        from ballista_tpu.exec.spill import choose_passes

        sset, total_bytes = grace
        k = choose_passes(total_bytes, budget_bytes, self._GRACE_BUCKETS)
        self.metrics.add("spill_passes", k)
        group = self._GRACE_BUCKETS // k
        batch_rows = ctx.config.tpu_batch_rows()
        site = self.display() + "|grace"
        for pass_i in range(k):
            tabs = [
                t
                for b in range(pass_i * group, (pass_i + 1) * group)
                if (t := sset.read(b)) is not None and t.num_rows
            ]
            if not tabs:
                continue
            # narrowing OFF: every bucket must share one physical layout
            # (a per-bucket int32/int64 decision would recompile the merge
            # program per bucket)
            bucket: list[DeviceBatch] = []
            for t in tabs:
                bucket.extend(table_from_arrow(t, batch_rows, frozenset()))
            merged = concat_batches(bucket) if len(bucket) > 1 else bucket[0]
            with self.metrics.time("merge_time"):
                state = self._run_group_agg(
                    merged, merge_ops, n_groups, cap, from_state=True,
                    ctx=ctx, site=site,
                )
            yield self._finalize(state, n_groups)
        sset.close()

    def _slice_states(
        self,
        states: list[DeviceBatch],
        ctx: TaskContext | None,
        site: str,
        partition: int,
    ) -> list[DeviceBatch]:
        """Slice front-compacted partial states down to a learned capacity
        before a merge fold. A partial state's live groups occupy a prefix
        (valid = iota < n_groups), so re-bucketing is a free device slice —
        no compaction pass — and the merge's sort/segment work then scales
        with actual groups, not with the padded state capacity (a q3-shaped
        fold drops from 3x2M to 3x1M rows). The capacity is learned via the
        plan cache and validated with a deferred flag, like exec/shrink."""
        if ctx is None or ctx.plan_cache is None:
            return states
        import jax.numpy as jnp

        from ballista_tpu.columnar.batch import round_capacity

        cache = ctx.plan_cache
        # job-scoped like join _strategy_key: one executor serves many jobs
        # whose plans can collide structurally; a shared entry would make
        # alternating jobs re-poison each other's learned capacities and
        # pay a SpeculationMiss re-run per query
        job = getattr(ctx, "job_id", "")
        key = ("agg_state_cap", job, site, partition)
        # Slicing assumes live groups occupy a PREFIX. True for partial
        # outputs (valid = iota < n_groups) but NOT for states that came
        # through an in-place-masking hash repartition, whose live rows are
        # scattered over the producer's whole prefix — so prefix-validity
        # is learned as its own flag (AND-ed across states), and every
        # slice is additionally validated by "no live row beyond the
        # slice", which catches layout drift exactly.
        pkey = ("agg_state_prefix", job, site, partition)
        learned = cache.get(key)
        prefix_ok = cache.get(pkey)
        if learned is None or prefix_ok is None:
            for st in states:
                n = st.count_valid()
                ctx.defer_learn(key, n)
                iota = jnp.arange(st.capacity, dtype=jnp.int32)
                ctx.defer_learn(pkey, jnp.all(st.valid == (iota < n)))
            return states
        if prefix_ok is not True:
            return states
        slice_cap = round_capacity(max(16, int(learned * 5 // 4)))
        out = []
        for st in states:
            if slice_cap >= st.capacity:
                out.append(st)
                continue
            ctx.defer_speculation(
                jnp.any(st.valid[slice_cap:]),
                "learned aggregate-state capacity went stale (live rows "
                "beyond the slice)",
                [key, pkey],
            )
            out.append(st.head(slice_cap))
        return out

    def _finalize(self, state: DeviceBatch, n_groups: int) -> DeviceBatch:
        return finalize_state(state, self.spec, self._schema)

