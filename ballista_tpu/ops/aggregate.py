"""Grouped and scalar aggregation kernels.

Replaces DataFusion's HashAggregateExec (the reference serializes it at
ballista/rust/core/src/serde/physical_plan/mod.rs HashAggregateExecNode arm;
proto ballista.proto:275-623). TPU-native design: **sort-based grouping** —
group keys sort via cached stable argsort passes (ops/perm.py; multi-operand
``lax.sort`` is avoided for its pathological compile times), then one jitted
finisher program does segment-boundary detection and segment scatter-reduces.
No hash table, no data-dependent control flow, fully static shapes with a
configurable group-capacity bound (``ballista.tpu.agg_capacity``); overflow
is detected on device and raised host-side.

Two-phase distributed aggregation mirrors the reference's partial/final
split: partials produced per batch/partition are merged by re-running
group_aggregate with the merge ops (COUNT merges via SUM, etc.).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from enum import Enum

import jax
import jax.numpy as jnp

from ballista_tpu.errors import ExecutionError
from ballista_tpu.ops.perm import (
    group_by_dtype,
    multi_key_perm,
    take_many_split,
)


class AggOp(Enum):
    SUM = "sum"
    COUNT = "count"  # COUNT(expr): counts non-null; COUNT(*) passes no nulls
    MIN = "min"
    MAX = "max"

    @property
    def merge_op(self) -> "AggOp":
        """Op used to merge partial states (COUNT merges by SUM)."""
        return AggOp.SUM if self == AggOp.COUNT else self


def _sum_dtype(dtype):
    """SQL SUM widens to the largest type of its class (int64 / float64);
    BOOL sums count TRUEs."""
    if dtype == jnp.bool_ or jnp.issubdtype(dtype, jnp.integer):
        return jnp.int64
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.float64
    return dtype


def _max_ident(dtype) -> jnp.ndarray:
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.array(True)
    return jnp.array(jnp.iinfo(dtype).max, dtype=dtype)


def _min_ident(dtype) -> jnp.ndarray:
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf, dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.array(False)
    return jnp.array(jnp.iinfo(dtype).min, dtype=dtype)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GroupAggResult:
    """Device-side aggregation output, all arrays of length ``capacity``.
    Registered as a pytree so aggregate passes can run under jit."""

    keys: list[jnp.ndarray]
    key_nulls: list[jnp.ndarray | None]
    values: list[jnp.ndarray]
    value_nulls: list[jnp.ndarray | None]
    valid: jnp.ndarray  # bool[capacity] — which output slots are groups
    n_groups: jnp.ndarray  # int32 scalar
    overflow: jnp.ndarray  # bool scalar: more groups than capacity
    # device bool scalars for the clustered-input speculation protocol
    # (exec/aggregate.py): ``input_was_sorted`` reports whether the rows
    # came in already grouped-adjacent (learned on sort-path runs, free off
    # the stable sort's permutation); ``sorted_ok`` validates a
    # presorted-path run (None on sort-path runs).
    input_was_sorted: jnp.ndarray | None = None
    sorted_ok: jnp.ndarray | None = None

    def tree_flatten(self):
        return (
            (self.keys, self.key_nulls, self.values, self.value_nulls,
             self.valid, self.n_groups, self.overflow,
             self.input_was_sorted, self.sorted_ok),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

    def check_overflow(self) -> None:
        """Host-side check — call OUTSIDE jit (forces a device sync)."""
        if bool(self.overflow):
            from ballista_tpu.errors import CapacityError

            raise CapacityError(
                f"aggregate exceeded group capacity "
                f"({int(self.n_groups)} groups); raise ballista.tpu.agg_capacity",
                required=int(self.n_groups),
            )


@functools.lru_cache(maxsize=None)
def _zeroed_program(kdtype: str, cap: int):
    def agg_zero_null_keys(nm, kc):
        return jnp.where(nm, jnp.zeros_like(kc), kc)

    return jax.jit(agg_zero_null_keys)


@functools.lru_cache(maxsize=None)
def _not_program(cap: int):
    def agg_invalid(v):
        return ~v

    return jax.jit(agg_invalid)


def _stacked_scatter_set(rid, capacity: int, cols: list) -> list:
    """Scatter-set columns into ``capacity`` slots, one scatter per distinct
    dtype (columns stacked on a trailing axis). Rows with ``rid ==
    capacity`` are dropped."""
    out: list = [None] * len(cols)
    for dt, idxs in group_by_dtype(cols).items():
        if len(idxs) == 1:
            i = idxs[0]
            out[i] = jnp.zeros(capacity, dtype=cols[i].dtype).at[rid].set(
                cols[i], mode="drop"
            )
            continue
        stacked = jnp.stack([cols[i] for i in idxs], axis=1)
        res = jnp.zeros((capacity, len(idxs)), dtype=stacked.dtype).at[
            rid
        ].set(stacked, mode="drop")
        for j, i in enumerate(idxs):
            out[i] = res[:, j]
    return out


# One-hot-matmul reduction limits: slot count must stay MXU-friendly and
# the materialized (P, chunk) f64 one-hot must fit comfortably in HBM —
# the TPU x64 rewrite emulates f64 as f32 pairs, so the dot's temporaries
# run ~3x the nominal operand size (a 2GB budget OOM'd 16GB HBM on an
# 8M-row q5 aggregate next to the join intermediates).
_MATMUL_MAX_SLOTS = 2048
_MATMUL_MAX_ONEHOT_BYTES = 512 << 20

# The pallas kernel (ops/pallas_agg.py) replaces the XLA one-hot matmul on
# big batches only: its f32 in-block accumulation carries ~2e-8 relative
# error (ops/pallas_agg.py), acceptable for SQL sums at scale (no defined
# summation order) but
# above what small-data unit tests assert (rtol=1e-9). Below the bar the
# XLA f64 path is cheap anyway.
_PALLAS_MIN_ROWS = 1 << 20

# Past _MATMUL_MAX_SLOTS a dense pass reduces its counts and integer sums
# through a factorized one-hot (``_factored_sums``), never by scatter. It
# did not lose to the int64 scatter-add at any size measured on a v5e (PR
# 37, PERF.md §6: ``_dense_agg`` jitted, one int64 SUM; scatter against
# factorized, ms): 10,201 slots at 2,097,152 rows 401.8 / 13.1, at 16,384
# rows 4.27 / 1.07, at 1,024 1.23 / 1.00, at 256 0.97 / 0.98; 50,001 slots
# (three sums, one float64 still by scatter) at 262,144 rows 73.6 / 39.7,
# at 1,024 1.72 / 1.47.
# Rows of one factorized chunk: its partials stay exact in f32 (below 2^24
# at 255 a row), and its (rows, K x P2) right operand near this many bytes.
_FACTORED_MAX_CHUNK = 1 << 16
_FACTORED_CHUNK_BYTES = 16 << 20


def dense_factored(capacity: int) -> bool:
    """Whether a dense pass into ``capacity`` slots reduces its counts and
    integer sums through the factorized one-hot (``exec/aggregate.py``
    counts such passes)."""
    return capacity > _MATMUL_MAX_SLOTS


def _factored_layout(capacity: int, n: int, K: int) -> tuple[int, int, int]:
    """``(P1, P2, chunk)`` of ``_factored_sums``: slots as a P1 x P2 grid
    with P2 = ceil(sqrt(capacity)), and the rows of one chunk for ``n``
    rows and K limb columns, a power of two where it is below ``n`` (a
    power of two divides a batch's capacity: no padded copy)."""
    P2 = math.isqrt(capacity - 1) + 1
    P1 = -(-capacity // P2)
    chunk = max(512, _FACTORED_CHUNK_BYTES // (2 * P2 * K))
    chunk = min(n, _FACTORED_MAX_CHUNK, 1 << (chunk.bit_length() - 1))
    return P1, P2, chunk


def _limb_shifts(src_dtype) -> list[int]:
    """The shifts of ``_byte_limbs``: one limb a byte of the source width
    (an int64 for any other integer), one for a bool."""
    src = jnp.dtype(src_dtype)
    if src == jnp.bool_:
        return [0]
    width = src.itemsize if jnp.issubdtype(src, jnp.signedinteger) else 8
    return [8 * b for b in range(width)]


def _byte_limbs(x, src_dtype) -> list:
    """``x`` (an int64 contribution of a ``src_dtype`` column) as limbs with
    ``x == sum(limb << shift)`` over ``_limb_shifts`` exactly: unsigned
    bytes below a signed top byte, so every limb lies in [-128, 255], exact
    in bfloat16."""
    shifts = _limb_shifts(src_dtype)
    return [(x >> s) & 255 for s in shifts[:-1]] + [x >> shifts[-1]]


def _factored_sums(rid, capacity: int, flags: list, ints: list):
    """Slot sums through the MXU, exact: the counts of the bool columns
    ``flags`` ((capacity, len(flags)) int64) and the sums of the int64
    columns of ``ints`` ((contribution, source dtype) pairs; (capacity,
    len(ints)) int64, wrapping as a scatter-add does).

    With ``hi = rid // P2`` and ``lo = rid % P2``, ``S[hi, lo] = sum_n
    onehot_hi[n, hi] * (x_n * onehot_lo[n, lo])`` is one (P1, n) x (n, P2 *
    K) contraction per row chunk over the K limb columns (a flag is one, an
    int its ``_byte_limbs``): 0/1 one-hots and limbs in [-128, 255] are
    exact in bfloat16 and a chunk's f32 partials exact under 2^24; chunks
    add up in int64 and the limbs recombine by their shifts. Rows with
    ``rid == capacity`` meet no slot."""
    n = rid.shape[0]
    shifts = [[0] for _ in flags] + [_limb_shifts(src) for _, src in ints]
    K = sum(len(sh) for sh in shifts)
    P1, P2, chunk = _factored_layout(capacity, n, K)
    nb = -(-n // chunk)
    pad = nb * chunk - n
    hi = jnp.where(rid < capacity, rid // P2, P1).astype(jnp.int32)
    lo = (rid % P2).astype(jnp.int32)
    xs = [hi, lo, jnp.stack(flags, axis=1)]
    if ints:
        xs.append(jnp.stack([x for x, _ in ints], axis=1))
    # padded rows hold zeros in every limb: they add nothing where they land
    xs = [
        jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)).reshape(
            (nb, chunk) + x.shape[1:]
        )
        for x in xs
    ]
    iota1 = jax.lax.broadcasted_iota(jnp.int32, (chunk, P1), 1)
    iota2 = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1, P2), 2)

    def body(acc, xs_c):
        hi_c, lo_c, flags_c, *ints_c = xs_c
        limbs = [flags_c[:, j] for j in range(len(flags))]
        for j, (_, src) in enumerate(ints):
            limbs += _byte_limbs(ints_c[0][:, j], src)
        mat = jnp.stack([l.astype(jnp.bfloat16) for l in limbs], axis=1)
        oh_hi = (hi_c[:, None] == iota1).astype(jnp.bfloat16)
        rhs = jnp.where(
            lo_c[:, None, None] == iota2, mat[:, :, None],
            jnp.zeros((), jnp.bfloat16),
        ).reshape(chunk, K * P2)
        part = jax.lax.dot_general(
            oh_hi, rhs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc + part.astype(jnp.int64), None

    acc, _ = jax.lax.scan(body, jnp.zeros((P1, K * P2), jnp.int64), xs)
    sums = acc.reshape(P1, K, P2).transpose(0, 2, 1).reshape(P1 * P2, K)
    sums = sums[:capacity]
    out, k = [], 0
    for sh in shifts:
        # int64 wraps as the scatter-add this replaces does
        out.append(functools.reduce(
            jnp.add, [sums[:, k + j] << s for j, s in enumerate(sh)]
        ))
        k += len(sh)
    counts = jnp.stack(out[: len(flags)], axis=1)
    int_sums = jnp.stack(out[len(flags):], axis=1) if ints else None
    return counts, int_sums


def _stacked_reduce(
    rid, capacity: int, vals: list, lives: list, ops: tuple
) -> tuple[list, list, jnp.ndarray]:
    """All value reductions with ONE reduction per (kind, dtype), and the
    rows each slot holds.

    ``rid`` is the common slot index (``capacity`` = dropped); per-column
    NULL masks are folded into the *contribution* instead of the index
    (SUM adds 0, MIN/MAX add their identity, COUNT adds 0) so every column
    shares the same reduction. A live mask of ``None`` is every row with a
    slot. The non-null count matrix doubles as COUNT output and the SQL
    all-NULL flags; its first column counts the rows with a slot, whose
    ``> 0`` is the slot's occupancy (returned third).

    Small slot counts (the dense dictionary-key path — TPC-H q1 has 12)
    route f64 sums and the count matrix over the MXU instead: a one-hot
    (P, n) f64 matmul is ~2x the speed of even the stacked scatter on a
    v5e (measured 45ms vs 100ms net for 1M rows x 8 columns). Counts are
    exact through f64 (< 2^53); int64 sums keep the scatter (their sums
    may exceed f64's exact-integer range). Past ``_MATMUL_MAX_SLOTS`` the
    counts and integer sums take the factorized one-hot (int sums as byte
    limbs, recombined in int64: bit-identical to the scatter-add,
    wraparound included); f64 sums and MIN/MAX keep their scatter there."""
    m = len(vals)
    out_vals: list = [None] * m
    out_val_nulls: list = [None] * m
    n = rid.shape[0]
    use_mm = capacity <= _MATMUL_MAX_SLOTS
    use_factored = dense_factored(capacity)
    use_pallas = False
    if use_mm and n >= _PALLAS_MIN_ROWS:
        from ballista_tpu.ops import pallas_agg

        use_pallas = pallas_agg.available()
    # count columns: the rows with a slot, then each live mask of its own
    cnt_cols = [rid < capacity]
    live_idx = []
    for l in lives:
        live_idx.append(0 if l is None else len(cnt_cols))
        if l is not None:
            cnt_cols.append(l)

    # chunk so the materialized (capacity, chunk) f64 one-hot stays within
    # budget; rows beyond n (chunk padding) and dropped rows (rid ==
    # capacity) match no iota slot, so they contribute nothing
    chunk = n
    if use_mm and capacity * n * 8 > _MATMUL_MAX_ONEHOT_BYTES:
        chunk = max(1 << 15, _MATMUL_MAX_ONEHOT_BYTES // (capacity * 8))
        chunk = min(chunk, n)

    def _mm(stacked_f64):
        if chunk == n:
            oh = (
                jax.lax.broadcasted_iota(jnp.int32, (capacity, n), 0)
                == rid[None, :]
            ).astype(jnp.float64)
            return jax.lax.dot_general(
                oh, stacked_f64, (((1,), (0,)), ((), ()))
            )
        nb = -(-n // chunk)
        pad = nb * chunk - n
        rid_p = jnp.pad(rid, (0, pad), constant_values=capacity)
        st_p = jnp.pad(stacked_f64, ((0, pad), (0, 0)))
        iota = jax.lax.broadcasted_iota(jnp.int32, (capacity, chunk), 0)

        def body(acc, xs):
            rid_c, st_c = xs
            oh = (iota == rid_c[None, :]).astype(jnp.float64)
            return acc + jax.lax.dot_general(
                oh, st_c, (((1,), (0,)), ((), ()))
            ), None

        acc, _ = jax.lax.scan(
            body,
            jnp.zeros((capacity, stacked_f64.shape[1])),
            (
                rid_p.reshape(nb, chunk),
                st_p.reshape(nb, chunk, stacked_f64.shape[1]),
            ),
        )
        return acc

    add_groups: dict[str, list] = {}
    min_groups: dict[str, list] = {}
    max_groups: dict[str, list] = {}
    int_sums: list = []  # (column, contribution, source dtype): factorized
    nc = len(cnt_cols)
    if use_pallas:
        # ONE kernel call covers the count matrix and every f64 sum: live
        # flags ride as f32 0/1 rows (counts stay exact — see module note
        # in pallas_agg), f64 contributions as exact (hi, lo) f32 pairs.
        from ballista_tpu.ops import pallas_agg

        rows = [c.astype(jnp.float32) for c in cnt_cols]
        f64_cols: list[int] = []
        contribs_f64: dict[int, jnp.ndarray] = {}
        counts = None  # filled after the single kernel call below
    elif use_mm:
        cnt_mat = jnp.stack([c.astype(jnp.float64) for c in cnt_cols], axis=1)
        counts = _mm(cnt_mat).astype(jnp.int64)
    for i, (vc, op) in enumerate(zip(vals, ops)):
        live = cnt_cols[live_idx[i]]
        if op == AggOp.COUNT:
            continue
        if op == AggOp.SUM:
            acc_t = jnp.dtype(_sum_dtype(vc.dtype))
            contrib = jnp.where(live, vc, jnp.zeros_like(vc)).astype(acc_t)
            if use_pallas and acc_t == jnp.float64:
                hi, lo = pallas_agg.split_hi_lo(contrib)
                rows.append(hi)
                rows.append(lo)
                f64_cols.append(i)
                contribs_f64[i] = contrib
                continue
            if use_factored and acc_t == jnp.int64:
                int_sums.append((i, contrib, vc.dtype))
                continue
            add_groups.setdefault(str(acc_t), []).append((i, contrib))
        elif op == AggOp.MIN:
            masked = jnp.where(live, vc, _max_ident(vc.dtype))
            min_groups.setdefault(str(vc.dtype), []).append((i, masked))
        elif op == AggOp.MAX:
            masked = jnp.where(live, vc, _min_ident(vc.dtype))
            max_groups.setdefault(str(vc.dtype), []).append((i, masked))
        else:  # pragma: no cover
            raise ExecutionError(f"unknown agg op {op}")
    if use_factored:
        counts, int_out = _factored_sums(
            rid, capacity, cnt_cols, [(x, src) for _, x, src in int_sums]
        )
        for j, (i, _, _) in enumerate(int_sums):
            out_vals[i] = int_out[:, j]
    if use_pallas:
        sums = pallas_agg.onehot_sums(rid, rows, capacity)
        counts = jnp.round(sums[:, :nc]).astype(jnp.int64)
        if f64_cols:
            # The kernel accumulates in f32: a value beyond ~1e30 (or a
            # NaN/Inf input) would overflow hi/lo or poison every slot of
            # its column. Guard on the contributions' magnitude and fall
            # back to the XLA f64 one-hot path for the f64 sums — rare
            # enough that the cond's cold branch never runs in practice.
            f64_stack = jnp.stack(
                [contribs_f64[i] for i in f64_cols], axis=1
            )
            in_range = jnp.max(jnp.abs(jnp.where(
                jnp.isfinite(f64_stack), f64_stack, jnp.inf
            ))) < 1e30
            pallas_sums = jnp.stack(
                [
                    sums[:, nc + 2 * j] + sums[:, nc + 2 * j + 1]
                    for j in range(len(f64_cols))
                ],
                axis=1,
            )
            safe = jax.lax.cond(
                in_range,
                lambda: pallas_sums,
                lambda: _mm(f64_stack),
            )
            for j, i in enumerate(f64_cols):
                out_vals[i] = safe[:, j]
    for i, op in enumerate(ops):
        nonnull = counts[:, live_idx[i]]
        if op == AggOp.COUNT:
            out_vals[i] = nonnull
        else:
            out_val_nulls[i] = nonnull == 0  # agg over no values: NULL
    for groups, kind in (
        (add_groups, "add"), (min_groups, "min"), (max_groups, "max")
    ):
        for dt, entries in groups.items():
            stacked = jnp.stack([c for _, c in entries], axis=1)
            if kind == "add" and use_mm and dt == "float64":
                res = _mm(stacked)
            elif kind == "add":
                init = jnp.zeros((capacity, len(entries)), stacked.dtype)
                res = init.at[rid].add(stacked, mode="drop")
            elif kind == "min":
                init = jnp.full(
                    (capacity, len(entries)), _max_ident(stacked.dtype)
                )
                res = init.at[rid].min(stacked, mode="drop")
            else:
                init = jnp.full(
                    (capacity, len(entries)), _min_ident(stacked.dtype)
                )
                res = init.at[rid].max(stacked, mode="drop")
            for j, (i, _) in enumerate(entries):
                out_vals[i] = res[:, j]
    return out_vals, out_val_nulls, counts[:, 0] > 0


# -- segment-reduction finisher -----------------------------------------------
#
# After the group sort (or on input that is already clustered on the group
# keys), rows of one group are ADJACENT, so every reduction can avoid the
# random scatter a hash-grouping design needs. Measured on the v5e (8.4M
# rows -> 2M groups): a stacked scatter-add runs 0.7-1.1s/column (per-row
# serial cost), while cumsum + segment-boundary gathers compute the same
# sums in ~0.25s for TWO columns:
#
#   sum[g]   = cumsum(contrib)[end_g] - cumsum(contrib)[start_g] + c[start_g]
#   count[g] = same over the live flag
#   keys[g]  = key cols gathered at start_g (first row of the segment)
#
# start/end positions come from two scatters of iota (min/max with
# indices_are_sorted — these run near-sequentially, unlike value scatters).
# MIN/MAX keep a scatter (no prefix trick) but ride sorted indices.
#
# The whole finisher is split into TWO jitted programs: fusing the cumsums,
# boundary scatters, and boundary gathers into one program SIGSEGVs this
# toolchain's TPU compiler (reproducible on combined cumsum + 2 scatters +
# gathers); the split also costs nothing (dispatches are async).
#
# f64 SUM NOTE: segment sums via prefix-difference round like a different
# summation order and carry error proportional to the GLOBAL prefix
# magnitude (~1e-6 absolute at 8M rows of 1e4-scale money values). SQL
# does not define a summation order; int64/count sums stay exact (integer
# cumsum).


# Float prefix sums avoid `jnp.cumsum`: under the TPU x64 rewrite a single
# f64 cumsum op takes ~110-150s to COMPILE (at any length — even 4096),
# while an equivalent blocked triangular-matmul prefix compiles in seconds
# and runs on the MXU at the same speed (measured 0.13s vs 0.10s at 8.4M,
# rel err 1.4e-13 at Precision.HIGHEST). Integer cumsums compile fine and
# stay exact, so they keep the stock op. CPU keeps the stock op for floats
# too (native f64 cumsum is exact, fast, and quick to compile — and the
# CPU bench baseline must not be sandbagged by a TPU workaround).
_PREFIX_BLOCK = 512


def _block_prefix(x3: jnp.ndarray) -> jnp.ndarray:
    """(nb, block, M) floats -> the inclusive prefix along axis 1, as ONE
    2-D matmul of the rows (nb * M, block) with the upper-triangular ones
    (no cumsum ops anywhere). As an ``einsum("kj,bkm->bjm")`` the same
    product costs the TPU compiler 40 s where M is 1 and 2M rows deep, 3-10 s
    otherwise; as rows times a square it costs 1-7 s at every M (PR 29,
    compiled here for a described v5e)."""
    nb, block, m = x3.shape
    u = (
        jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
        <= jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    ).astype(x3.dtype)
    rows = x3.transpose(0, 2, 1).reshape(nb * m, block)
    out = jnp.dot(rows, u, precision=jax.lax.Precision.HIGHEST)
    return out.reshape(nb, m, block).transpose(0, 2, 1)


def _mm_prefix(x2: jnp.ndarray, block: int) -> jnp.ndarray:
    """(n, M) -> inclusive prefix along axis 0 via recursive blocked
    upper-triangular matmuls."""
    n, m = x2.shape
    nb = -(-n // block)
    x3 = jnp.pad(x2, ((0, nb * block - n), (0, 0))).reshape(nb, block, m)
    inner = _block_prefix(x3)
    if nb == 1:
        return inner.reshape(block, m)[:n]
    bsums = x3.sum(axis=1)
    offs = _mm_prefix(bsums, block) - bsums
    return (inner + offs[:, None, :]).reshape(nb * block, m)[:n]


def _blocked_prefix(x2: jnp.ndarray, block: int) -> jnp.ndarray:
    """(n, M) integers -> inclusive prefix along axis 0 as cumsums of
    ``block`` rows and a recursive prefix over the blocks' sums: integer
    addition is associative, so the result is the stock op's, bit for
    bit."""
    n, m = x2.shape
    if n <= block:
        return jnp.cumsum(x2, axis=0)
    nb = -(-n // block)
    xp = jnp.pad(x2, ((0, nb * block - n), (0, 0)))
    inner = jnp.cumsum(xp.reshape(nb, block, m), axis=1)
    bsums = inner[:, -1, :]
    offs = _blocked_prefix(bsums, block) - bsums
    return (inner + offs[:, None, :]).reshape(nb * block, m)[:n]


# Integer cumsums are exact but, on the TPU, as slow to compile over a long
# axis as the float ones: the stock op over (524288, 2) int64 costs this
# compiler 213-314 s and over (2M, 2) 55-69 s, int32[2M] 14-21 s (PR 29,
# compiled here for a described v5e), and every (rows, columns, dtype) a
# query reaches is a program of its own. The same prefix as cumsums along a
# short axis compiles in 2-5 s at any length.
_INT_PREFIX_BLOCK = 2048


def _prefix_sum_2d(x2: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix along axis 0, routed per dtype/backend (see the
    compile-time notes above)."""
    if jax.default_backend() == "cpu":
        return jnp.cumsum(x2, axis=0)
    if jnp.issubdtype(x2.dtype, jnp.floating):
        return _mm_prefix(x2, _PREFIX_BLOCK)
    return _blocked_prefix(x2, _INT_PREFIX_BLOCK)


def running_count(flags: jnp.ndarray, dtype=jnp.int64) -> jnp.ndarray:
    """Inclusive count of the set ``flags`` (n,) up to each row: the integer
    prefix of the window and percentile operators, through
    ``_prefix_sum_2d``."""
    return _prefix_sum_2d(flags.astype(dtype)[:, None])[:, 0]


def _float_prefix_parts(x2: jnp.ndarray, block: int = _PREFIX_BLOCK):
    """(n, M) floats -> the inclusive prefix along axis 0 in two levels,
    for ``_float_seg_totals``: ``inner`` (nb * block, M), the prefix within
    each block of ``block`` rows; ``bsums`` (nb + 1, M), the blocks' sums
    and a zero; ``offs`` (nb + 1, M), the sums of the blocks before each,
    so ``offs[nb]`` is the grand total. ``offs[b] + inner[i]`` is the
    global prefix, and is never formed: see ``_float_seg_totals``."""
    n, m = x2.shape
    nb = -(-n // block)
    x3 = jnp.pad(x2, ((0, nb * block - n), (0, 0))).reshape(nb, block, m)
    if jax.default_backend() == "cpu":
        inner = jnp.cumsum(x3, axis=1)
        bsums = inner[:, -1, :]
        incl = jnp.cumsum(bsums, axis=0)
    else:
        inner = _block_prefix(x3)
        bsums = x3.sum(axis=1)
        incl = _mm_prefix(bsums, block)
    zero = jnp.zeros((1, m), x2.dtype)
    return (
        inner.reshape(nb * block, m),
        jnp.concatenate([bsums, zero]),
        jnp.concatenate([zero, incl]),
    )


def _float_seg_totals(parts, ps, out_valid, block: int = _PREFIX_BLOCK):
    """Per-segment totals from ``_float_prefix_parts`` and the segments'
    start rows ``ps`` (increasing over the live slots ``out_valid``).

    A total is the prefix before the next segment's start less the prefix
    before this one's. Taken of the global prefix, that difference loses
    what the prefix's magnitude costs: 2M rows of values near 50 run to
    1e8, and the TPU's emulated float64 carries some 1e-14 of it, 5e-10 of
    a 20-row group's sum (measured, PR 29), where float32 gives 7e-8. In
    two levels the large terms cancel exactly or never arise. With
    ``b0, b1`` the blocks of the two starts and ``w0, w1`` the prefixes
    within them,

        total = (w1 - w0) + [b1 > b0] * bsums[b0]
                + (offs[b1] - offs[min(b0 + 1, b1)])

    and the last term is x - x = 0 unless the segment holds a whole block
    between its ends, so a segment within two blocks never sees a number
    larger than a block's sum, and a longer one sees the blocks' prefix
    only against a sum of its own size."""
    inner, bsums, offs = parts
    end = inner.shape[0]  # one past the last row: block nb, nothing within
    st = jnp.where(out_valid, jnp.clip(ps, 0, end), end)
    b0 = st // block
    within = (st % block) > 0
    w0 = jnp.where(within[:, None], inner[jnp.clip(st - 1, 0, end - 1)], 0)
    b1 = jnp.concatenate([b0[1:], jnp.full(1, end // block, b0.dtype)])
    w1 = jnp.concatenate([w0[1:], jnp.zeros_like(w0[:1])])
    whole = offs[b1] - offs[jnp.minimum(b0 + 1, b1)]
    first = jnp.where((b1 > b0)[:, None], bsums[b0], 0)
    return (w1 - w0) + first + whole


def _same_val(a, b):
    """SQL group equality: NaN==NaN is one group; -0.0 == +0.0."""
    same = a == b
    if jnp.issubdtype(a.dtype, jnp.floating):
        same = same | (jnp.isnan(a) & jnp.isnan(b))
    return same


def _gt_val(a, b):
    """Sort-order 'greater': NaN sorts after every number."""
    if jnp.issubdtype(a.dtype, jnp.floating):
        return (a > b) | (jnp.isnan(a) & ~jnp.isnan(b))
    return a > b


def _ffill_tuple(vals: tuple, flag):
    """Forward-fill ``vals`` from the last flagged row at-or-before each
    row (Hillis–Steele doubling in a fori_loop — one small loop body; an
    unrolled associative_scan takes minutes to compile here). Returns
    (filled values, filled flag)."""
    n = flag.shape[0]
    steps = max(1, (n - 1).bit_length())
    iota = jnp.arange(n, dtype=jnp.int32)

    def body(k, carry):
        vs, fl = carry
        off = jnp.left_shift(jnp.int32(1), k)
        pf = jnp.roll(fl, off) & (iota >= off)
        take_prev = ~fl & pf
        new_vs = tuple(
            jnp.where(take_prev, jnp.roll(v, off), v) for v in vs
        )
        return new_vs, fl | pf

    vs, fl = jax.lax.fori_loop(0, steps, body, (tuple(vals), flag))
    return vs, fl


def _seg_layouts(val_dtypes: tuple, null_sig: tuple, ops: tuple):
    """Static column layouts: which live-count cumsum serves each column
    (no-null columns share one), how SUM columns stack per accumulator
    dtype, and which columns reduce by scatter-min/max."""
    live_keys: list[int] = []
    live_index: dict[int, int] = {}
    for i, has_null in enumerate(null_sig):
        k = i if has_null else -1
        if k not in live_index:
            live_index[k] = len(live_keys)
            live_keys.append(k)
    sum_groups: dict[str, list[int]] = {}
    mm_idx: list[int] = []
    for i, (dt, op) in enumerate(zip(val_dtypes, ops)):
        if op == AggOp.SUM:
            acc = str(jnp.dtype(_sum_dtype(jnp.dtype(dt))))
            sum_groups.setdefault(acc, []).append(i)
        elif op in (AggOp.MIN, AggOp.MAX):
            mm_idx.append(i)
    sum_layout = tuple(
        (dt, tuple(idxs)) for dt, idxs in sum_groups.items()
    )
    return sum_layout, tuple(live_keys), tuple(mm_idx)


def _seg_part1(
    valid,
    key_cols: list,
    key_nulls: list,
    val_cols: list,
    val_nulls: list,
    perm,
    ops: tuple,
    capacity: int,
    clustered: bool,
    sum_layout: tuple,
    live_layout: tuple,
    mm_idx: tuple,
):
    """Program 1: segment ids + boundary positions + running sums.

    ``clustered=False``: inputs are the SORTED (gathered) operands — valid
    rows compacted to the front, groups adjacent; ``perm`` is the sort
    permutation, used only to report ``input_was_sorted`` (a strictly
    increasing live prefix of a STABLE sort's permutation means the input
    was already clustered — the learning signal for the presorted path).

    ``clustered=True``: inputs are in ORIGINAL order, speculated to be
    grouped-adjacent among live rows (invalid rows anywhere); boundaries
    compare against the previous LIVE row via a forward-fill, and
    ``sorted_ok`` reports whether the speculation actually held.
    """
    n = valid.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)

    # (null flag, zeroed value) per key: the group-identity tuple.
    zkeys, kflags = [], []
    for kc, kn in zip(key_cols, key_nulls):
        if kn is not None:
            zkeys.append(jnp.where(kn, jnp.zeros_like(kc), kc))
            kflags.append(kn)
        else:
            zkeys.append(kc)
            kflags.append(None)

    sorted_ok = None
    input_was_sorted = None
    if clustered:
        parts = tuple(zkeys) + tuple(f for f in kflags if f is not None)
        pv, pf = _ffill_tuple(parts, valid)
        prev_z = pv[: len(zkeys)]
        prev_f_it = iter(pv[len(zkeys):])
        prev_flags = [
            next(prev_f_it) if f is not None else None for f in kflags
        ]
        # shift to STRICTLY-previous live row
        prev_z = [
            jnp.concatenate([jnp.zeros(1, z.dtype), z[:-1]]) for z in prev_z
        ]
        prev_flags = [
            None
            if f is None
            else jnp.concatenate([jnp.zeros(1, bool), f[:-1]])
            for f in prev_flags
        ]
        prev_live = jnp.concatenate([jnp.zeros(1, bool), pf[:-1]])
        same = jnp.ones(n, dtype=bool)
        greater = jnp.zeros(n, dtype=bool)
        eq_chain = jnp.ones(n, dtype=bool)
        for z, pz, f, pflag in zip(zkeys, prev_z, kflags, prev_flags):
            if f is not None:
                # null flags sort nulls last (False < True): prev is
                # "greater" when prev is null and current is not
                pair_same = (f == pflag) & _same_val(z, pz)
                pair_gt = (pflag & ~f) | ((f == pflag) & _gt_val(pz, z))
            else:
                pair_same = _same_val(z, pz)
                pair_gt = _gt_val(pz, z)
            same = same & pair_same
            greater = greater | (eq_chain & pair_gt)
            eq_chain = eq_chain & pair_same
        changed = valid & (~prev_live | ~same)
        sorted_ok = ~jnp.any(valid & prev_live & greater)
        row_valid = valid
    else:
        changed = jnp.zeros(n, dtype=bool).at[0].set(True)
        for z, f in zip(zkeys, kflags):
            if f is not None:
                changed = changed | jnp.concatenate(
                    [jnp.ones(1, dtype=bool), f[1:] != f[:-1]]
                )
            changed = changed | jnp.concatenate(
                [jnp.ones(1, dtype=bool), ~_same_val(z[1:], z[:-1])]
            )
        row_valid = valid
        changed = changed & row_valid
        if perm is not None:
            n_live = jnp.sum(row_valid.astype(jnp.int32))
            input_was_sorted = jnp.all(
                (perm[1:] > perm[:-1]) | (iota[1:] >= n_live)
            )

    seg = _prefix_sum_2d(changed.astype(jnp.int32)[:, None])[:, 0] - 1
    n_groups = jnp.sum(changed.astype(jnp.int32))
    overflow = n_groups > capacity
    # dead rows (and overflow segments) scatter out of bounds -> dropped.
    # The sorted-indices hint is only legal when dead rows can't interrupt
    # the monotonic run: true post-sort (dead rows are all at the tail),
    # FALSE on the clustered path (dead rows anywhere -> their `capacity`
    # sentinel breaks monotonicity, and a wrong hint is UB on TPU).
    sid = jnp.where(row_valid, seg, capacity)
    hint = not clustered

    # Segment START positions only. End positions are never materialized:
    # dead rows contribute zero to every running sum, so the cumsum just
    # before one segment's start equals the cumsum at the previous
    # segment's end — part2 reconstructs per-segment totals from the
    # starts alone (one boundary gather instead of two, no scatter-max).
    ps = jnp.full(capacity, n, jnp.int32).at[sid].min(
        iota, mode="drop", indices_are_sorted=hint
    )

    lives = [
        row_valid if vn is None else (row_valid & ~vn) for vn in val_nulls
    ]
    # non-null running counts, one stacked (n, M) int32 cumsum; distinct
    # live masks only (no-null columns all share the plain valid mask).
    # A key-only aggregate (DISTINCT dedup) has no value columns: emit a
    # 1-wide dummy so downstream shapes stay static.
    cnt_stack = jnp.stack(
        [
            (row_valid if k == -1 else lives[k]).astype(jnp.int32)
            for k in live_layout
        ]
        or [jnp.zeros(n, jnp.int32)],
        axis=1,
    )
    cnt_cs = _prefix_sum_2d(cnt_stack)

    # running sums, stacked per accumulator dtype
    sum_cs = []
    for dt, idxs in sum_layout:
        acc_t = jnp.dtype(dt)
        contribs = [
            jnp.where(
                lives[i], val_cols[i], jnp.zeros_like(val_cols[i])
            ).astype(acc_t)
            for i in idxs
        ]
        stacked = jnp.stack(contribs, axis=1)
        sum_cs.append(
            _float_prefix_parts(stacked)
            if jnp.issubdtype(acc_t, jnp.floating)
            else _prefix_sum_2d(stacked)
        )
    mm_vals = []
    for i in mm_idx:
        vc, live = val_cols[i], lives[i]
        if ops[i] == AggOp.MIN:
            masked = jnp.where(live, vc, _max_ident(vc.dtype))
            mm_vals.append(
                jnp.full(capacity, _max_ident(vc.dtype), vc.dtype)
                .at[sid].min(masked, mode="drop", indices_are_sorted=hint)
            )
        else:
            masked = jnp.where(live, vc, _min_ident(vc.dtype))
            mm_vals.append(
                jnp.full(capacity, _min_ident(vc.dtype), vc.dtype)
                .at[sid].max(masked, mode="drop", indices_are_sorted=hint)
            )
    return (
        n_groups.astype(jnp.int32),
        overflow,
        input_was_sorted,
        sorted_ok,
        ps,
        cnt_cs,
        sum_cs,
        mm_vals,
    )


def _seg_part2(
    n_groups,
    ps,
    cnt_cs,
    sum_cs: list,
    mm_vals: list,
    key_cols: list,
    key_nulls: list,
    ops: tuple,
    capacity: int,
    sum_layout: tuple,
    live_layout: tuple,
    mm_idx: tuple,
):
    """Program 2: ONE boundary gather per stacked cumsum -> per-group
    totals. ``pre[g] = cs[ps_g - 1]`` (0 when ``ps_g == 0``); since dead
    rows contribute nothing, ``pre[g+1]`` is exactly the cumsum at segment
    g's end, so ``totals[g] = pre[g+1] - pre[g]`` with the last live group
    closed by the grand total ``cs[n-1]``. Dead slots (``ps == n``
    sentinel) gather the grand total on both sides and cancel to zero."""
    n = cnt_cs.shape[0]
    slot = jnp.arange(capacity, dtype=jnp.int32)
    out_valid = slot < n_groups
    ps_c = jnp.clip(ps, 0, n - 1)
    ps_prev = jnp.clip(ps_c - 1, 0, n - 1)
    is_last = slot == n_groups - 1

    def seg_totals(cs2d):
        pre = jnp.where((ps > 0)[:, None], cs2d[ps_prev], 0)
        total = cs2d[n - 1]
        nxt = jnp.concatenate([pre[1:], pre[-1:]])
        nxt = jnp.where(is_last[:, None], total[None, :], nxt)
        return nxt - pre

    cnt_tot = seg_totals(cnt_cs)
    live_slot = {k: j for j, k in enumerate(live_layout)}
    sum_slot: dict[int, tuple[int, int]] = {}
    sum_tots = [
        _float_seg_totals(cs, ps, out_valid)
        if isinstance(cs, (tuple, list))
        else seg_totals(cs)
        for cs in sum_cs
    ]
    for gi, (dt, idxs) in enumerate(sum_layout):
        for j, i in enumerate(idxs):
            sum_slot[i] = (gi, j)
    mm_map = dict(zip(mm_idx, mm_vals))

    m = len(ops)
    out_vals: list = [None] * m
    out_val_nulls: list = [None] * m
    for i, op in enumerate(ops):
        lk = i if i in live_slot else -1
        nonnull = cnt_tot[:, live_slot[lk]].astype(jnp.int64)
        if op == AggOp.COUNT:
            out_vals[i] = jnp.where(out_valid, nonnull, 0)
            continue
        out_val_nulls[i] = nonnull == 0
        if op == AggOp.SUM:
            gi, j = sum_slot[i]
            out_vals[i] = sum_tots[gi][:, j]
        else:
            out_vals[i] = mm_map[i]

    # group keys: the first row of each segment is LIVE and carries the
    # group's actual key values — one stacked gather at start positions
    key_arrs = list(key_cols) + [kn for kn in key_nulls if kn is not None]
    if key_arrs:
        gathered, _ = take_many_split(key_arrs, [], ps_c)
    else:
        gathered = []
    out_keys = [
        jnp.where(out_valid, k, jnp.zeros_like(k))
        for k in gathered[: len(key_cols)]
    ]
    kn_it = iter(gathered[len(key_cols):])
    out_key_nulls = [
        (next(kn_it) & out_valid) if kn is not None else None
        for kn in key_nulls
    ]
    return GroupAggResult(
        keys=out_keys,
        key_nulls=out_key_nulls,
        values=out_vals,
        value_nulls=out_val_nulls,
        valid=out_valid,
        n_groups=n_groups,
        overflow=jnp.zeros((), bool),  # carried by part1's flag
    )


_seg_part1_jit = jax.jit(
    _seg_part1,
    static_argnames=(
        "ops", "capacity", "clustered", "sum_layout", "live_layout",
        "mm_idx",
    ),
)
_seg_part2_jit = jax.jit(
    _seg_part2,
    static_argnames=("ops", "capacity", "sum_layout", "live_layout",
                     "mm_idx"),
)


def _segment_aggregate(
    valid,
    key_cols: list,
    key_nulls: list,
    val_cols: list,
    val_nulls: list,
    perm,
    ops: tuple,
    capacity: int,
    clustered: bool,
) -> GroupAggResult:
    """Host-composed two-program segment reduction (see module comment)."""
    sum_layout, live_layout, mm_idx = _seg_layouts(
        tuple(str(v.dtype) for v in val_cols),
        tuple(vn is not None for vn in val_nulls),
        tuple(ops),
    )
    (
        n_groups, overflow, input_was_sorted, sorted_ok, ps,
        cnt_cs, sum_cs, mm_vals,
    ) = _seg_part1_jit(
        valid, list(key_cols), list(key_nulls), list(val_cols),
        list(val_nulls), perm, tuple(ops), capacity, clustered,
        sum_layout, live_layout, mm_idx,
    )
    res = _seg_part2_jit(
        n_groups, ps, cnt_cs, list(sum_cs), list(mm_vals),
        list(key_cols), list(key_nulls), tuple(ops), capacity,
        sum_layout, live_layout, mm_idx,
    )
    res.overflow = overflow
    res.input_was_sorted = input_was_sorted
    res.sorted_ok = sorted_ok
    return res


def group_aggregate(
    key_cols: list[jnp.ndarray],
    key_nulls: list[jnp.ndarray | None],
    valid: jnp.ndarray,
    val_cols: list[jnp.ndarray],
    val_nulls: list[jnp.ndarray | None],
    ops: list[AggOp],
    capacity: int,
    presorted: bool = False,
) -> GroupAggResult:
    """Aggregate ``val_cols[i]`` with ``ops[i]`` grouped by ``key_cols``.

    All inputs share one row axis; ``valid`` masks live rows. Outputs have
    static length ``capacity`` with a validity mask over actual groups.

    ``presorted=False``: host-composes cached sort passes + the stacked
    gather, then the two-program segment finisher; the result's
    ``input_was_sorted`` device flag reports (for free, off the stable
    sort's permutation) whether the sort was actually needed.

    ``presorted=True``: skips the sort AND the gather entirely — rows are
    speculated to be grouped-adjacent among live rows (clustered input,
    e.g. TPC-H lineitem grouped by l_orderkey); the result's ``sorted_ok``
    flag must be validated via the deferred-speculation protocol.
    """
    if presorted:
        return _segment_aggregate(
            valid, key_cols, key_nulls, val_cols, val_nulls, None,
            tuple(ops), capacity, clustered=True,
        )
    cap = valid.shape[0]
    # SQL GROUP BY: NULL is its own group. Null keys get a flag pass and a
    # zeroed value so all-null rows compare equal.
    passes: list[tuple[jnp.ndarray, bool]] = [
        (_not_program(cap)(valid), False)  # valid rows first
    ]
    for kc, kn in zip(key_cols, key_nulls):
        if kn is not None:
            passes.append((kn, False))
            passes.append(
                (_zeroed_program(str(kc.dtype), cap)(kn, kc), False)
            )
        else:
            passes.append((kc, False))
    perm = multi_key_perm(passes)
    from ballista_tpu.ops.perm import take_batch

    s_cols, s_nulls, s_valid = take_batch(
        list(key_cols) + list(val_cols),
        list(key_nulls) + list(val_nulls),
        valid,
        perm,
    )
    nk = len(key_cols)
    return _segment_aggregate(
        s_valid, list(s_cols[:nk]), list(s_nulls[:nk]),
        list(s_cols[nk:]), list(s_nulls[nk:]), perm, tuple(ops),
        capacity, clustered=False,
    )


def _dense_agg(
    key_codes: list,
    key_nulls: list,
    vocab_sizes: tuple,
    valid,
    val_cols: list,
    val_nulls: list,
    ops: tuple,
):
    """Dense grouped aggregation for dictionary-coded / small-domain keys:
    the group slot is the mixed-radix index over (vocab+1) values per key
    (the +1 slot is NULL — SQL groups NULLs together), and every reduction
    is one reduction of its kind over all columns (``_stacked_reduce``) —
    no sorting at all. This is the hot TPC-H q1 shape (GROUP BY
    returnflag, linestatus -> 6 slots): one fused XLA program per batch
    instead of a cascade of sort passes.

    Capacity is exactly ``prod(vocab+1)``, so overflow is impossible."""
    radix = [v + 1 for v in vocab_sizes]
    P = dense_slots(vocab_sizes)
    seg = None
    for code, nm, v in zip(key_codes, key_nulls, vocab_sizes):
        c = jnp.clip(code.astype(jnp.int32), 0, v - 1)
        if nm is not None:
            c = jnp.where(nm, v, c)
        seg = c if seg is None else seg * (v + 1) + c
    rid_all = jnp.where(valid, seg, P)

    # a value without NULLs is live in every valid row: the reduction's
    # count of rows a slot, which is also the slot's occupancy
    lives = [None if vn is None else (valid & ~vn) for vn in val_nulls]
    out_vals, out_val_nulls, occupied = _stacked_reduce(
        rid_all, P, list(val_cols), lives, ops
    )

    # reconstruct key codes per slot from the mixed-radix index
    slot = jnp.arange(P, dtype=jnp.int32)
    out_keys, out_key_nulls = [], []
    strides = []
    s = 1
    for r in reversed(radix):
        strides.append(s)
        s *= r
    strides.reverse()
    for (code, nm, v), stride in zip(
        zip(key_codes, key_nulls, vocab_sizes), strides
    ):
        digit = (slot // stride) % (v + 1)
        out_keys.append(digit.astype(code.dtype))
        out_key_nulls.append(
            (digit == v) if nm is not None else None
        )
    n_groups = jnp.sum(occupied.astype(jnp.int32))
    return GroupAggResult(
        keys=out_keys,
        key_nulls=out_key_nulls,
        values=out_vals,
        value_nulls=out_val_nulls,
        valid=occupied,
        n_groups=n_groups,
        overflow=jnp.zeros((), dtype=bool),
    )


_dense_agg_jit = jax.jit(
    _dense_agg, static_argnames=("vocab_sizes", "ops")
)

# Dense slots grow as prod(vocab+1); past this the sort-based kernel's
# O(n log n) wins back: the factorized one-hot's MXU work grows as slots x
# rows, and the f64 sums and MIN/MAX that keep their scatter stop being
# cache-friendly.
DENSE_AGG_MAX_SLOTS = 1 << 16


def dense_slots(vocab_sizes) -> int:
    """The dense path's slot count: the mixed radix of (vocab + 1) a key."""
    return math.prod(v + 1 for v in vocab_sizes)


def dense_group_aggregate(
    key_codes: list[jnp.ndarray],
    key_nulls: list[jnp.ndarray | None],
    vocab_sizes: list[int],
    valid: jnp.ndarray,
    val_cols: list[jnp.ndarray],
    val_nulls: list[jnp.ndarray | None],
    ops: list[AggOp],
) -> GroupAggResult:
    """Sort-free aggregation over dictionary codes (see ``_dense_agg``)."""
    return _dense_agg_jit(
        list(key_codes), list(key_nulls), tuple(vocab_sizes), valid,
        list(val_cols), list(val_nulls), tuple(ops),
    )


def scalar_aggregate(
    valid: jnp.ndarray,
    val_cols: list[jnp.ndarray],
    val_nulls: list[jnp.ndarray | None],
    ops: list[AggOp],
) -> tuple[list[jnp.ndarray], list[jnp.ndarray | None]]:
    """Ungrouped aggregation -> one scalar per op (+ null flags)."""
    return _scalar_agg_jit(valid, list(val_cols), list(val_nulls), tuple(ops))


def _scalar_agg(valid, val_cols, val_nulls, ops):
    outs: list[jnp.ndarray] = []
    nulls: list[jnp.ndarray | None] = []
    for vc, vn, op in zip(val_cols, val_nulls, ops):
        live = valid if vn is None else (valid & ~vn)
        cnt = jnp.sum(live.astype(jnp.int64))
        if op == AggOp.COUNT:
            outs.append(cnt)
            nulls.append(None)
            continue
        if op == AggOp.SUM:
            outs.append(
                jnp.sum(
                    jnp.where(live, vc, jnp.zeros_like(vc)).astype(
                        _sum_dtype(vc.dtype)
                    )
                )
            )
        elif op == AggOp.MIN:
            outs.append(jnp.min(jnp.where(live, vc, _max_ident(vc.dtype))))
        elif op == AggOp.MAX:
            outs.append(jnp.max(jnp.where(live, vc, _min_ident(vc.dtype))))
        else:  # pragma: no cover
            raise ExecutionError(f"unknown agg op {op}")
        nulls.append(cnt == 0)
    return outs, nulls


_scalar_agg_jit = jax.jit(_scalar_agg, static_argnames=("ops",))
