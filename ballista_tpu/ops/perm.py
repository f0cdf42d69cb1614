"""Cached permutation primitives: the engine's sort substrate.

``lax.sort`` compile time for the TPU explodes with operand count and key
width (PERF.md records the seconds). So the engine never emits
multi-operand sorts. Instead every multi-key sort is a
sequence of single-key STABLE argsort passes (least-significant key first —
classic LSD radix), and each pass reuses one globally cached compiled
program per (dtype, direction, capacity). All of TPC-H shares a handful of
these programs per batch capacity, so compile cost amortizes across
queries, and the persistent compilation cache makes them free across
processes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def argsort_i32(c: jnp.ndarray) -> jnp.ndarray:
    """``jnp.argsort(c, stable=True)`` with an int32 index operand.

    jnp's own iota is int64 under x64, which the TPU carries as two 32-bit
    operands. The same stable sort with a 32-bit iota compiles in about half
    the time and runs faster on a v5e (PERF.md, PR 21: int32[2M] 30.1 s ->
    15.9 s to compile, 5.3 ms -> 3.1 ms to run). Capacities stay far below
    2^31. Traceable."""
    iota = jax.lax.iota(jnp.int32, c.shape[0])
    return jax.lax.sort_key_val(c, iota, is_stable=True)[1]


def _reversed(c: jnp.ndarray) -> jnp.ndarray:
    """A key whose ascending order is ``c``'s descending one."""
    if jnp.issubdtype(c.dtype, jnp.floating):
        return -c
    return ~c  # ~x = -x-1: total order reversal incl. INT_MIN


@functools.lru_cache(maxsize=None)
def _argsort_program(dtype: str, cap: int, descending: bool):
    def sort_argsort(col):
        return argsort_i32(_reversed(col) if descending else col)

    return jax.jit(sort_argsort)


def _f32_order(x: jnp.ndarray) -> jnp.ndarray:
    """The int32 that orders as the float32 ``x`` does: what ``lax.sort``'s
    own comparator makes of a float at every comparison (zeros and NaNs
    standardised, then the bits, negatives reversed; NaNs last), made once."""
    x = jnp.where(x == 0, jnp.zeros_like(x), x)
    x = jnp.where(jnp.isnan(x), jnp.full_like(x, jnp.nan), x)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, jnp.iinfo(jnp.int32).max - bits, bits)


@functools.lru_cache(maxsize=None)
def _f64_keys_program(cap: int, descending: bool):
    def sort_f64_keys(col):
        c = -col if descending else col
        hi = c.astype(jnp.float32)
        lo = (c - hi.astype(jnp.float64)).astype(jnp.float32)
        return _f32_order(hi), _f32_order(lo)

    return jax.jit(sort_f64_keys)


def argsorts_of(col: jnp.ndarray) -> int:
    """Argsort passes a sort by ``col`` dispatches: ``narrow_passes``' rule."""
    wide = col.dtype == jnp.float64 and jax.default_backend() != "cpu"
    return 2 if wide else 1


def narrow_passes(
    passes: list[tuple[jnp.ndarray, bool]],
) -> list[tuple[jnp.ndarray, bool]]:
    """``passes`` (most significant first) with every float64 key as two
    int32 keys, on the TPU.

    The chip has no float64: the compiler carries one as a pair of float32
    (a value read back differs from what went in by up to 2e-15 of itself),
    compares pairs inside the sort, and takes 225-259 s over one
    float64[8M] argsort program, ascending and descending apart (PERF.md,
    PR 33; compiled for a described v5e; int64 84 s, int32 32 s). The pair
    sorts as two stable passes over its halves' integer order, which ride
    the int32 program of their capacity with the flags: the order is the
    same, and a float64 key compiles no sort program of its own. The CPU has
    float64 whole and compiles its sort in a second: it keeps the one pass.
    """
    out = []
    for col, desc in passes:
        if argsorts_of(col) == 2:
            hi, lo = _f64_keys_program(col.shape[0], desc)(col)
            out += [(hi, False), (lo, False)]
        else:
            out.append((col, desc))
    return out


def stable_argsort(col: jnp.ndarray, descending: bool = False) -> jnp.ndarray:
    """Stable argsort via a cached single-key program."""
    if col.dtype == jnp.bool_:
        # flags ride the int32 program of their capacity: every distinct
        # (dtype, capacity) is a sort program of its own, and one sort
        # program costs the TPU compiler 10-55 s (PERF.md, PR 21)
        col = col.astype(jnp.int32)
    return _argsort_program(str(col.dtype), col.shape[0], descending)(col)


@functools.lru_cache(maxsize=None)
def _take_program(dtype: str, cap: int):
    def perm_take(col, perm):
        return col[perm]

    return jax.jit(perm_take)


def take(col: jnp.ndarray, perm: jnp.ndarray) -> jnp.ndarray:
    """Gather one column by a permutation (cached per dtype/capacity)."""
    return _take_program(str(col.dtype), col.shape[0])(col, perm)


def group_by_dtype(cols: list) -> dict:
    """Positions of ``cols`` grouped by dtype string — the shared index
    plan for stacked gathers (take_many) and stacked scatters
    (ops/aggregate)."""
    by_dtype: dict[str, list[int]] = {}
    for i, c in enumerate(cols):
        by_dtype.setdefault(str(c.dtype), []).append(i)
    return by_dtype


def take_many(cols: list, perm: jnp.ndarray) -> list:
    """Gather many columns by one permutation with one gather per distinct
    dtype (columns stacked on a trailing axis).

    A TPU gather's cost is dominated by the per-row random access, not the
    row payload, so gathering an (n, M) stack moves M columns for ~the
    price of one. Callers inside jit get the stack/unbind fused away."""
    by_dtype = group_by_dtype(cols)
    out: list = [None] * len(cols)
    for dt, idxs in by_dtype.items():
        if len(idxs) == 1:
            i = idxs[0]
            out[i] = cols[i][perm]
            continue
        stacked = jnp.stack([cols[i] for i in idxs], axis=1)
        g = stacked[perm]
        for j, i in enumerate(idxs):
            out[i] = g[:, j]
    return out


def take_many_split(
    cols: list, optionals: list, perm: jnp.ndarray
) -> tuple[list, list]:
    """One stacked-by-dtype gather over ``cols`` plus the non-None entries
    of ``optionals`` (null masks). Returns (gathered cols, gathered
    optionals with None preserved in place)."""
    present = [i for i, m in enumerate(optionals) if m is not None]
    gathered = take_many(
        list(cols) + [optionals[i] for i in present], perm
    )
    out_opt: list = [None] * len(optionals)
    for j, i in enumerate(present):
        out_opt[i] = gathered[len(cols) + j]
    return gathered[: len(cols)], out_opt


def _take_stacked(cols, nulls, valid, perm):
    """Columns, null masks and validity by ``perm``, stacked by dtype."""
    gathered, out_nulls = take_many_split(
        [valid] + list(cols), list(nulls), perm
    )
    return gathered[1:], out_nulls, gathered[0]


@functools.lru_cache(maxsize=None)
def _take_batch_program(sig: tuple, nulls_sig: tuple):
    """One jitted program gathering a whole column set (+ null masks +
    valid) by a permutation, stacked by dtype — the sort/shuffle data
    movement as ONE dispatch instead of one per column. (jax.jit retraces
    per shape on its own, so capacity is deliberately NOT in the key.)"""

    def perm_take_batch(cols, nulls, valid, perm):
        return _take_stacked(cols, nulls, valid, perm)

    return jax.jit(perm_take_batch)


def take_batch(cols: list, nulls: list, valid, perm):
    """Gather columns + null masks + valid by ``perm`` in one dispatch."""
    sig = tuple(str(c.dtype) for c in cols)
    nulls_sig = tuple(m is not None for m in nulls)
    prog = _take_batch_program(sig, nulls_sig)
    return prog(tuple(cols), tuple(nulls), valid, perm)


def refine_perm(
    perm: jnp.ndarray, col: jnp.ndarray, descending: bool = False
) -> jnp.ndarray:
    """One radix pass: reorder ``perm`` by ``col[perm]`` (stable, so prior
    passes' order is preserved among equal keys)."""
    c = take(col, perm)
    idx = stable_argsort(c, descending)
    return take(perm, idx)


@functools.lru_cache(maxsize=None)
def _i64_keys_program(cap: int, descending: bool):
    def sort_i64_keys(col):
        c = ~col if descending else col
        hi = (c >> 32).astype(jnp.int32)
        # the low half orders as an unsigned number: its top bit flipped,
        # the same bits order as an int32
        lo = jax.lax.bitcast_convert_type(
            c.astype(jnp.uint32) ^ jnp.uint32(0x80000000), jnp.int32
        )
        return hi, lo

    return jax.jit(sort_i64_keys)


def split_wide_ints(
    passes: list[tuple[jnp.ndarray, bool]],
) -> list[tuple[jnp.ndarray, bool]]:
    """``passes`` (most significant first) with every int64 key as two int32
    keys, on the TPU.

    The chip has no int64 either: an int64 argsort program costs its
    compiler 17-32 s at every capacity from 32,768 to 2M rows, 2 to 2.5
    times the int32 program of that capacity, which the validity pass of
    the same sort compiles anyway (PERF.md, PR 36: six such programs were
    122 s of one cell's cold run). Join keys and group keys are int64 from
    the first stage boundary on (a shuffle's partitions are read back at
    their logical width, so that every partition has one), so nearly every
    sort behind an exchange had one. The halves sort as two stable passes
    through the int32 program, in the same order, ties included. The pass
    this adds costs what the first pass of every sort no longer does
    (``multi_key_perm``)."""
    if jax.default_backend() == "cpu":
        return passes
    out = []
    for col, desc in passes:
        if col.dtype == jnp.int64:
            hi, lo = _i64_keys_program(col.shape[0], desc)(col)
            out += [(hi, False), (lo, False)]
        else:
            out.append((col, desc))
    return out


def multi_key_perm(
    passes: list[tuple[jnp.ndarray, bool]],
) -> jnp.ndarray:
    """Permutation sorting by ``passes`` in MOST-significant-first order.
    Each pass is (column, descending). Executes least-significant first.
    The first pass sorts the rows where they lie: gathering its key by the
    identity, and the identity by its result, would be two of the random
    access passes that are most of a sort's device time (PERF.md §5)."""
    todo = list(reversed(split_wide_ints(narrow_passes(passes))))
    col, desc = todo[0]
    perm = stable_argsort(col, desc)
    for col, desc in todo[1:]:
        perm = refine_perm(perm, col, desc)
    return perm


# -- the window and percentile operators' own programs ----------------------
# A sort over a whole table is these operators' work and nobody else's, so
# its programs carry a name of their own (``holistic_*``): the trace's
# ``XLA Modules`` line and a reader can then tell them from the group-by's
# and the join's (docs/observability.md). A pass is ONE program here: the
# key's gather, the argsort and the permutation's gather fused.


@functools.lru_cache(maxsize=None)
def _holistic_pass_program(dtype: str, cap: int, descending: bool):
    def holistic_sort_pass(col, perm):
        c = col[perm]
        return perm[argsort_i32(_reversed(c) if descending else c)]

    return jax.jit(holistic_sort_pass)


def holistic_perm(passes: list[tuple[jnp.ndarray, bool]]) -> jnp.ndarray:
    """``multi_key_perm`` for the window and percentile operators: the same
    passes, the same order among equal keys, one named program a pass."""
    perm = jnp.arange(passes[0][0].shape[0], dtype=jnp.int32)
    for col, desc in reversed(narrow_passes(passes)):
        if col.dtype == jnp.bool_:
            col = col.astype(jnp.int32)  # as stable_argsort: one program less
        perm = _holistic_pass_program(str(col.dtype), col.shape[0], desc)(
            col, perm
        )
    return perm


@functools.lru_cache(maxsize=None)
def _holistic_take_program(sig: tuple, nulls_sig: tuple):
    def holistic_take(cols, nulls, valid, perm):
        return _take_stacked(cols, nulls, valid, perm)

    return jax.jit(holistic_take)


def holistic_take(cols: list, nulls: list, valid, perm):
    """``take_batch`` under the operators' own name: columns, null masks
    and validity by ``perm`` in one dispatch, stacked by dtype."""
    prog = _holistic_take_program(
        tuple(str(c.dtype) for c in cols),
        tuple(m is not None for m in nulls),
    )
    return prog(tuple(cols), tuple(nulls), valid, perm)

