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


@functools.lru_cache(maxsize=None)
def _argsort_program(dtype: str, cap: int, descending: bool, is_float: bool):
    def sort_argsort(col):
        c = col
        if descending:
            if is_float:
                c = -c
            else:
                c = ~c  # ~x = -x-1: total order reversal incl. INT_MIN
        return argsort_i32(c)

    return jax.jit(sort_argsort)


def stable_argsort(col: jnp.ndarray, descending: bool = False) -> jnp.ndarray:
    """Stable argsort via a cached single-key program."""
    if col.dtype == jnp.bool_:
        # flags ride the int32 program of their capacity: every distinct
        # (dtype, capacity) is a sort program of its own, and one sort
        # program costs the TPU compiler 10-55 s (PERF.md, PR 21)
        col = col.astype(jnp.int32)
    return _argsort_program(
        str(col.dtype),
        col.shape[0],
        descending,
        bool(jnp.issubdtype(col.dtype, jnp.floating)),
    )(col)


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


@functools.lru_cache(maxsize=None)
def _take_batch_program(sig: tuple, nulls_sig: tuple):
    """One jitted program gathering a whole column set (+ null masks +
    valid) by a permutation, stacked by dtype — the sort/shuffle data
    movement as ONE dispatch instead of one per column. (jax.jit retraces
    per shape on its own, so capacity is deliberately NOT in the key.)"""

    def perm_take_batch(cols, nulls, valid, perm):
        gathered, out_nulls = take_many_split(
            [valid] + list(cols), list(nulls), perm
        )
        return gathered[1:], out_nulls, gathered[0]

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


def multi_key_perm(
    passes: list[tuple[jnp.ndarray, bool]],
) -> jnp.ndarray:
    """Permutation sorting by ``passes`` in MOST-significant-first order.
    Each pass is (column, descending). Executes least-significant first."""
    cap = passes[0][0].shape[0]
    perm = jnp.arange(cap, dtype=jnp.int32)
    for col, desc in reversed(passes):
        perm = refine_perm(perm, col, desc)
    return perm
