"""Compaction: move live rows to the front of a batch.

Filters in this engine only clear validity bits (no data movement). Before
ops that are sensitive to row placement — shuffle writes, join builds,
limits — an explicit compaction gathers live rows to the front via one
stable argsort pass on the invalid flag, or on the shuffle writer's
partition ids (cached program, see ops/perm.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ballista_tpu.columnar.batch import DeviceBatch
from ballista_tpu.ops.perm import stable_argsort, take


@functools.lru_cache(maxsize=None)
def _invalid_program(cap: int):
    def compact_invalid(v):
        return ~v

    return jax.jit(compact_invalid)


@functools.lru_cache(maxsize=None)
def _front_valid_program(cap: int):
    def compact_front_valid(v):
        return jnp.arange(cap, dtype=jnp.int32) < jnp.sum(
            v.astype(jnp.int32)
        )

    return jax.jit(compact_front_valid)


def compact(batch: DeviceBatch, key: jnp.ndarray | None = None) -> DeviceBatch:
    """Live rows to the front, in input order. ``key``: an int32 sort key
    in which every live row is less than every dead one (the shuffle
    writer's partition ids: a dead row's is the partition count), so the
    live rows come out in key order and in input order within a key. The
    same stable argsort program either way."""
    if key is None:
        key = _invalid_program(batch.capacity)(batch.valid)
    order = stable_argsort(key)
    cols = tuple(take(c, order) for c in batch.columns)
    nulls = tuple(None if m is None else take(m, order) for m in batch.nulls)
    valid = _front_valid_program(batch.capacity)(batch.valid)
    return DeviceBatch(
        schema=batch.schema,
        columns=cols,
        nulls=nulls,
        valid=valid,
        dictionaries=dict(batch.dictionaries),
    )
