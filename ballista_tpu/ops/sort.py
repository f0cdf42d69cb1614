"""Multi-key sort on device.

Replaces DataFusion's SortExec (referenced by the plan serde at
ballista/rust/core/src/serde/physical_plan/mod.rs sort arm). A multi-key
sort runs as stable single-key argsort passes, least-significant key first
(LSD radix over cached per-(dtype,capacity) programs — see ops/perm.py for
why multi-operand ``lax.sort`` is avoided); all columns then ride one
gather per column. Invalid rows always sort last (leading ``~valid`` pass),
so a sorted batch is also compact.

String columns sort correctly by dictionary code because dictionaries are
order-preserving (see columnar.arrow_interop).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ballista_tpu.columnar.batch import DeviceBatch
from ballista_tpu.ops.perm import argsorts_of, multi_key_perm, take_batch


@dataclasses.dataclass(frozen=True)
class SortKey:
    """One ORDER BY term: column index, direction, null placement."""

    col: int
    ascending: bool = True
    nulls_first: bool = False


def resolve_sort_keys(schema, sort_exprs) -> list["SortKey"]:
    """ORDER BY terms -> SortKeys; raises PlanError for non-column keys
    (the planner projects expressions first). Shared by SortExec and the
    mesh TopK so key semantics cannot drift."""
    from ballista_tpu.errors import PlanError
    from ballista_tpu.expr import logical as L

    keys = []
    for s in sort_exprs:
        if not isinstance(s.expr, L.Column):
            raise PlanError(
                "sort requires column sort keys (planner projects "
                "expressions first)"
            )
        keys.append(
            SortKey(
                col=L.resolve_field_index(schema, s.expr.cname),
                ascending=s.ascending,
                nulls_first=s.nulls_first,
            )
        )
    return keys


def sort_passes(cols, nulls, valid, keys: list["SortKey"]):
    """The (column, descending) pass list realizing SortKey semantics:
    invalid rows last, then per key a null-placement pass and the key
    itself. The single source of truth for sort ordering — sort_perm and
    the mesh TopK program both build on it. Operates on raw sequences so
    it can run inside a traced (shard_map) context."""
    passes = [(~valid, False)]
    for k in keys:
        nm = nulls[k.col]
        if nm is not None:
            # 0 sorts before 1: nulls_first -> nulls get 0
            passes.append((nm != k.nulls_first, False))
        passes.append((cols[k.col], not k.ascending))
    return passes


def argsort_count(cols, nulls, keys) -> int:
    """Argsort passes a sort by ``keys`` dispatches: ``sort_passes``'
    validity pass, each key's null pass and the key's own (two for a key
    that ``ops/perm.py narrow_passes`` splits), without making them."""
    return 1 + sum(
        (nulls[k.col] is not None) + argsorts_of(cols[k.col]) for k in keys
    )


def sort_perm(batch: DeviceBatch, keys: list[SortKey]) -> jnp.ndarray:
    """The sorting permutation for ``keys`` (invalid rows last)."""
    return multi_key_perm(
        sort_passes(batch.columns, batch.nulls, batch.valid, keys)
    )


def gather_batch(batch: DeviceBatch, perm: jnp.ndarray) -> DeviceBatch:
    """Reorder a whole batch by a permutation — ONE jitted dispatch with
    columns stacked by dtype, so the TPU pays one random-access pass
    instead of one per column (see ops/perm.take_many)."""
    cols, nulls, valid = take_batch(
        list(batch.columns), list(batch.nulls), batch.valid, perm
    )
    return DeviceBatch(
        schema=batch.schema,
        columns=tuple(cols),
        valid=valid,
        nulls=tuple(nulls),
        dictionaries=dict(batch.dictionaries),
    )


def sort_batch(batch: DeviceBatch, keys: list[SortKey]) -> DeviceBatch:
    return gather_batch(batch, sort_perm(batch, keys))
