"""Batch concatenation with dictionary unification.

Used by pipeline-breaking operators (sort, join build, final aggregate,
union) to merge a partition's batches into one statically-shaped batch.
String columns from different sources may carry different dictionaries;
they are remapped onto a merged (still order-preserving) dictionary before
the device concat.
"""

from __future__ import annotations

import jax.numpy as jnp

from ballista_tpu.columnar.batch import DeviceBatch, Dictionary, round_capacity
from ballista_tpu.columnar.dict_util import merge_many, remap_codes
from ballista_tpu.datatypes import DataType, Schema
from ballista_tpu.errors import InternalError
from ballista_tpu.obs import trace as obs_trace


def unify_dictionaries(
    batches: list[DeviceBatch], schema: Schema
) -> list[DeviceBatch]:
    """Remap STRING columns of all batches onto shared dictionaries. The
    host side of it (comparing, merging, the remap tables: work per
    dictionary entry) is the ``task.dict_merge`` phase."""
    out = batches
    for i, field in enumerate(schema):
        if field.dtype != DataType.STRING:
            continue
        names = [b.schema.fields[i].name for b in out]
        dicts = [b.dictionaries.get(n) for b, n in zip(out, names)]
        if any(d is None for d in dicts):
            raise InternalError(
                f"string column {field.name!r} missing dictionary in concat"
            )
        if all(d is dicts[0] for d in dicts):
            continue  # one partition's states: nothing to compare
        with obs_trace.phase("task.dict_merge"):
            if all(d.values == dicts[0].values for d in dicts):
                continue
            # one merge over all of them, and one remap table for each
            # distinct dictionary object
            merged, remaps = merge_many(dicts)
        new_batches = []
        for b, n, remap in zip(out, names, remaps):
            cols = list(b.columns)
            cols[i] = remap_codes(b.columns[i], remap)
            dd = dict(b.dictionaries)
            dd[n] = merged
            new_batches.append(
                DeviceBatch(
                    schema=b.schema,
                    columns=tuple(cols),
                    valid=b.valid,
                    nulls=b.nulls,
                    dictionaries=dd,
                )
            )
        out = new_batches
    return out


import jax


@jax.jit
def _concat_device(batches: list[DeviceBatch]) -> DeviceBatch:
    return _concat_impl(batches)


def concat_batches(batches: list[DeviceBatch]) -> DeviceBatch:
    """Concatenate batches (same schema) into one batch with bucketed
    capacity. Invalid rows are carried along (callers compact if needed).
    The device work runs under one jit (per input structure)."""
    if not batches:
        raise InternalError("concat of zero batches")
    if len(batches) == 1:
        return batches[0]
    schema = batches[0].schema
    batches = unify_dictionaries(batches, schema)
    return _concat_device(batches)


def _concat_impl(batches: list[DeviceBatch]) -> DeviceBatch:
    schema = batches[0].schema
    total = sum(b.capacity for b in batches)
    cap = round_capacity(total)
    ncols = len(schema)
    cols = []
    for i in range(ncols):
        parts = [b.columns[i] for b in batches]
        arr = jnp.concatenate(parts)
        if arr.shape[0] < cap:
            arr = jnp.pad(arr, (0, cap - arr.shape[0]))
        cols.append(arr)
    valid = jnp.concatenate([b.valid for b in batches])
    if valid.shape[0] < cap:
        valid = jnp.pad(valid, (0, cap - valid.shape[0]))
    nulls: list[jnp.ndarray | None] = []
    for i in range(ncols):
        masks = [b.nulls[i] for b in batches]
        if all(m is None for m in masks):
            nulls.append(None)
            continue
        parts = [
            m if m is not None else jnp.zeros(b.capacity, dtype=bool)
            for m, b in zip(masks, batches)
        ]
        nm = jnp.concatenate(parts)
        if nm.shape[0] < cap:
            nm = jnp.pad(nm, (0, cap - nm.shape[0]))
        nulls.append(nm)
    return DeviceBatch(
        schema=schema,
        columns=tuple(cols),
        valid=valid,
        nulls=tuple(nulls),
        dictionaries=dict(batches[0].dictionaries),
    )
