"""Dictionary algebra for string columns.

Device code only ever sees int32 codes; all string semantics live in the
order-preserving (sorted) host dictionaries. Comparing or joining two string
columns with *different* dictionaries requires remapping both onto a merged
dictionary first — the remap is a host-built lookup table gathered on device
(trace-time constant, so XLA folds it into the program).
"""

from __future__ import annotations

import bisect

import jax.numpy as jnp
import numpy as np

from ballista_tpu.columnar.batch import Dictionary
from ballista_tpu.obs import trace as obs_trace


def merge_dictionaries(
    a: Dictionary, b: Dictionary
) -> tuple[Dictionary, np.ndarray, np.ndarray]:
    """Merged sorted dictionary + code remap tables for each input.

    ``remap_a[old_code] = new_code`` (and likewise ``remap_b``). Sorted-merge
    keeps the merged dictionary order-preserving, so remapped codes still
    compare like the strings they encode. Host work per entry: the
    ``task.dict_merge`` phase (docs/observability.md).
    """
    with obs_trace.phase("task.dict_merge"):
        merged, (remap_a, remap_b) = merge_many([a, b])
    return merged, remap_a, remap_b


def merge_many(
    dicts: list[Dictionary],
) -> tuple[Dictionary, list[np.ndarray]]:
    """The sorted union of ``dicts`` and, for each, the table from its
    codes to the union's: one pass over the entries, whatever the number
    of dictionaries, where folding ``merge_dictionaries`` over them sorts
    the growing union once per dictionary. The caller holds the
    ``task.dict_merge`` phase."""
    merged = tuple(sorted(set().union(*(d.values for d in dicts))))
    pos = {v: i for i, v in enumerate(merged)}
    tables: dict[int, np.ndarray] = {}
    for d in dicts:
        if id(d) not in tables:
            tables[id(d)] = np.asarray(
                [pos[v] for v in d.values], dtype=np.int32
            )
    return Dictionary(merged), [tables[id(d)] for d in dicts]


def remap_codes(codes: jnp.ndarray, table: np.ndarray) -> jnp.ndarray:
    """Gather codes through a host remap table (empty table -> unchanged,
    the column is all-null)."""
    if len(table) == 0:
        return codes
    return jnp.asarray(table)[jnp.clip(codes, 0, len(table) - 1)]


def bisect_left(d: Dictionary, s: str) -> int:
    return bisect.bisect_left(d.values, s)


def bisect_right(d: Dictionary, s: str) -> int:
    return bisect.bisect_right(d.values, s)
