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


def predicate_table(d: Dictionary, key: tuple, compute):
    """What a string predicate or function gives for every entry of ``d``
    (a ``LIKE`` mask, ``substr``'s codes, an ``IN`` list's codes): computed
    once per (dictionary, ``key``) by ``compute(d)`` and kept with the
    dictionary it was computed for, so it goes when the dictionary does and
    a new dictionary is evaluated anew. The ``task.dict_predicate`` phase
    brackets the evaluation and the look-up alike; the counters tell them
    apart (docs/observability.md)."""
    from ballista_tpu.compilecache import metrics

    with obs_trace.phase("task.dict_predicate") as ph:
        table = d._tables.get(key)
        if table is not None:
            metrics.add("dict_predicate.reused")
            return table
        table = d._tables[key] = compute(d)
        metrics.add("dict_predicate.entries", len(d))
        if d._arrow is not None:  # the evaluation walked the entries
            ph.nbytes = d._arrow.nbytes
    return table


_RE2_SPECIAL = frozenset("\\.+*?()|[]{}^$")


def like_table(d: Dictionary, pattern: str) -> np.ndarray:
    """bool[len(d)]: which entries match the SQL ``LIKE`` pattern (``%`` any
    run of characters, ``_`` one character, no escape character,
    case-sensitive, anchored at both ends), by Arrow's ``match_like`` over
    the dictionary's array: one vectorised pass, no Python per entry."""
    import pyarrow.compute as pc

    def compute(d):
        if not len(d):
            return np.zeros(0, dtype=bool)
        if "\\" not in pattern:
            return np.asarray(pc.match_like(d.arrow(), pattern))
        # match_like reads a backslash as an escape on some of its paths
        # and as itself on others; SQL without an ESCAPE clause has none, so
        # such a pattern goes to the regular expression it stands for
        rx = "".join(
            ".*" if ch == "%" else "." if ch == "_"
            else "\\" + ch if ch in _RE2_SPECIAL else ch
            for ch in pattern
        )
        return np.asarray(
            pc.match_substring_regex(d.arrow(), f"(?s)^(?:{rx})$")
        )

    return predicate_table(d, ("like", pattern), compute)


def substr_table(
    d: Dictionary, start: int, length: int | None
) -> tuple[np.ndarray, Dictionary]:
    """SQL ``substr(s, start[, length])`` (1-based, in characters) of every
    entry: the table from ``d``'s codes to the codes of the sorted
    dictionary of the distinct results, and that dictionary."""
    import pyarrow as pa
    import pyarrow.compute as pc

    def compute(d):
        if not len(d):
            return np.zeros(0, dtype=np.int32), Dictionary(())
        lo = start - 1
        if lo < 0 or (length is not None and length < 0):
            # out of SQL's range: Python's slice, entry by entry, as ever
            cut = pa.array(
                [s[lo:] if length is None else s[lo : lo + length]
                 for s in d.values], type=pa.string(),
            )
        else:
            stop = None if length is None else lo + length
            cut = pc.utf8_slice_codeunits(d.arrow(), lo, stop)
        uniq = pc.unique(cut)
        uniq = uniq.take(pc.array_sort_indices(uniq))
        table = np.asarray(pc.index_in(cut, uniq)).astype(np.int32)
        return table, Dictionary(tuple(uniq.to_pylist()))

    return predicate_table(d, ("substr", start, length), compute)


def in_codes(d: Dictionary, literals: tuple[str, ...]) -> np.ndarray:
    """int32 codes of the ``literals`` that ``d`` holds (an ``IN`` list's)."""

    def compute(d):
        codes = (d.index_of(s) for s in literals)
        return np.asarray([c for c in codes if c >= 0], dtype=np.int32)

    return predicate_table(d, ("in", literals), compute)


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
