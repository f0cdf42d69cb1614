"""DeviceBatch — the columnar batch living on TPU.

The reference's unit of data is an Arrow ``RecordBatch`` flowing through
DataFusion operators. On TPU, XLA wants static shapes, so a DeviceBatch is:

- one device array per column, all padded to a shared static ``capacity``
  (rounded up to a bucket size so kernels recompile only per bucket, not per
  row count — SURVEY.md §7 "Dynamic shapes on XLA");
- a ``valid`` boolean row mask: padding rows and filtered-out rows are simply
  invalid. Filters never move data; compaction is an explicit op
  (:mod:`ballista_tpu.ops.compact`) used before shuffles and joins.
- optional per-column null masks (True = null) for nullable data;
- host-side dictionaries for STRING columns (device sees int32 codes).

This replaces the reference's RecordBatch+Arrow-array stack
(used throughout e.g. ballista/rust/core/src/execution_plans/shuffle_writer.rs:209-256)
with a representation XLA can tile onto the MXU/VPU.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ballista_tpu.datatypes import DataType, Field, Schema
from ballista_tpu.errors import InternalError, SchemaError

# Minimum batch capacity. 2048 = 8 sublanes * 256 — comfortably tileable; we
# round capacities up a geometric bucket ladder above this so the jit cache
# stays small (every distinct capacity is a distinct compiled-program
# signature — docs/compile_cache.md).
MIN_CAPACITY = 2048


class CapacityLadder:
    """The process-wide capacity-bucket policy.

    Every static row capacity in the engine (scan batches, join build
    tables, aggregate states, expansion outputs, shrink targets) rounds up
    through ONE ladder so unrelated queries land on the same compiled
    programs. The ladder is geometric — ``min_cap * ratio**k`` — or an
    explicit sorted bucket list extended geometrically past its top; the
    default (min 2048, ratio 2) is the engine's historical power-of-two
    rounding. Configure via ``ballista.tpu.capacity_buckets``
    ("<min>:<ratio>" or "b0,b1,b2,..."): a coarser ratio trades padding
    (bounded by the ratio) for a smaller compile vocabulary.
    """

    def __init__(self, min_cap: int = MIN_CAPACITY, ratio: int = 2,
                 explicit: tuple[int, ...] | None = None):
        if explicit:
            explicit = tuple(sorted(set(int(b) for b in explicit)))
            if explicit[0] < 8:
                raise ValueError(f"capacity bucket too small: {explicit[0]}")
            min_cap = explicit[0]
        if min_cap < 8:
            raise ValueError(f"min capacity too small: {min_cap}")
        if ratio < 2:
            raise ValueError(f"bucket ratio must be >= 2: {ratio}")
        self.min_cap = int(min_cap)
        self.ratio = int(ratio)
        self.explicit = explicit

    @classmethod
    def parse(cls, spec: str) -> "CapacityLadder":
        spec = (spec or "").strip()
        if not spec:
            return cls()
        if "," in spec:
            lad = cls(explicit=tuple(
                int(s) for s in spec.split(",") if s.strip()
            ))
        elif ":" in spec:
            mn, _, r = spec.partition(":")
            lad = cls(min_cap=int(mn), ratio=int(r))
        else:
            lad = cls(min_cap=int(spec))
        # configured ladders keep the engine-wide tileable floor the old
        # pow2 rounding enforced unconditionally (the raw constructor
        # stays relaxed for targeted tests)
        if lad.min_cap < MIN_CAPACITY:
            raise ValueError(
                f"capacity bucket below the {MIN_CAPACITY} tileable "
                f"minimum: {lad.min_cap}"
            )
        return lad

    def spec(self) -> str:
        if self.explicit:
            return ",".join(str(b) for b in self.explicit)
        return f"{self.min_cap}:{self.ratio}"

    def round(self, n: int) -> int:
        """Smallest ladder bucket >= n (geometric past any explicit top)."""
        if self.explicit:
            for b in self.explicit:
                if n <= b:
                    return b
            cap = self.explicit[-1]
        else:
            cap = self.min_cap
        while cap < n:
            cap *= self.ratio
        return cap

    def buckets_upto(self, n: int) -> tuple[int, ...]:
        """Every ladder bucket <= round(n) — the prewarm enumeration."""
        top = self.round(max(n, self.min_cap))
        out = list(b for b in (self.explicit or ()) if b <= top)
        cap = out[-1] if out else self.min_cap
        if not out:
            out.append(cap)
        while cap < top:
            cap *= self.ratio
            out.append(cap)
        return tuple(out)


_LADDER = CapacityLadder()
_LADDER_INSTALLED = False  # flips-after-install are logged (see below)


def set_capacity_buckets(spec: str) -> "CapacityLadder":
    """Install the process-wide bucket ladder (``TpuContext`` and the
    executor task entry apply ``ballista.tpu.capacity_buckets`` here).
    Process-global by design: capacities are compiled-program signatures,
    and two ladders in one process would double the vocabulary the whole
    subsystem exists to shrink. Mixed-capacity batches in flight across a
    change remain valid (capacity is carried per batch, never re-derived).
    """
    global _LADDER, _LADDER_INSTALLED
    ladder = CapacityLadder.parse(spec)
    if ladder.spec() != _LADDER.spec():
        if _LADDER_INSTALLED:
            # a mid-process flip is legal but costly: an executor serving
            # sessions with different ladders compiles BOTH vocabularies
            # and re-learns adaptive capacities across each swap
            import logging

            logging.getLogger(__name__).warning(
                "capacity ladder changed %s -> %s; mixed-ladder sessions "
                "on one executor grow the compile vocabulary",
                _LADDER.spec(), ladder.spec(),
            )
        _LADDER = ladder
        _LADDER_INSTALLED = True
    return _LADDER


def capacity_ladder() -> CapacityLadder:
    return _LADDER


def round_capacity(n: int) -> int:
    """Round a row count up to the bucketed static capacity."""
    return _LADDER.round(n)


class Dictionary:
    """Host-side dictionary for a STRING column: code i <-> values[i].
    Immutable, and sorted (``columnar/dict_util.py``: codes compare as
    their strings do).

    A dictionary is static aux data of every ``DeviceBatch`` that carries
    it, so each jit dispatch hashes it and compares it with the one the
    program was traced for. Both are O(1) for the same object: the hash is
    computed once (O(n)) and kept; two dictionaries are equal exactly when
    their values are, and only a comparison of two distinct objects with
    equal hashes walks them.

    What is computed from the entries alone lives and dies with the
    dictionary: its Arrow array and the tables of the string predicates
    evaluated over it (``dict_util.predicate_table``)."""

    __slots__ = ("values", "_hash", "_arrow", "_tables")

    def __init__(self, values: tuple[str, ...], arrow=None) -> None:
        """``arrow``: the same entries as an Arrow string array, where the
        caller made the values from one."""
        self.values = values
        self._hash: int | None = None
        self._arrow = arrow
        # predicate key -> what it gives for every entry; unbounded by
        # design: its keys are the literals of the queries this dictionary
        # met, and it goes with the dictionary
        self._tables: dict = {}

    def index_of(self, s: str) -> int:
        """The code of ``s``, or -1: a bisection, the entries being sorted."""
        i = bisect.bisect_left(self.values, s)
        if i < len(self.values) and self.values[i] == s:
            return i
        return -1

    def arrow(self):
        """The entries as an Arrow string array, made once."""
        if self._arrow is None:
            import pyarrow as pa

            self._arrow = pa.array(self.values, type=pa.string())
        return self._arrow

    def __len__(self) -> int:
        return len(self.values)

    def __hash__(self) -> int:
        if self._hash is None:
            self._hash = hash(self.values)
        return self._hash

    def __eq__(self, other) -> bool:
        if self is other:
            return True
        if not isinstance(other, Dictionary):
            return NotImplemented
        return hash(self) == hash(other) and self.values == other.values

    def __repr__(self) -> str:
        return f"Dictionary({len(self.values)} entries)"

    def __reduce__(self):
        return (Dictionary, (self.values,))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceBatch:
    """A statically-shaped columnar batch. Columns/valid/nulls are jnp arrays
    (pytree leaves); schema and dictionaries are static aux data."""

    schema: Schema
    columns: tuple[jnp.ndarray, ...]
    valid: jnp.ndarray  # bool[capacity]
    nulls: tuple[jnp.ndarray | None, ...]  # per-column True=null, or None
    dictionaries: Mapping[str, Dictionary]  # for STRING columns

    # -- pytree protocol (lets DeviceBatch flow through jit/shard_map) -------
    def tree_flatten(self):
        leaves = (self.columns, self.valid, self.nulls)
        aux = (self.schema, tuple(sorted(self.dictionaries.items())))
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        columns, valid, nulls = leaves
        schema, dict_items = aux
        return cls(schema, tuple(columns), valid, tuple(nulls), dict(dict_items))

    # -- construction --------------------------------------------------------
    @classmethod
    def from_host(
        cls,
        schema: Schema,
        arrays: Sequence[np.ndarray],
        num_rows: int | None = None,
        dictionaries: Mapping[str, Dictionary] | None = None,
        nulls: Sequence[np.ndarray | None] | None = None,
        capacity: int | None = None,
    ) -> "DeviceBatch":
        """Pad host arrays to a bucketed capacity and move them to device."""
        if len(arrays) != len(schema):
            raise SchemaError(
                f"{len(arrays)} arrays for {len(schema)} fields"
            )
        n = num_rows if num_rows is not None else (len(arrays[0]) if arrays else 0)
        cap = capacity if capacity is not None else round_capacity(n)
        if cap < n:
            raise InternalError(f"capacity {cap} < num_rows {n}")
        # the host-to-device funnel (obs.trace.phase "task.h2d"): pad on
        # the host, hand each padded array to the device
        from ballista_tpu.obs import trace as obs_trace

        with obs_trace.phase("task.h2d") as ph:
            cols = []
            for field, arr in zip(schema, arrays):
                want = field.dtype.to_np()
                a = np.asarray(arr)
                if a.dtype != want and not (
                    want == np.int64 and a.dtype == np.int32
                ):
                    # int32 is a permitted physical form of a logical
                    # INT64 column (see arrow_interop narrowing)
                    a = a.astype(want)
                padded = np.zeros(cap, dtype=a.dtype)
                padded[:n] = a[:n]
                ph.nbytes += padded.nbytes
                cols.append(jnp.asarray(padded))
            valid = np.zeros(cap, dtype=bool)
            valid[:n] = True
            null_cols: list[jnp.ndarray | None] = []
            for i in range(len(schema)):
                nm = None if nulls is None else nulls[i]
                if nm is None:
                    null_cols.append(None)
                else:
                    pm = np.zeros(cap, dtype=bool)
                    pm[:n] = np.asarray(nm, dtype=bool)[:n]
                    ph.nbytes += pm.nbytes
                    null_cols.append(jnp.asarray(pm))
            valid = jnp.asarray(valid)
            ph.nbytes += cap
        return cls(
            schema=schema,
            columns=tuple(cols),
            valid=valid,
            nulls=tuple(null_cols),
            dictionaries=dict(dictionaries or {}),
        )

    @classmethod
    def empty(cls, schema: Schema, capacity: int = MIN_CAPACITY) -> "DeviceBatch":
        # STRING fields carry an (empty) dictionary: string operators key
        # off the dictionary's presence, and a zero-row batch — e.g. an
        # empty shuffle partition flowing into a string filter — must look
        # like any other string column, not like a missing one
        from ballista_tpu.datatypes import DataType

        return cls.from_host(
            schema,
            [np.zeros(0, f.dtype.to_np()) for f in schema],
            0,
            dictionaries={
                f.name: Dictionary(())
                for f in schema
                if f.dtype == DataType.STRING
            },
            capacity=capacity,
        )

    # -- accessors -----------------------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.valid.shape[0])

    def column(self, name: str) -> jnp.ndarray:
        return self.columns[self.schema.index_of(name)]

    def null_mask(self, name: str) -> jnp.ndarray | None:
        return self.nulls[self.schema.index_of(name)]

    def count_valid(self) -> jnp.ndarray:
        """Number of live rows, as a device scalar."""
        return jnp.sum(self.valid.astype(jnp.int32))

    def num_rows(self, site: str = "num_rows") -> int:
        """Number of live rows, blocking on device (host-side use only);
        ``site`` names the caller for the ``task.d2h`` counters."""
        from ballista_tpu.ops.fetch import read_array

        return int(read_array(self.count_valid(), site))

    def with_columns(
        self,
        schema: Schema,
        columns: Sequence[jnp.ndarray],
        nulls: Sequence[jnp.ndarray | None] | None = None,
        dictionaries: Mapping[str, Dictionary] | None = None,
    ) -> "DeviceBatch":
        """Same rows/validity, different column set (projection output)."""
        return DeviceBatch(
            schema=schema,
            columns=tuple(columns),
            valid=self.valid,
            nulls=tuple(nulls) if nulls is not None else tuple([None] * len(schema)),
            dictionaries=dict(
                dictionaries if dictionaries is not None else self.dictionaries
            ),
        )

    def with_valid(self, valid: jnp.ndarray) -> "DeviceBatch":
        out = DeviceBatch(
            schema=self.schema,
            columns=self.columns,
            valid=valid,
            nulls=self.nulls,
            dictionaries=dict(self.dictionaries),
        )
        # masking can only REMOVE rows, so a key-uniqueness mark (see
        # HashAggregateExec's final-merge skip) survives it
        if getattr(self, "keys_unique", False):
            out.keys_unique = True
        return out

    def head(self, capacity: int) -> "DeviceBatch":
        """Slice every array down to the first ``capacity`` rows (a pure
        device slice — the caller must know live rows fit the prefix)."""
        if capacity >= self.capacity:
            return self
        return DeviceBatch(
            schema=self.schema,
            columns=tuple(c[:capacity] for c in self.columns),
            valid=self.valid[:capacity],
            nulls=tuple(
                None if m is None else m[:capacity] for m in self.nulls
            ),
            dictionaries=dict(self.dictionaries),
        )

    # -- host materialization ------------------------------------------------
    # Above this many bytes, fetching the full padded capacity costs more
    # than an extra round trip + a device-side compaction (the break-even
    # is not measured on the attached chip).
    _SLICED_FETCH_BYTES = 4 << 20

    def sliced_fetch(self) -> bool:
        """Whether a fetch of this batch is worth a count sync first: its
        padded columns, null masks and ``valid`` pass
        ``_SLICED_FETCH_BYTES``."""
        n_null = sum(1 for m in self.nulls if m is not None)
        width = sum(c.dtype.itemsize for c in self.columns) + 1 + n_null
        return width * self.capacity > self._SLICED_FETCH_BYTES

    def compacts_for(self, n: int) -> bool:
        """Whether ``n`` live rows are few enough to compact on the device
        and fetch a head of the batch (at most a quarter live)."""
        return n * 4 <= self.capacity

    @staticmethod
    def head_rows(n: int) -> int:
        """The head a compacted batch of ``n`` live rows is fetched as: the
        next power of two, at least 8."""
        m = 8
        while m < n:
            m <<= 1
        return m

    def fetch_host(
        self, site: str, extra: Sequence[jnp.ndarray] = ()
    ) -> tuple[list[np.ndarray], list[np.ndarray | None], list[np.ndarray]]:
        """Every column, every null mask and the ``extra`` arrays at full
        capacity, in ONE ``fetch_arrays`` round trip at ``site``. Returns
        (columns, null masks with None in place, extras)."""
        from ballista_tpu.ops.fetch import fetch_arrays

        present = [m for m in self.nulls if m is not None]
        fetched = fetch_arrays(
            [*extra, *self.columns, *present], site=site
        )
        k = len(extra)
        cols = fetched[k : k + len(self.columns)]
        it = iter(fetched[k + len(self.columns) :])
        nulls = [None if m is None else next(it) for m in self.nulls]
        return cols, nulls, fetched[:k]

    def to_host(
        self, site: str = "to_host"
    ) -> tuple[Schema, list[np.ndarray], list[np.ndarray | None]]:
        """Gather live rows back to host (compacts: drops invalid rows).

        Returns (schema, columns, null_masks) with exact row count.
        ``site`` names the caller for the ``task.d2h`` counters
        (ops/fetch.py); the count sync reads as ``<site>.count``.

        Two fetch strategies, chosen by padded size: small batches fetch
        the whole capacity in ONE batched device_get (a single host round
        trip); large sparse batches (e.g. a 262k-capacity aggregate state
        holding 6 groups) first sync the live count (tiny), compact on
        device, and fetch only a tight power-of-two slice — bytes moved
        scale with live rows, not capacity.
        """
        # Per-array fetches cost a full host round trip each; fetch_arrays
        # packs everything into one device buffer and moves it in a single
        # round trip. The sliced strategy adds one tiny count sync first.
        from ballista_tpu.ops.fetch import fetch_arrays

        b = self
        if self.sliced_fetch():
            # an operator that KNOWS a live-row ceiling host-side (e.g.
            # GlobalLimit's fetch) saves the count sync — one fewer
            # blocking round trip on the query's critical path. The
            # ceiling is only trusted when it is tight enough to earn the
            # compaction; a huge LIMIT falls back to the count sync
            # (fetching the full padded capacity on its say-so could cost
            # far more than the one round trip it saves).
            n = getattr(self, "host_rows_max", None)
            if n is None or not self.compacts_for(n):
                n = int(
                    fetch_arrays([self.count_valid()], site=f"{site}.count")[0]
                )
            if self.compacts_for(n):
                from ballista_tpu.ops.compact import compact

                b = compact(self).head(self.head_rows(n))
        cols_h, nulls_h, (valid,) = b.fetch_host(site, (b.valid,))
        idx = np.nonzero(valid)[0]
        cols = [c[idx] for c in cols_h]
        nulls = [None if m is None else m[idx] for m in nulls_h]
        return self.schema, cols, nulls

    def __repr__(self) -> str:
        return (
            f"DeviceBatch({self.schema!r}, capacity={self.capacity})"
        )
