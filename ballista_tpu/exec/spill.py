"""Grace-hash host spill: Arrow IPC bucket files under a disk budget.

When an operator's resident working set (a join build side, a final
aggregate's state set) would exceed ``ballista.tpu.hbm_budget_mb``, it
hash-splits rows into bucket files on host — the same Arrow IPC format and
routing rule the shuffle writer uses (executor/shuffle.py, ref
shuffle_writer.rs:142-292: the reference never holds a table, only
batches) — and re-processes the buckets sequentially through the same
kernels. This module owns the file lifecycle:

- one :class:`SpillManager` per task attempt (created lazily on the
  TaskContext, closed at the attempt boundary by run_with_capacity_retry),
  holding every spill set in one per-attempt directory;
- a directory under the executor's work_dir rides the shuffle TTL sweep
  (executor/cleanup.py) if the process dies before close; local-context
  spills live under a shared temp root that the same sweep can clean;
- total bytes written are accounted against ``ballista.tpu.spill_budget_mb``
  so a runaway spill fails the task instead of filling the disk.

Routing MUST agree with the shuffle tier — both call ops/partition.py, so a
string key hashes by VALUE (stable across per-batch dictionaries) and NULL
keys land in one bucket.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import uuid

import numpy as np
import pyarrow as pa
import pyarrow.ipc as paipc

from ballista_tpu.columnar.arrow_interop import batch_to_arrow
from ballista_tpu.columnar.batch import DeviceBatch
from ballista_tpu.errors import ExecutionError
from ballista_tpu.ops.fetch import read_array

# Shared temp root for spills of contexts without a work_dir; swept by
# executor.cleanup.clean_spill_data on executors, and removed per-attempt
# by SpillManager.close() in normal operation. Per-user (uid suffix) so
# two users on one host never contend over directory ownership — user A's
# 0755 root would make user B's makedirs fail, and neither's TTL sweep
# could delete the other's orphans.
SPILL_TMP_ROOT = os.path.join(
    tempfile.gettempdir(),
    f"ballista_tpu_spill-{getattr(os, 'getuid', lambda: 'u')()}",
)


def device_nbytes(batch: DeviceBatch) -> int:
    """Device bytes a batch pins: padded columns + validity + null masks
    (the quantity budgeted by ``ballista.tpu.hbm_budget_mb``)."""
    n = sum(c.size * c.dtype.itemsize for c in batch.columns)
    n += batch.valid.size
    n += sum(m.size for m in batch.nulls if m is not None)
    return n


class SpillManager:
    """All spill files of one task attempt, under one directory."""

    def __init__(self, base_dir: str | None, budget_bytes: int) -> None:
        from ballista_tpu.analysis import reswitness

        if base_dir is None:
            base_dir = SPILL_TMP_ROOT
        os.makedirs(base_dir, exist_ok=True)
        self.dir = os.path.join(base_dir, f"attempt-{uuid.uuid4().hex[:12]}")
        os.makedirs(self.dir, exist_ok=True)
        self.budget_bytes = budget_bytes
        self.total_bytes = 0
        self._sets: list[SpillSet] = []
        self._witness_token = reswitness.acquire("spill-manager", self.dir)

    def new_set(self, tag: str, buckets: int) -> "SpillSet":
        s = SpillSet(self, os.path.join(self.dir, tag), buckets)
        self._sets.append(s)
        return s

    def account(self, nbytes: int) -> None:
        self.total_bytes += nbytes
        if self.budget_bytes and self.total_bytes > self.budget_bytes:
            raise ExecutionError(
                "grace-hash spill exceeded ballista.tpu.spill_budget_mb "
                f"({self.total_bytes >> 20}MB written); raise the budget or "
                "run the query on more executors"
            )

    def close(self) -> None:
        from ballista_tpu.analysis import reswitness

        for s in self._sets:
            s.close()
        self._sets.clear()
        shutil.rmtree(self.dir, ignore_errors=True)
        reswitness.release(self._witness_token)
        self._witness_token = None


class SpillSet:
    """One grace pass's hash-bucket files: rows route to ``buckets`` Arrow
    IPC files by key hash; readers consume whole buckets (a bucket fits
    the HBM budget by construction of K)."""

    def __init__(self, manager: SpillManager, dir: str, buckets: int) -> None:
        self.manager = manager
        self.dir = dir
        self.buckets = buckets
        os.makedirs(dir, exist_ok=True)
        self._writers: dict[int, paipc.RecordBatchFileWriter] = {}
        self.bucket_bytes = [0] * buckets
        self.bucket_rows = [0] * buckets
        self._closed = False

    def _path(self, bucket: int) -> str:
        return os.path.join(self.dir, f"bucket-{bucket}.arrow")

    def write(self, bucket: int, rb: pa.RecordBatch) -> None:
        if rb.num_rows == 0:
            return
        w = self._writers.get(bucket)
        if w is None:
            w = paipc.new_file(self._path(bucket), rb.schema)
            self._writers[bucket] = w
        w.write_batch(rb)
        self.bucket_rows[bucket] += rb.num_rows
        self.bucket_bytes[bucket] += rb.nbytes
        self.manager.account(rb.nbytes)

    def write_split(self, batch: DeviceBatch, pids: np.ndarray) -> int:
        """Route one DeviceBatch's live rows to bucket files by their
        precomputed partition ids (aligned with batch capacity; invalid
        rows carry the drop id and are excluded by batch_to_arrow's
        live-row gather). Returns bytes written."""
        before = self.manager.total_bytes
        rb = batch_to_arrow(batch, site="spill.rows")
        if rb.num_rows:
            live = pids[read_array(batch.valid, "spill.valid")]
            # one stable argsort groups rows by bucket; searchsorted slices
            # give each bucket's contiguous index range — one pass over the
            # ids instead of a full `live == b` scan per occupied bucket
            # (64 scans/batch on the spill hot path otherwise)
            order = np.argsort(live, kind="stable")
            grouped = live[order]
            bounds = np.searchsorted(
                grouped, np.arange(self.buckets + 1)
            )
            for b in np.unique(grouped):
                s, e = bounds[b], bounds[b + 1]
                self.write(int(b), rb.take(pa.array(order[s:e])))
        return self.manager.total_bytes - before

    def finish_writes(self) -> None:
        """Seal every bucket file (IPC footers) so reads can begin."""
        for w in self._writers.values():
            w.close()
        self._writers.clear()

    def read(self, bucket: int) -> pa.Table | None:
        """One sealed bucket -> Arrow table (None when nothing spilled
        there)."""
        self.finish_writes()
        path = self._path(bucket)
        if not os.path.exists(path):
            return None
        with paipc.open_file(path) as r:
            return r.read_all()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.finish_writes()
        total = sum(self.bucket_bytes)
        if total:
            # tracing (docs/observability.md): one point event per spill
            # set, parented to the ambient task-attempt span — a no-op
            # (one thread-local read) when the session doesn't trace
            from ballista_tpu.obs import trace as obs_trace

            obs_trace.event(
                "spill_pass",
                attrs={
                    "buckets": self.buckets,
                    "bytes": total,
                    "rows": sum(self.bucket_rows),
                },
            )
        shutil.rmtree(self.dir, ignore_errors=True)


def spill_batch_by_keys(
    spill_set: SpillSet, batch: DeviceBatch, key_idxs: tuple
) -> int:
    """Hash-route one DeviceBatch's live rows into the set's bucket files
    (the shuffle writer's exact routing: ops/partition via the shared
    jitted program). Returns bytes written."""
    from ballista_tpu.exec.repartition import jit_partition_ids
    from ballista_tpu.ops.partition import string_key_tables

    tables = string_key_tables(batch, list(key_idxs))
    pids = read_array(
        jit_partition_ids(tuple(key_idxs), spill_set.buckets)(batch, tables),
        "spill.pids",
    )
    return spill_set.write_split(batch, pids)


def tables_string_dicts(tabs: list) -> dict:
    """One union Dictionary per STRING column across ``tabs``, for passing
    as ``fixed_dicts`` to per-chunk table_from_arrow conversions — every
    chunk of every table then encodes identical codes, so a consumer that
    unifies dictionaries (the grace join's probe loop) remaps at most once
    per pass instead of once per chunk."""
    import pyarrow.compute as pc

    from ballista_tpu.columnar.batch import Dictionary

    vals: dict[str, set] = {}
    for t in tabs:
        for name in t.schema.names:
            typ = t.schema.field(name).type
            if pa.types.is_dictionary(typ):
                typ = typ.value_type
            if not (pa.types.is_string(typ) or pa.types.is_large_string(typ)):
                continue
            uniq = pc.unique(t.column(name))
            if pa.types.is_dictionary(uniq.type):
                uniq = uniq.cast(uniq.type.value_type)
            vals.setdefault(name, set()).update(
                v for v in uniq.to_pylist() if v is not None
            )
    return {n: Dictionary(tuple(sorted(v))) for n, v in vals.items()}


def choose_passes(total_bytes: int, budget_bytes: int, max_k: int) -> int:
    """Number of grace passes K (a power of two, >= 2) such that one
    bucket's share of ``total_bytes`` fits comfortably inside the budget —
    half of it, leaving headroom for the kernels' own transients (sort
    scratch, probe gathers)."""
    k = 2
    while k < max_k and total_bytes > k * max(budget_bytes, 1) // 2:
        k <<= 1
    return k
