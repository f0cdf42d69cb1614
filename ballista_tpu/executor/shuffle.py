"""ShuffleWriterExec: stage-root operator materializing shuffle output.

ref ballista/rust/core/src/execution_plans/shuffle_writer.rs:65-431. For
each input partition it executes the child fragment, hash-partitions rows
on DEVICE (ops/partition.py — the reference's BatchPartitioner runs on CPU,
:209-256), brings each batch to host in bucket order (``split_batch``),
and appends each bucket to one Arrow IPC file per output partition:

    <work_dir>/<job_id>/<stage_id>/<output_partition>/data-<input_partition>.arrow

With no partition keys the stage writes a single output partition (the
coalesce boundary, ref planner.rs:62-78). Returns per-file metadata
(path + row/batch/byte stats) that flows back in CompletedTask statuses.

Data-plane perf (docs/shuffle.md):

- **Batch coalescing** — post-partition slices are ``batch_bytes /
  fan_out`` small; every appender concatenates them up to
  ``ballista.tpu.shuffle_target_batch_mb`` before write/stream so the
  wire and the reader pay per-batch fixed costs once per target-size
  batch, not once per sliver.
- **Push shuffle** (``ballista.tpu.push_shuffle``, eager jobs on a
  scheduler-connected executor): output partitions commit into the
  in-memory push registry (executor/push.py) instead of files — zero
  disk I/O while consumers keep up; window overflow spills to the very
  path the meta advertises, so consumers transparently fall back to the
  pull plane.
"""

from __future__ import annotations

import os
from typing import Iterator

import numpy as np
import pyarrow as pa
import pyarrow.ipc as paipc

from ballista_tpu.columnar.arrow_interop import batch_to_arrow, host_to_arrow
from ballista_tpu.columnar.batch import DeviceBatch
from ballista_tpu.columnar.coalesce import BatchCoalescer
from ballista_tpu.compilecache import metrics as compile_metrics
from ballista_tpu.datatypes import Schema
from ballista_tpu.errors import ExecutionError
from ballista_tpu.exec.base import (
    ExecutionPlan,
    HashPartitioning,
    TaskContext,
    UnknownPartitioning,
)
from ballista_tpu.exec.repartition import jit_bucket_counts, jit_partition_ids
from ballista_tpu.expr import logical as L
from ballista_tpu.obs import trace as obs_trace
from ballista_tpu.ops.compact import compact
from ballista_tpu.ops.fetch import read_array
from ballista_tpu.ops.partition import string_key_tables
from ballista_tpu.scheduler_types import ShuffleWritePartitionMeta


def resolve_file_codec(codec: str) -> str:
    """The codec shuffle FILES are written with. ``auto`` resolves to
    ``none``: the wire codec is negotiated per (producer, consumer) link
    at fetch time (reader.py), so compressing the at-rest bytes would
    only tax colocated readers' zero-copy mmap path."""
    return "none" if codec == "auto" else codec


def bucket_order(
    pids: np.ndarray, num_partitions: int, live: int
) -> np.ndarray:
    """The indices of the ``live`` live rows grouped by bucket, in input
    order within a bucket. ``pids``: a partition id a row,
    ``num_partitions`` for a dead one, so the dead rows sort last and are
    cut off.

    One O(n) pass: numpy's stable argsort of an 8- or 16-bit key is a
    radix sort, so the ids narrow to the smallest type that holds the drop
    bucket first."""
    if num_partitions < 1 << 8:
        pids = pids.astype(np.uint8)
    elif num_partitions < 1 << 16:
        pids = pids.astype(np.uint16)
    return np.argsort(pids, kind="stable")[:live]


def split_batch(
    batch: DeviceBatch, pids, num_partitions: int, site: str
) -> tuple[pa.RecordBatch, np.ndarray] | None:
    """One device batch's live rows among ``num_partitions`` output
    partitions: (the rows in bucket order as one Arrow batch, the
    ``num_partitions + 1`` bounds of the buckets in it), or None when no
    row is live. Within a bucket the rows keep their input order.

    ``pids``: the batch's partition ids on the device, ``num_partitions``
    for a dead row (``jit_partition_ids``); the device counts each bucket's
    rows. The fetch follows ``DeviceBatch.to_host``'s rule. A batch fetched
    whole is one read at ``site`` of its ids, counts, columns and null
    masks; the host orders the ids (``bucket_order``) and gathers each
    column once. A large batch reads its counts first (``<site>.count``);
    at most a quarter live, the device's compaction sorts its rows by id,
    and the host reads the head of that and only slices it. Dictionaries
    are decoded after the gather, and a null slot holds what a ``take``
    leaves there: the bytes of the split by a host sort and ``take`` that
    this replaced."""
    compile_metrics.add("shuffle.split_batches")
    counts = jit_bucket_counts(num_partitions)(pids)
    if batch.sliced_fetch():
        bounds = _bucket_bounds(read_array(counts, f"{site}.count"))
        n = int(bounds[-1])
        if n == 0:
            return None
        if batch.compacts_for(n):
            compile_metrics.add("shuffle.split_device_ordered")
            head = compact(batch, key=pids).head(batch.head_rows(n))
            cols, nulls, _ = head.fetch_host(site)
            rb = host_to_arrow(
                batch.schema,
                [c[:n] for c in cols],
                [None if m is None else m[:n] for m in nulls],
                batch.dictionaries,
                take_layout=True,
            )
            return rb, bounds
    cols, nulls, (ids, counts) = batch.fetch_host(site, (pids, counts))
    bounds = _bucket_bounds(counts)
    if not bounds[-1]:
        return None
    with obs_trace.phase("task.shuffle_write") as ph:
        order = bucket_order(ids, num_partitions, int(bounds[-1]))
        cols = [np.take(c, order) for c in cols]
        nulls = [None if m is None else np.take(m, order) for m in nulls]
        ph.nbytes = sum(c.nbytes for c in cols)
    rb = host_to_arrow(
        batch.schema, cols, nulls, batch.dictionaries, take_layout=True
    )
    return rb, bounds


def _bucket_bounds(counts: np.ndarray) -> np.ndarray:
    """``jit_bucket_counts``' counts, the drop bucket last, to the bounds of
    the live buckets in bucket order: one more than there are buckets."""
    bounds = np.zeros(len(counts), dtype=np.int64)
    np.cumsum(counts[:-1], out=bounds[1:])
    return bounds


class ShuffleWriterExec(ExecutionPlan):
    def __init__(
        self,
        job_id: str,
        stage_id: int,
        input: ExecutionPlan,
        partition_keys: list[L.Expr],
        output_partitions: int,
    ) -> None:
        super().__init__()
        self.job_id = job_id
        self.stage_id = stage_id
        self.input = input
        self.partition_keys = list(partition_keys)
        self.output_partitions = max(1, output_partitions)

    def schema(self) -> Schema:
        return self.input.schema()

    def children(self) -> list[ExecutionPlan]:
        return [self.input]

    def output_partitioning(self):
        if self.partition_keys:
            return HashPartitioning(
                tuple(self.partition_keys), self.output_partitions
            )
        return UnknownPartitioning(self.output_partitions)

    def describe(self) -> str:
        keys = [k.name() for k in self.partition_keys]
        return (
            f"ShuffleWriterExec: job={self.job_id}, stage={self.stage_id}, "
            f"keys={keys}, out={self.output_partitions}"
        )

    def _push_eligible(self, ctx: TaskContext) -> bool:
        """Push shuffle is an opportunistic fast path with hard
        prerequisites: the session opted in (default on), the job is
        EAGER (consumers learn locations task-by-task — barriered
        sessions bake locations at promotion and gain nothing from
        memory residency), the executor is scheduler-connected (the same
        requirement the eager reader has; direct/in-proc plan execution
        keeps the pull path), and the window is positive."""
        cfg = ctx.config
        return bool(
            cfg.push_shuffle()
            and cfg.eager_shuffle()
            and ctx.work_dir
            and ctx.shuffle_locations is not None
            and cfg.push_shuffle_window_mb() > 0
        )

    # -- the task entry point (ref shuffle_writer.rs:142-292) ----------------
    def execute_shuffle_write(
        self, input_partition: int, ctx: TaskContext
    ) -> list[ShuffleWritePartitionMeta]:
        if not ctx.work_dir:
            raise ExecutionError("shuffle write requires ctx.work_dir")
        schema = self.input.schema()
        key_idxs = tuple(
            L.resolve_field_index(schema, k.cname)
            if isinstance(k, L.Column)
            else self._key_error(k)
            for k in self.partition_keys
        )
        writers: dict[int, _Appender] = {}
        file_codec = resolve_file_codec(ctx.config.shuffle_compression())
        ipc_options = _ipc_write_options(file_codec)
        target_bytes = ctx.config.shuffle_target_batch_mb() << 20
        push = self._push_eligible(ctx)
        window_bytes = ctx.config.push_shuffle_window_mb() << 20

        def appender(out_part: int) -> "_Appender":
            w = writers.get(out_part)
            if w is None:
                d = os.path.join(
                    ctx.work_dir, self.job_id, str(self.stage_id),
                    str(out_part),
                )
                if push:
                    path = os.path.join(
                        d, f"push-{input_partition}.arrow"
                    )
                    w = _PushAppender(
                        path,
                        key=(
                            self.job_id, self.stage_id, input_partition,
                            out_part,
                        ),
                        owner=ctx.work_dir,
                        options=ipc_options,
                        window_bytes=window_bytes,
                        target_bytes=target_bytes,
                        metrics=self.metrics,
                    )
                else:
                    os.makedirs(d, exist_ok=True)
                    path = os.path.join(d, f"data-{input_partition}.arrow")
                    w = _IpcAppender(
                        path, options=ipc_options, target_bytes=target_bytes
                    )
                writers[out_part] = w
            return w

        try:
            with self.metrics.time("write_time"):
                for batch in self.input.execute(input_partition, ctx):
                    if not self.partition_keys or self.output_partitions == 1:
                        rb = batch_to_arrow(batch, site="shuffle_write.rows")
                        if rb.num_rows:
                            with obs_trace.phase(
                                "task.shuffle_write", nbytes=rb.nbytes
                            ):
                                appender(0).write(rb)
                        continue
                    with self.metrics.time("repart_time"):
                        tables = string_key_tables(batch, list(key_idxs))
                        pids = jit_partition_ids(
                            key_idxs, self.output_partitions
                        )(batch, tables)
                    split = split_batch(
                        batch, pids, self.output_partitions,
                        site="shuffle_write.rows",
                    )
                    if split is None:
                        continue
                    rb, bounds = split
                    with obs_trace.phase(
                        "task.shuffle_write", nbytes=rb.nbytes
                    ):
                        for out_part in range(self.output_partitions):
                            lo = int(bounds[out_part])
                            hi = int(bounds[out_part + 1])
                            if hi > lo:
                                appender(out_part).write(
                                    rb.slice(lo, hi - lo)
                                )
        except BaseException:
            # a failed ATTEMPT must leave nothing observable: push streams
            # are aborted (the registry key frees for the retry); partial
            # files keep the pre-existing contract (never published,
            # swept by TTL)
            for w in writers.values():
                w.discard()
            raise

        out = []
        for out_part, w in sorted(writers.items()):
            with obs_trace.phase("task.shuffle_write"):
                num_rows, num_batches, num_bytes, pushed = w.close()
            self.metrics.add("output_rows", num_rows)
            out.append(
                ShuffleWritePartitionMeta(
                    partition_id=out_part,
                    path=w.path,
                    num_batches=num_batches,
                    num_rows=num_rows,
                    num_bytes=num_bytes,
                    push=pushed,
                )
            )
        return out

    @staticmethod
    def _key_error(k):
        raise ExecutionError(
            f"shuffle partition key {k.name()!r} must be a column"
        )

    # In-process fallback: stream the child through (used when a stage plan
    # is executed without materialization, e.g. single-process mode).
    def execute(self, partition: int, ctx: TaskContext) -> Iterator[DeviceBatch]:
        yield from self.input.execute(partition, ctx)


def _ipc_write_options(codec: str) -> paipc.IpcWriteOptions | None:
    """Resolved codec -> IpcWriteOptions. Readers auto-detect per file
    (the codec rides the IPC message headers), so writers upgraded to a
    new default coexist with old files inside one consumed partition."""
    if codec in ("", "none"):
        return None
    try:
        return paipc.IpcWriteOptions(compression=codec)
    except Exception as e:  # noqa: BLE001 — codec missing from this build
        raise ExecutionError(
            f"shuffle compression codec {codec!r} unavailable in this "
            f"pyarrow build: {e}"
        ) from e


class _Appender:
    """Shared appender surface: ``write`` record batches in order,
    ``close`` -> (rows, batches, bytes, pushed), ``discard`` on attempt
    failure."""

    path: str

    def write(self, rb: pa.RecordBatch) -> None:  # pragma: no cover
        raise NotImplementedError

    def close(self) -> tuple[int, int, int, bool]:  # pragma: no cover
        raise NotImplementedError

    def discard(self) -> None:  # pragma: no cover
        raise NotImplementedError


class _IpcAppender(_Appender):
    """One Arrow IPC file being appended batch-by-batch (the reference's
    IPCWriter, shuffle_writer.rs:162-199), coalescing sub-target batches
    before they hit the file. A lifetime with zero writes closes clean:
    no file is created and the stats are (0, 0, 0)."""

    def __init__(
        self,
        path: str,
        options: paipc.IpcWriteOptions | None = None,
        target_bytes: int = 0,
    ):
        self.path = path
        self._options = options
        self._writer: paipc.RecordBatchFileWriter | None = None
        self._coalescer = BatchCoalescer(target_bytes)
        self.num_rows = 0
        self.num_batches = 0

    def write(self, rb: pa.RecordBatch) -> None:
        out = self._coalescer.add(rb)
        if out is not None:
            self._write_now(out)

    def _write_now(self, rb: pa.RecordBatch) -> None:
        if self._writer is None:
            if self._options is not None:
                self._writer = paipc.new_file(
                    self.path, rb.schema, options=self._options
                )
            else:
                self._writer = paipc.new_file(self.path, rb.schema)
        self._writer.write_batch(rb)
        self.num_rows += rb.num_rows
        self.num_batches += 1

    def close(self) -> tuple[int, int, int, bool]:
        tail = self._coalescer.flush()
        if tail is not None:
            self._write_now(tail)
        if self._writer is not None:
            self._writer.close()
        num_bytes = os.path.getsize(self.path) if os.path.exists(self.path) else 0
        return self.num_rows, self.num_batches, num_bytes, False

    def discard(self) -> None:
        if self._writer is not None:
            self._writer.close()
            self._writer = None


class _PushAppender(_Appender):
    """One output partition being committed into the push registry
    (docs/shuffle.md): coalesced batches append to an in-memory stream;
    the registry's window eviction may convert it to disk mid-write, and
    ``close`` seals it — push=True when it committed in memory. Spill
    bytes forced by this task's appends land in its own
    ``push_spill_bytes`` metric."""

    def __init__(self, path, key, owner, options, window_bytes,
                 target_bytes, metrics):
        from ballista_tpu.executor.push import REGISTRY

        self.path = path
        self._registry = REGISTRY
        # ownership lives in the registry from birth: seal() commits it
        # for consumers, abort()/drop_owner retire it — never this class
        self._stream = REGISTRY.open(  # lifelint: transfer=push-registry
            key, path, owner, options
        )
        self._window_bytes = window_bytes
        self._coalescer = BatchCoalescer(target_bytes)
        self._metrics = metrics

    def write(self, rb: pa.RecordBatch) -> None:
        out = self._coalescer.add(rb)
        if out is not None:
            self._append_now(out)

    def _append_now(self, rb: pa.RecordBatch) -> None:
        spilled = self._registry.append(
            self._stream, rb, self._window_bytes
        )
        if spilled:
            self._metrics.add("push_spill_bytes", spilled)

    def close(self) -> tuple[int, int, int, bool]:
        tail = self._coalescer.flush()
        if tail is not None:
            self._append_now(tail)
        num_rows, num_batches, num_bytes, pushed = self._registry.seal(
            self._stream
        )
        if pushed:
            self._metrics.add("pushed_bytes", num_bytes)
        return num_rows, num_batches, num_bytes, pushed

    def discard(self) -> None:
        self._registry.abort(self._stream)
