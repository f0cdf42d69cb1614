"""The hash exchange's split of a batch among its output partitions
(``executor/shuffle.py split_batch``).

Every output partition must carry the bytes the split by a host sort gave:
the batch's live rows gathered to the host, a stable argsort of their
partition ids, one ``take`` into bucket order, then a slice per bucket.
That reference is kept here. Cases: small batches fetched whole, a dense
batch over the sliced-fetch threshold, a sparse one that the device
compacts in bucket order; null masks, a string column with its
dictionary, int64 keys above 2^31; all-dead batches and empty buckets;
and one write through both the file and the push appender.
"""

from __future__ import annotations

import io
import os

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.ipc as paipc
import pytest

from ballista_tpu.columnar.arrow_interop import batch_to_arrow
from ballista_tpu.columnar.batch import DeviceBatch, Dictionary
from ballista_tpu.compilecache import metrics
from ballista_tpu.config import BallistaConfig
from ballista_tpu.datatypes import DataType, Field, Schema
from ballista_tpu.exec.base import ExecutionPlan, TaskContext, UnknownPartitioning
from ballista_tpu.exec.repartition import jit_partition_ids
from ballista_tpu.executor.push import REGISTRY, stream_key
from ballista_tpu.executor.shuffle import (
    ShuffleWriterExec,
    _IpcAppender,
    _ipc_write_options,
    bucket_order,
    resolve_file_codec,
    split_batch,
)
from ballista_tpu.expr import logical as L
from ballista_tpu.ops.partition import string_key_tables

SCHEMA = Schema([
    Field("k", DataType.INT64, True),
    Field("s", DataType.STRING, True),
    Field("v", DataType.FLOAT64, False),
])
KEYS = (0, 1)
WORDS = Dictionary(tuple(sorted(f"w{i:03d}" for i in range(50))))
SMALL, LARGE = 4096, 1 << 18  # LARGE: 23 bytes a row, past 4 MB

# shape -> (capacity, live share, distinct keys or None for all)
SHAPES = {
    "small": (SMALL, 0.6, None),
    "small_few_keys": (SMALL, 0.9, 2),
    "small_all_dead": (SMALL, 0.0, None),
    "dense": (LARGE, 0.7, None),
    "sparse": (LARGE, 0.1, None),
    "sparse_all_dead": (LARGE, 0.0, None),
}


def make_batch(shape: str, seed: int) -> DeviceBatch:
    cap, live, distinct = SHAPES[shape]
    rng = np.random.default_rng(seed)
    k = rng.integers(-(2**40), 2**40, cap, dtype=np.int64)
    s = rng.integers(0, len(WORDS), cap).astype(np.int32)
    v = rng.standard_normal(cap)
    nulls = [rng.random(cap) < 0.1, rng.random(cap) < 0.1, None]
    if distinct is not None:
        # a few (k, s) pairs and no nulls: most buckets stay empty
        pick = rng.integers(0, distinct, cap)
        k, s, nulls = k[pick], s[pick], [None] * 3
    assert np.abs(k).max() >= 2**31
    b = DeviceBatch.from_host(
        SCHEMA, [k, s, v], num_rows=cap, nulls=nulls,
        dictionaries={"s": WORDS}, capacity=cap,
    )
    return b.with_valid(jnp.asarray(rng.random(cap) < live))


def pids_of(batch: DeviceBatch, p: int):
    tables = string_key_tables(batch, list(KEYS))
    return jit_partition_ids(KEYS, p)(batch, tables)


def reference_split(batch: DeviceBatch, pids, p: int) -> dict:
    """The split by a host sort: {output partition: its slice}."""
    pids = np.asarray(pids)
    rb = batch_to_arrow(batch)
    live = pids[np.asarray(batch.valid)]
    order = np.argsort(live, kind="stable")
    sorted_rb = rb.take(pa.array(order))
    bounds = np.searchsorted(live[order], np.arange(p + 1))
    return {
        q: sorted_rb.slice(lo, hi - lo)
        for q, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:]))
        if hi > lo
    }


def split_of(batch: DeviceBatch, pids, p: int) -> dict:
    out = split_batch(batch, pids, p, site="t.rows")
    if out is None:
        return {}
    rb, bounds = out
    return {
        q: rb.slice(lo, hi - lo)
        for q, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:]))
        if hi > lo
    }


def ipc_bytes(rb: pa.RecordBatch) -> bytes:
    sink = io.BytesIO()
    with paipc.new_stream(sink, rb.schema) as w:
        w.write_batch(rb)
    return sink.getvalue()


class BatchesExec(ExecutionPlan):
    """A leaf that yields the given device batches."""

    def __init__(self, batches: list[DeviceBatch]) -> None:
        super().__init__()
        self.batches = batches

    def schema(self) -> Schema:
        return SCHEMA

    def children(self) -> list[ExecutionPlan]:
        return []

    def output_partitioning(self):
        return UnknownPartitioning(1)

    def execute(self, partition, ctx):
        yield from self.batches


def reference_files(batches, p: int, cfg, out_dir) -> dict:
    """What the writer made of ``batches`` with the split by a host sort:
    {output partition: file bytes}."""
    options = _ipc_write_options(resolve_file_codec(cfg.shuffle_compression()))
    target = cfg.shuffle_target_batch_mb() << 20
    writers: dict[int, _IpcAppender] = {}
    for b in batches:
        parts = (
            {0: batch_to_arrow(b)} if p == 1
            else reference_split(b, pids_of(b, p), p)
        )
        for q, rb in parts.items():
            if rb.num_rows:
                if q not in writers:
                    writers[q] = _IpcAppender(
                        str(out_dir / f"ref-{q}.arrow"), options, target
                    )
                writers[q].write(rb)
    out = {}
    for q, w in writers.items():
        w.close()
        with open(w.path, "rb") as f:
            out[q] = f.read()
    return out


@pytest.mark.parametrize("p", [1, 2, 7])
@pytest.mark.parametrize("shape", [*SHAPES, "writer"])
def test_split_matches_the_host_sort_byte_for_byte(shape, p, tmp_path):
    if shape != "writer":
        batch = make_batch(shape, seed=p)
        pids = pids_of(batch, p)
        want = reference_split(batch, pids, p)
        got = split_of(batch, pids, p)
        assert got.keys() == want.keys()
        if SHAPES[shape][1] == 0.0:
            assert not got
        if SHAPES[shape][2] is not None:
            assert len(got) <= SHAPES[shape][2]
        for q, rb in got.items():
            assert rb.nbytes == want[q].nbytes, q
            assert ipc_bytes(rb) == ipc_bytes(want[q]), q
        return
    # one writer over a batch of every shape, through the file appender
    # and then the push appender
    batches = [make_batch(s, seed=i) for i, s in enumerate(SHAPES)]
    cfg = BallistaConfig()
    want = reference_files(batches, p, cfg, tmp_path)
    assert want

    def writer():
        keys = [L.col("k"), L.col("s")]
        return ShuffleWriterExec("js", 1, BatchesExec(batches), keys, p)

    pull_metas = writer().execute_shuffle_write(
        0, TaskContext(config=cfg, work_dir=str(tmp_path / "pull"))
    )
    assert {m.partition_id for m in pull_metas} == set(want)
    for m in pull_metas:
        assert not m.push
        with open(m.path, "rb") as f:
            assert f.read() == want[m.partition_id], m.partition_id

    push_dir = str(tmp_path / "push")
    ctx = TaskContext(
        config=cfg, work_dir=push_dir, shuffle_locations=lambda *a: None
    )
    push_metas = writer().execute_shuffle_write(0, ctx)
    try:
        assert {m.partition_id for m in push_metas} == set(want)
        for m in push_metas:
            assert m.push and not os.path.exists(m.path)
            got = REGISTRY.take_batches(stream_key("js", 1, 0, m.partition_id))
            with paipc.open_file(pa.BufferReader(want[m.partition_id])) as r:
                expect = [r.get_batch(i) for i in range(r.num_record_batches)]
            assert [ipc_bytes(b) for b in got] == [ipc_bytes(b) for b in expect]
    finally:
        REGISTRY.drop_owner(push_dir)


@pytest.mark.parametrize("p", [2, 300, 70000])
def test_bucket_order_is_the_stable_order_of_the_live_ids(p):
    """uint8, uint16 and int32 keys: the live rows by id, input order
    within an id, the dead rows (id ``p``) cut off."""
    rng = np.random.default_rng(p)
    pids = rng.integers(0, p + 1, 5000).astype(np.int32)
    live = int((pids < p).sum())
    got = bucket_order(pids, p, live)
    want = np.argsort(pids, kind="stable")[:live]
    assert got.tolist() == want.tolist()


@pytest.mark.parametrize(
    "shape, reads, device_ordered",
    [("small", 1, 0), ("dense", 2, 0), ("sparse", 2, 1)],
)
def test_one_hash_written_batch_reads_once_or_twice(
    shape, reads, device_ordered, tmp_path
):
    """A batch fetched whole is one read; one past the sliced-fetch
    threshold reads its bucket counts first, and at most a quarter live
    the device puts it in bucket order."""
    batch = make_batch(shape, seed=11)
    writer = ShuffleWriterExec(
        "jr", 1, BatchesExec([batch]), [L.col("k"), L.col("s")], 2
    )
    ctx = TaskContext(config=BallistaConfig(), work_dir=str(tmp_path))
    writer.execute_shuffle_write(0, ctx)  # compiles outside the count
    with metrics.delta() as d:
        writer.execute_shuffle_write(0, ctx)
    assert d.value["phase.task.d2h.count"] == reads
    assert d.value["phase.task.d2h.count:shuffle_write.rows"] == 1
    assert d.value["shuffle.split_batches"] == 1
    assert d.value.get("shuffle.split_device_ordered", 0) == device_ordered
    assert metrics.snapshot()["shuffle.split_device_ordered"] >= device_ordered
