"""The executor's scan store (exec/scan.py ScanStore): file scans decoded
through an Executor's codec read and upload a file once, serve it again
from the device, never serve a file that has changed since, stay under
``ballista.tpu.scan_stream_mb`` of device bytes, and read a miss once
however many tasks want it. A codec without a store holds no table data.
"""

import os
import threading

import numpy as np
import pyarrow as pa
import pyarrow.csv as pacsv
import pyarrow.parquet as papq
import pytest

from ballista_tpu.avro import write_avro
from ballista_tpu.columnar.arrow_interop import (
    batch_to_arrow,
    schema_from_arrow,
)
from ballista_tpu.compilecache import metrics
from ballista_tpu.config import BallistaConfig
from ballista_tpu.exec import scan as scan_mod
from ballista_tpu.exec.base import TaskContext
from ballista_tpu.exec.scan import (
    AvroScanExec,
    CsvScanExec,
    ParquetScanExec,
    ScanStore,
)
from ballista_tpu.executor.executor import Executor
from ballista_tpu.serde import BallistaCodec

WATCHED = (
    "phase.task.scan_host.count", "phase.task.h2d.bytes",
    "scan_store.hits", "scan_store.misses", "scan_store.evictions",
    "scan_store.resident_bytes",
)


def table(n: int, seed: int = 0) -> pa.Table:
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": pa.array(np.arange(n, dtype=np.int64)),
        "v": pa.array(rng.uniform(0, 1, n)),
    })


def write(kind: str, t: pa.Table, path) -> None:
    if kind == "parquet":
        papq.write_table(t, path, row_group_size=max(1, t.num_rows // 2))
    elif kind == "csv":
        pacsv.write_csv(t, path)
    else:
        write_avro(str(path), t)


def scan_proto(kind: str, path, t: pa.Table, partitions: int = 1, **kw):
    """The scan as a scheduler would send it: encoded by a codec of its own."""
    cls = {"parquet": ParquetScanExec, "csv": CsvScanExec,
           "avro": AvroScanExec}[kind]
    scan = cls(str(path), schema_from_arrow(t.schema), partitions=partitions,
               **kw)
    scan.table_name = "t"
    return BallistaCodec().physical_to_proto(scan)


def held(store: ScanStore) -> int:
    return sum(e.nbytes for e in store._entries.values())


def run(scan, tctx, partition: int = 0) -> dict:
    """One partition's live rows as host columns, with what the run added
    to the watched counters."""
    before = metrics.snapshot()
    ks, vs = [], []
    for b in scan.execute(partition, tctx):
        live = np.asarray(b.valid)
        ks.append(np.asarray(b.column("k"))[live])
        vs.append(np.asarray(b.column("v"))[live])
    after = metrics.snapshot()
    return {
        "k": np.concatenate(ks), "v": np.concatenate(vs),
        "delta": {c: after.get(c, 0) - before.get(c, 0) for c in WATCHED},
    }


@pytest.fixture()
def executor(tmp_path):
    return Executor("scan-store-test", str(tmp_path / "work"))


@pytest.mark.parametrize("kind", ["parquet", "csv", "avro"])
def test_second_task_neither_reads_nor_uploads(executor, tmp_path, kind):
    t = table(20_000)
    path = tmp_path / f"t.{kind}"
    write(kind, t, path)
    node = scan_proto(kind, path, t)
    tctx = TaskContext()
    # a fresh plan per task, as the executor decodes them
    first = run(executor.codec.physical_from_proto(node), tctx)
    second = run(executor.codec.physical_from_proto(node), tctx)
    assert first["delta"]["scan_store.misses"] == 1
    assert first["delta"]["phase.task.scan_host.count"] >= 1
    assert first["delta"]["phase.task.h2d.bytes"] > 0
    assert first["delta"]["scan_store.resident_bytes"] > 0
    assert second["delta"] == dict.fromkeys(WATCHED, 0) | {
        "scan_store.hits": 1}
    for col in ("k", "v"):
        np.testing.assert_array_equal(first[col], t[col].to_numpy())
        np.testing.assert_array_equal(second[col], first[col])


def test_rewritten_file_is_read_again(executor, tmp_path):
    path = tmp_path / "t.parquet"
    old, new = table(20_000, seed=1), table(12_345, seed=2)
    write("parquet", old, path)
    tctx = TaskContext()
    node = scan_proto("parquet", path, old)
    first = run(executor.codec.physical_from_proto(node), tctx)
    np.testing.assert_array_equal(first["v"], old["v"].to_numpy())
    write("parquet", new, path)
    second = run(executor.codec.physical_from_proto(node), tctx)
    np.testing.assert_array_equal(second["k"], new["k"].to_numpy())
    np.testing.assert_array_equal(second["v"], new["v"].to_numpy())
    assert second["delta"]["scan_store.misses"] == 1
    assert second["delta"]["scan_store.hits"] == 0
    # the old file's batches left with it: what is resident is the new file
    third = run(executor.codec.physical_from_proto(node), tctx)
    np.testing.assert_array_equal(third["v"], new["v"].to_numpy())
    assert third["delta"]["scan_store.hits"] == 1
    now = held(executor.codec.scan_store)
    assert now == (first["delta"]["scan_store.resident_bytes"]
                   + second["delta"]["scan_store.resident_bytes"])
    assert 0 < now < first["delta"]["scan_store.resident_bytes"]


def test_file_replaced_under_a_running_scan_is_not_parked(
    executor, tmp_path, monkeypatch
):
    """A scan that opened the old file and finds another under the path
    when it has read (a task that waited out another's flight while the
    file was replaced by rename) serves what it opened and parks nothing:
    old rows under the new file's stamp would be hits for good."""
    path = tmp_path / "t.parquet"
    old, new = table(20_000, seed=1), table(20_000, seed=2)
    write("parquet", old, path)
    node = scan_proto("parquet", path, old)
    tctx = TaskContext()
    real = papq.ParquetFile

    def opened_then_replaced(p):
        f = real(p)
        monkeypatch.setattr(scan_mod.papq, "ParquetFile", real)
        write("parquet", new, tmp_path / "next.parquet")
        os.replace(tmp_path / "next.parquet", p)
        return f

    monkeypatch.setattr(scan_mod.papq, "ParquetFile", opened_then_replaced)
    first = run(executor.codec.physical_from_proto(node), tctx)
    np.testing.assert_array_equal(first["v"], old["v"].to_numpy())
    assert first["delta"]["scan_store.misses"] == 1
    assert first["delta"]["scan_store.resident_bytes"] == 0
    assert not executor.codec.scan_store._entries
    second = run(executor.codec.physical_from_proto(node), tctx)
    np.testing.assert_array_equal(second["v"], new["v"].to_numpy())
    assert second["delta"]["scan_store.misses"] == 1
    third = run(executor.codec.physical_from_proto(node), tctx)
    np.testing.assert_array_equal(third["v"], new["v"].to_numpy())
    assert third["delta"]["scan_store.hits"] == 1


@pytest.mark.parametrize("other", ["header", "delimiter", "schema"])
def test_one_csv_parsed_two_ways_is_two_entries(executor, tmp_path, other):
    """What a CSV parses to depends on the plan's schema, header flag and
    delimiter, not on the file alone: the same path decoded with other
    settings (another session's registration, a registration corrected)
    is never served the first one's parse."""
    path = tmp_path / "t.csv"
    path.write_text("1|0.5\n2|1.5\n3|2.5\n")
    as_kv = pa.table({"k": pa.array([1, 2, 3]), "v": pa.array([.5, 1.5, 2.5])})
    first_kw = {"has_header": False, "delimiter": "|"}
    if other == "header":  # the first line taken for column names
        second_t, second_kw = as_kv.slice(1), {**first_kw, "has_header": True}
    elif other == "delimiter":  # one string column a line
        second_t = pa.table({"k": pa.array(["1|0.5", "2|1.5", "3|2.5"])})
        second_kw = {**first_kw, "delimiter": ","}
    else:  # the keys read as floats
        second_t = as_kv.set_column(0, "k", pa.array([1.0, 2.0, 3.0]))
        second_kw = first_kw
    tctx = TaskContext()

    def rows(t, kw):
        scan = executor.codec.physical_from_proto(
            scan_proto("csv", path, t, **kw))
        before = metrics.snapshot()
        got = [batch_to_arrow(b) for b in scan.execute(0, tctx)]
        after = metrics.snapshot()
        return pa.Table.from_batches(got), {
            c: after.get(c, 0) - before.get(c, 0) for c in WATCHED}

    for t, kw in ((as_kv, first_kw), (second_t, second_kw)):
        got, delta = rows(t, kw)
        assert delta["scan_store.misses"] == 1
        if other == "header":  # pyarrow names the columns from line one
            got = got.rename_columns(t.column_names)
        assert got.equals(t), (got, t)
    for t, kw in ((as_kv, first_kw), (second_t, second_kw)):
        got, delta = rows(t, kw)
        assert delta["scan_store.hits"] == 1 and not delta["scan_store.misses"]
        assert got.num_rows == t.num_rows
    assert len(executor.codec.scan_store._entries) == 2


def test_bound_evicts_the_least_recently_served(executor, tmp_path):
    # 40000 rows of int64 + float64: 640 KB of parquet columns (under the
    # 1 MB at which a scan streams); on the device five 8192-row batches
    # of 13 bytes a row (the keys fit int32): one file fits under the
    # bound, two do not
    cfg = (BallistaConfig()
           .with_setting("ballista.tpu.scan_stream_mb", "1")
           .with_setting("ballista.tpu.batch_rows", "8192"))
    tctx = TaskContext(config=cfg)
    bound = 1 << 20
    tables = {n: table(40_000, seed=i) for i, n in enumerate("ab")}
    nodes = {}
    for name, t in tables.items():
        write("parquet", t, tmp_path / f"{name}.parquet")
        nodes[name] = scan_proto("parquet", tmp_path / f"{name}.parquet", t)
    store = executor.codec.scan_store

    before = metrics.snapshot()
    a_batches = executor.codec.physical_from_proto(nodes["a"]).execute(0, tctx)
    first_of_a = next(a_batches)  # a task part-way through a's batches
    assert 0 < held(store) <= bound
    assert [name[0] for name in store._entries] == [
        str(tmp_path / "a.parquet")]
    b = run(executor.codec.physical_from_proto(nodes["b"]), tctx)
    assert b["delta"]["scan_store.evictions"] == 1
    assert 0 < held(store) <= bound
    # nothing stays of a path whose last entry went
    assert [name[0] for name in store._entries] == [
        str(tmp_path / "b.parquet")]
    after = metrics.snapshot()
    assert (after["scan_store.resident_bytes"]
            - before.get("scan_store.resident_bytes", 0)
            == held(store))
    # the evicted batches are still the task's own
    rest = [first_of_a, *a_batches]
    assert len(rest) == 5
    got = np.concatenate(
        [np.asarray(x.column("v"))[np.asarray(x.valid)] for x in rest])
    np.testing.assert_array_equal(got, tables["a"]["v"].to_numpy())
    # and a is read again when it is next wanted
    again = run(executor.codec.physical_from_proto(nodes["a"]), tctx)
    assert again["delta"]["scan_store.misses"] == 1
    assert again["delta"]["scan_store.evictions"] == 1
    np.testing.assert_array_equal(again["v"], tables["a"]["v"].to_numpy())


def test_four_tasks_one_read(executor, tmp_path):
    t = table(20_000)
    path = tmp_path / "t.parquet"
    write("parquet", t, path)
    scan = executor.codec.physical_from_proto(scan_proto("parquet", path, t))
    tctx = TaskContext()
    gate = threading.Barrier(4)
    out: list = []

    def task():
        gate.wait(timeout=60)
        out.append(run(scan, tctx))

    before = metrics.snapshot()
    threads = [threading.Thread(target=task) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert not any(th.is_alive() for th in threads) and len(out) == 4
    after = metrics.snapshot()
    assert after["scan_store.misses"] - before.get("scan_store.misses", 0) == 1
    assert after["scan_store.hits"] - before.get("scan_store.hits", 0) == 3
    assert (after["phase.task.scan_host.count"]
            - before.get("phase.task.scan_host.count", 0)) == 2  # read, to numpy
    for r in out:
        np.testing.assert_array_equal(r["v"], t["v"].to_numpy())
    assert executor.codec.scan_store._flights == {}


@pytest.mark.parametrize("kind", ["parquet", "csv", "avro"])
def test_codec_without_a_store_decodes_scans_without_a_cache(tmp_path, kind):
    from ballista_tpu.exec.context import TpuContext
    from ballista_tpu.scheduler.server import SchedulerServer

    t = table(10)
    node = scan_proto(kind, tmp_path / f"t.{kind}", t)
    assert BallistaCodec().physical_from_proto(node).scan_cache is None
    scheduler = SchedulerServer(TpuContext())
    try:
        assert scheduler.codec.scan_store is None
        assert scheduler.codec.physical_from_proto(node).scan_cache is None
    finally:
        scheduler.shutdown()


@pytest.mark.parametrize("kind", ["parquet", "csv", "avro"])
def test_local_context_scans_through_a_store_of_its_own(tmp_path, kind):
    """TpuContext.scan of a file registration (what a TableScan without a
    ``source`` plans to) draws from the context's own store."""
    from ballista_tpu.exec.context import TpuContext

    t = table(20_000)
    path = tmp_path / f"t.{kind}"
    write(kind, t, path)
    ctx = TpuContext()
    getattr(ctx, f"register_{kind}")("t", str(path))
    tctx = TaskContext()
    first = run(ctx.scan("t", None, 1), tctx)
    second = run(ctx.scan("t", None, 1), tctx)
    assert first["delta"]["scan_store.misses"] == 1
    assert first["delta"]["scan_store.resident_bytes"] == held(ctx._scans) > 0
    assert second["delta"] == dict.fromkeys(WATCHED, 0) | {
        "scan_store.hits": 1}
    for got in (first, second):
        np.testing.assert_array_equal(got["v"], t["v"].to_numpy())


def test_standalone_q6_over_parquet_stays_on_the_device(tmp_path):
    from ballista_tpu import tpch
    from ballista_tpu.client.context import BallistaContext

    ctx = BallistaContext.standalone()
    try:
        for name, t in tpch.gen_all(0.01, 7).items():
            papq.write_table(t, tmp_path / f"{name}.parquet")
            ctx.register_parquet(name, str(tmp_path / f"{name}.parquet"))
        sql = open("benchmarks/queries/q6.sql").read()
        answers, deltas = [], []
        for _ in range(2):
            before = metrics.snapshot()
            answers.append(ctx.sql(sql).collect())
            after = metrics.snapshot()
            deltas.append({c: after.get(c, 0) - before.get(c, 0)
                           for c in WATCHED})
        cluster = ctx._standalone_cluster
        assert cluster.scheduler.codec.scan_store is None
        assert isinstance(cluster.executor.codec.scan_store, ScanStore)
    finally:
        ctx.close()
    assert deltas[0]["phase.task.h2d.bytes"] > 1e6
    assert deltas[0]["scan_store.misses"] >= 1
    assert deltas[1]["phase.task.h2d.bytes"] < 1e6
    assert deltas[1]["scan_store.misses"] == 0
    assert deltas[1]["scan_store.hits"] == deltas[0]["scan_store.misses"]
    assert answers[0].equals(answers[1]) and answers[0].num_rows == 1
