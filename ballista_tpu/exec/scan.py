"""Scan operators: host IO (pyarrow = Arrow C++) feeding DeviceBatches.

The reference scans via DataFusion's ListingTable (CSV/Parquet/Avro
providers, serialized in ballista.proto:60-92). Here scans decode on host
with pyarrow and stage columns onto the device; string columns are
dictionary-encoded table-wide at scan time so every batch of a scan shares
dictionaries (SURVEY.md §7 "Strings/dictionaries on TPU").

Pushed-down filters are evaluated per row group / per chunk on host Arrow
data where cheap (parquet row-group pruning by min/max stats), then
re-evaluated exactly on device — pruning is an optimization, never a
correctness dependence.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import weakref
from typing import Callable, Iterator

import pyarrow as pa
import pyarrow.csv as pacsv
import pyarrow.parquet as papq

from ballista_tpu.columnar.arrow_interop import (
    narrowable_int64_cols,
    schema_to_arrow,
    table_from_arrow,
)
from ballista_tpu.columnar.batch import DeviceBatch
from ballista_tpu.compilecache import metrics
from ballista_tpu.datatypes import DataType, Schema
from ballista_tpu.exec.base import (
    ExecutionPlan,
    TaskContext,
    UnknownPartitioning,
)
from ballista_tpu.exec.spill import device_nbytes
from ballista_tpu.obs import trace as obs_trace


class MemoryScanExec(ExecutionPlan):
    """Scan of an in-memory Arrow table, split into N partitions (the
    DataFusion MemoryExec the reference's shuffle tests build on,
    shuffle_writer.rs:489-520)."""

    def __init__(
        self,
        table: pa.Table | Callable[[], tuple[pa.Table, frozenset | None]],
        out_schema: Schema,
        projection: list[str] | None = None,
        partitions: int = 1,
        batch_rows: int | None = None,
        device_cache: dict | None = None,
    ) -> None:
        """``table``: the Arrow table, or a file scan's read of it: a
        callable taking nothing and returning the table and its
        ``narrow_cols`` (None: decide from the table), called only for a
        partition that is not in ``device_cache``.

        ``device_cache``: an (optionally shared, table-lifetime) dict the
        scan parks its uploaded DeviceBatches in. Re-reading and
        re-uploading is what a warm scan costs without it: over parquet
        an executor with no such cache uploaded 261 MB a query against 43
        MB with the columns resident, at 0.82 queries/s against 1.13
        (ledger, PR 25: tpch-sf1-daemons.power, tpch-sf1-mem.power). A
        registered table's columns are immutable, and DeviceBatches are
        functional (operators mask/copy, never mutate), so re-serving the
        resident arrays is safe. The context passes its per-table cache so
        repeated queries skip the upload entirely (device data residency —
        the TPU-idiomatic replacement for the reference's OS page cache)."""
        super().__init__()
        self.table = table
        self.projection = projection
        self._schema = (
            out_schema.select(projection) if projection else out_schema
        )
        self.partitions = max(1, partitions)
        self.batch_rows = batch_rows
        self.device_cache = device_cache
        # INT64 columns to store as physical int32 (None = decide from the
        # table on first execute; see arrow_interop.narrowable_int64_cols)
        self.narrow_cols: frozenset | None = None

    def schema(self) -> Schema:
        return self._schema

    def output_partitioning(self):
        return UnknownPartitioning(self.partitions)

    def describe(self) -> str:
        cols = self.projection if self.projection else "*"
        return f"MemoryScanExec: cols={cols}, partitions={self.partitions}"

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[DeviceBatch]:
        for b in self.batches(partition, ctx):
            # device scalar — resolved lazily at metrics report time (an
            # int() here would cost a host sync per batch)
            self.metrics.add("output_rows", b.count_valid())
            yield b

    def batches(self, partition: int, ctx: TaskContext) -> list[DeviceBatch]:
        """``partition``'s batches, whole: the device cache's, or uploaded
        now and parked there."""
        # resolved per task so ballista.tpu.batch_rows travels with the
        # session config across process boundaries (decoded stage plans
        # carry no batch_rows; the config does)
        batch_rows = self.batch_rows or ctx.config.tpu_batch_rows()
        key = (
            tuple(self.projection or ()), self.partitions, batch_rows,
            partition,
        )
        if self.device_cache is not None:
            cached = self.device_cache.get(key)
            if cached is not None:
                return cached
        if callable(self.table):
            t, narrow = self.table()
        else:
            t, narrow = self.table, self.narrow_cols
        if self.projection:
            t = t.select(self.projection)
        n = t.num_rows
        per = -(-n // self.partitions)  # ceil
        start = partition * per
        stop = min(n, start + per)
        if start >= stop:
            out = [DeviceBatch.empty(self._schema)]
        else:
            chunk = t.slice(start, stop - start)
            # narrowing decided over the WHOLE table so every partition
            # slice shares one physical layout (stable compile shapes)
            if narrow is None:
                narrow = self.narrow_cols = narrowable_int64_cols(t)
            out = list(table_from_arrow(chunk, batch_rows, narrow))
        if self.device_cache is not None:
            self.device_cache[key] = out
        return out


def _file_id(path: str) -> tuple[int, int]:
    """What a cached scan of ``path`` is valid for: the file's modification
    time in nanoseconds and its size."""
    try:
        st = os.stat(path)
    except OSError:
        return (-1, -1)
    return (st.st_mtime_ns, st.st_size)


class _Entry:
    """What a ScanStore keeps of one ``(path, key)``, all of it read from
    the file while it was ``file_id``: ``dev`` the dict a MemoryScanExec
    parks its uploaded batches in; ``host`` and ``narrow`` a CSV or Avro
    file's parse and its narrowing (every partition slices the one
    parse), or ``host`` a streamed column's whole-file dictionary;
    ``nbytes`` what the store's bound counts it at."""

    __slots__ = ("file_id", "dev", "host", "narrow", "nbytes")

    def __init__(self, file_id: tuple[int, int] | None) -> None:
        self.file_id = file_id
        self.dev: dict = {}
        self.host = None
        self.narrow: frozenset | None = None
        self.nbytes = 0

    def filled(self) -> tuple[int, bool]:
        return (len(self.dev), self.host is not None)


def _release(entries: dict) -> None:
    metrics.add(
        "scan_store.resident_bytes", -sum(e.nbytes for e in entries.values())
    )


class ScanStore:
    """What file scans read and uploaded, kept for as long as the owner
    lives: an Executor (tasks decode a fresh plan each, so this is where a
    scan finds what an earlier task left) or a TpuContext.

    Exact: an entry serves only the file it was read from. A scan stats
    the path before it opens the file, an entry of another
    ``(mtime_ns, size)`` is dropped with every other stale entry of the
    path, and a read is parked only if the path still stats the same after
    it. Bounded: the bytes of all entries stay under the serving session's
    ``ballista.tpu.scan_stream_mb`` (what scans may materialise on the
    device; a scan above it streams and is never parked; 0 turns both
    off), the least recently served entry going first; a task still
    reading evicted batches keeps its own reference. Single-flight: a
    miss is read and uploaded by one task, and the others that want the
    same ``(path, key)`` wait for it and hit. A parquet entry holds no
    host table (the device batches are what is served again).

    Counters (``compilecache/metrics.py``): ``scan_store.hits`` (a serve
    that neither read nor uploaded), ``.misses``, ``.evictions`` and
    ``.resident_bytes``, the bytes the process's stores hold now."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # (path, key) -> _Entry, least recently served first
        self._entries: collections.OrderedDict = collections.OrderedDict()
        # (path, key) -> [lock, tasks holding or waiting for it]
        self._flights: dict[tuple, list] = {}
        weakref.finalize(self, _release, self._entries)

    def for_path(self, path: str) -> "ScanCache":
        return ScanCache(self, path)

    @contextlib.contextmanager
    def _flight(self, name: tuple):
        with self._lock:
            slot = self._flights.setdefault(name, [threading.Lock(), 0])
            slot[1] += 1
        try:
            with slot[0]:
                yield
        finally:
            with self._lock:
                slot[1] -= 1
                if not slot[1]:
                    del self._flights[name]

    def _take(self, name: tuple, file_id: tuple[int, int]) -> _Entry:
        """``name``'s entry if it was read from the file as it is now, else
        a new one that ``_serve`` may park."""
        with self._lock:
            e = self._entries.get(name)
            if e is not None and e.file_id == file_id:
                return e
            # a rewritten file: out with every entry read from the old one
            stale = [
                n for n, x in self._entries.items()
                if n[0] == name[0] and x.file_id != file_id
            ]
            freed = sum(self._entries.pop(n).nbytes for n in stale)
        metrics.add("scan_store.resident_bytes", -freed)
        return _Entry(file_id)

    def _serve(self, name: tuple, e: _Entry, missed: bool, bound: int) -> None:
        """``e`` was served: count it, make it the most recently served,
        park what a miss read, and evict down to ``bound`` bytes."""
        changed = missed and _file_id(name[0]) != e.file_id
        evictions = 0
        with self._lock:
            before = sum(x.nbytes for x in self._entries.values())
            parked = self._entries.pop(name, None) is not None
            if not changed and (missed or parked):
                e.nbytes = getattr(e.host, "nbytes", 0) + sum(
                    device_nbytes(b) for bs in e.dev.values() for b in bs
                )
                self._entries[name] = e
            held = sum(x.nbytes for x in self._entries.values())
            while bound and held > bound:
                held -= self._entries.popitem(last=False)[1].nbytes
                evictions += 1
        metrics.add_many([
            ("scan_store.hits", int(not missed)),
            ("scan_store.misses", int(missed)),
            ("scan_store.evictions", evictions),
            ("scan_store.resident_bytes", held - before),
        ])


class ScanCache:
    """One file's side of a ScanStore: what a file scan takes as
    ``scan_cache``."""

    def __init__(self, store: ScanStore, path: str) -> None:
        self.store = store
        self.path = path

    @contextlib.contextmanager
    def entry(
        self, key: tuple, file_id: tuple[int, int], ctx: TaskContext
    ) -> Iterator[_Entry]:
        """The entry of ``key`` (whatever, besides the file, the cached
        data depends on) for a scan that found the file as ``file_id``
        BEFORE opening it. Held single-flight while the caller fills what
        it lacks; what was filled is parked on the way out."""
        name = (self.path, key)
        with self.store._flight(name):
            e = self.store._take(name, file_id)
            had = e.filled()
            yield e
            self.store._serve(
                name, e, e.filled() != had, ctx.config.scan_stream_mb() << 20
            )


class _StagedFileScanExec(ExecutionPlan):
    """Shared machinery for file scans that parse on host then stage like
    a memory table: read ONCE per operator, slice per partition, one
    whole-table narrowing decision (CSV + Avro; Parquet reads row groups
    per partition and derives narrowing from file statistics instead)."""

    def __init__(
        self,
        path: str,
        table_schema: Schema,
        projection: list[str] | None = None,
        partitions: int = 1,
        batch_rows: int | None = None,
        scan_cache: ScanCache | None = None,
    ) -> None:
        """``scan_cache``: the file's side of a :class:`ScanStore` (a
        context's or an executor's), holding the parsed host table AND
        the uploaded DeviceBatches across queries; an overwritten file
        invalidates both tiers. The same residency rationale as
        MemoryScanExec's device_cache — a warm file scan otherwise
        re-parses AND re-uploads gigabytes per query."""
        super().__init__()
        self.path = path
        self.table_schema = table_schema
        self.projection = projection
        self._schema = (
            table_schema.select(projection) if projection else table_schema
        )
        self.partitions = max(1, partitions)
        self.batch_rows = batch_rows
        self.scan_cache = scan_cache
        # without a scan cache: the parse, ONCE per operator
        self._own = _Entry(None)

    def schema(self) -> Schema:
        return self._schema

    def output_partitioning(self):
        return UnknownPartitioning(self.partitions)

    def _read(self) -> pa.Table:  # pragma: no cover — subclasses implement
        raise NotImplementedError

    def _parse_key(self) -> tuple:
        """What, besides the file, ``_read``'s result depends on."""
        return (self.table_schema,)

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[DeviceBatch]:
        if self.scan_cache is None:
            yield from self._batches(self._own, None, partition, ctx)
            return
        # one entry, and so one flight, a file and a way of parsing it:
        # the parse is whole-file
        with self.scan_cache.entry(
            self._parse_key(), _file_id(self.path), ctx
        ) as e:
            out = self._batches(e, e.dev, partition, ctx)
        yield from out

    def _batches(
        self, e: _Entry, device_cache: dict | None, partition: int,
        ctx: TaskContext,
    ) -> list[DeviceBatch]:
        def table() -> tuple[pa.Table, frozenset]:
            if e.host is None:
                with self.metrics.time("read_time"), obs_trace.phase(
                    "task.scan_host"
                ):
                    t = self._read()
                # narrowing decided ONCE per parsed table (not per
                # partition), over all of it
                e.host, e.narrow = t, narrowable_int64_cols(t)
            return e.host, e.narrow

        mem = MemoryScanExec(
            table, self.table_schema, self.projection, self.partitions,
            self.batch_rows, device_cache,
        )
        return mem.batches(partition, ctx)


class CsvScanExec(_StagedFileScanExec):
    """CSV file scan (ref: CsvScanExecNode, ballista.proto:417-429)."""

    def __init__(
        self,
        path: str,
        table_schema: Schema,
        has_header: bool = True,
        delimiter: str = ",",
        projection: list[str] | None = None,
        partitions: int = 1,
        batch_rows: int | None = None,
        scan_cache: ScanCache | None = None,
    ) -> None:
        super().__init__(
            path, table_schema, projection, partitions, batch_rows,
            scan_cache,
        )
        self.has_header = has_header
        self.delimiter = delimiter

    def describe(self) -> str:
        return f"CsvScanExec: {self.path}, partitions={self.partitions}"

    def _parse_key(self) -> tuple:
        return (self.table_schema, self.has_header, self.delimiter)

    def _read(self) -> pa.Table:
        arrow_schema = schema_to_arrow(self.table_schema)
        convert = pacsv.ConvertOptions(
            column_types={f.name: f.type for f in arrow_schema}
        )
        read = pacsv.ReadOptions(
            column_names=None if self.has_header else arrow_schema.names,
        )
        parse = pacsv.ParseOptions(delimiter=self.delimiter)
        return pacsv.read_csv(
            self.path, read_options=read, parse_options=parse,
            convert_options=convert,
        )


class AvroScanExec(_StagedFileScanExec):
    """Avro file scan (ref: AvroFormat in DataFusion's ListingTable; the
    reference serializes AvroScanExecNode alongside CSV/Parquet at
    ballista.proto:60-92). Decoded on host by ballista_tpu.avro."""

    def describe(self) -> str:
        return f"AvroScanExec: {self.path}, partitions={self.partitions}"

    def _read(self) -> pa.Table:
        from ballista_tpu.avro import read_avro

        return read_avro(self.path)


def _stat_value(v, dtype: DataType):
    """Normalize a parquet statistics min/max to the engine's literal
    domain (DATE32 -> epoch days, TIMESTAMP -> microseconds)."""
    import datetime

    if v is None:
        return None
    if dtype == DataType.DATE32 and isinstance(v, datetime.date):
        return (v - datetime.date(1970, 1, 1)).days
    if dtype == DataType.TIMESTAMP_US and isinstance(v, datetime.datetime):
        epoch = datetime.datetime(1970, 1, 1, tzinfo=v.tzinfo)
        return int((v - epoch).total_seconds() * 1_000_000)
    if isinstance(v, bytes):
        try:
            return v.decode()
        except UnicodeDecodeError:
            return None
    return v


def _cmp_may_match(op: "L.Operator", mn, mx, lit) -> bool:
    """Could ANY value in [mn, mx] satisfy ``value <op> lit``? Conservative
    (True on doubt)."""
    from ballista_tpu.expr import logical as L

    try:
        if op == L.Operator.EQ:
            return mn <= lit <= mx
        if op == L.Operator.NEQ:
            return not (mn == mx == lit)
        if op == L.Operator.LT:
            return mn < lit
        if op == L.Operator.LTEQ:
            return mn <= lit
        if op == L.Operator.GT:
            return mx > lit
        if op == L.Operator.GTEQ:
            return mx >= lit
    except TypeError:
        return True
    return True


def _predicate_may_match(expr, schema: Schema, col_stats: dict) -> bool:
    """min/max row-group pruning evaluator. ``col_stats[name] = (mn, mx)``.
    Returns False only when the predicate is provably false for EVERY row
    of the group — pruning is an optimization, never a correctness
    dependence (the exact filter still runs on device)."""
    from ballista_tpu.expr import logical as L

    if isinstance(expr, L.BinaryExpr):
        if expr.op == L.Operator.AND:
            return _predicate_may_match(
                expr.left, schema, col_stats
            ) and _predicate_may_match(expr.right, schema, col_stats)
        if expr.op == L.Operator.OR:
            return _predicate_may_match(
                expr.left, schema, col_stats
            ) or _predicate_may_match(expr.right, schema, col_stats)
        if expr.op.is_comparison:
            col, lit, flip = None, None, False
            if isinstance(expr.left, L.Column) and isinstance(
                expr.right, L.Literal
            ):
                col, lit = expr.left, expr.right
            elif isinstance(expr.right, L.Column) and isinstance(
                expr.left, L.Literal
            ):
                col, lit, flip = expr.right, expr.left, True
            if col is None or lit.value is None:
                return True
            stats = col_stats.get(col.cname)
            if stats is None:
                return True
            mn, mx = stats
            if mn is None or mx is None:
                return True
            op = expr.op
            if flip:  # lit <op> col  ==  col <flipped-op> lit
                op = {
                    L.Operator.LT: L.Operator.GT,
                    L.Operator.LTEQ: L.Operator.GTEQ,
                    L.Operator.GT: L.Operator.LT,
                    L.Operator.GTEQ: L.Operator.LTEQ,
                }.get(op, op)
            return _cmp_may_match(op, mn, mx, lit.value)
    if isinstance(expr, L.Between):
        lo_ok = _predicate_may_match(
            L.BinaryExpr(expr.expr, L.Operator.GTEQ, expr.low),
            schema, col_stats,
        )
        hi_ok = _predicate_may_match(
            L.BinaryExpr(expr.expr, L.Operator.LTEQ, expr.high),
            schema, col_stats,
        )
        keep = lo_ok and hi_ok
        return not keep if expr.negated else keep
    if isinstance(expr, L.InList) and not expr.negated:
        return any(
            _predicate_may_match(
                L.BinaryExpr(expr.expr, L.Operator.EQ, item),
                schema, col_stats,
            )
            for item in expr.values
            if isinstance(item, L.Literal)
        ) or any(
            not isinstance(item, L.Literal) for item in expr.values
        )
    return True


class ParquetScanExec(ExecutionPlan):
    """Parquet scan with row-group min/max pruning (ref:
    ParquetScanExecNode, ballista.proto:431-439; pruning flag config.rs
    BALLISTA_PARQUET_PRUNING). ``predicates`` are the scan's pushed-down
    filters — row groups whose statistics prove a predicate false for
    every row are skipped before any bytes are read; the exact filter
    still runs on device, so pruning can never change results.

    Partitioning is by row-group ranges so partitions read disjoint byte
    ranges of the file.
    """

    def __init__(
        self,
        path: str,
        table_schema: Schema,
        projection: list[str] | None = None,
        partitions: int = 1,
        batch_rows: int | None = None,
        predicates: list | None = None,
        scan_cache: ScanCache | None = None,
    ) -> None:
        super().__init__()
        self.path = path
        self.table_schema = table_schema
        self.projection = projection
        self._schema = (
            table_schema.select(projection) if projection else table_schema
        )
        self.partitions = max(1, partitions)
        self.batch_rows = batch_rows
        self.predicates = list(predicates or [])
        self.scan_cache = scan_cache
        self._kept_groups: list[int] | None = None

    def schema(self) -> Schema:
        return self._schema

    def output_partitioning(self):
        return UnknownPartitioning(self.partitions)

    def describe(self) -> str:
        p = (
            f", prune_on=[{', '.join(e.name() for e in self.predicates)}]"
            if self.predicates
            else ""
        )
        return f"ParquetScanExec: {self.path}, partitions={self.partitions}{p}"

    def _pruned_groups(self, f: papq.ParquetFile, pruning: bool) -> list[int]:
        if self._kept_groups is not None:
            return self._kept_groups
        ngroups = f.num_row_groups
        if not pruning or not self.predicates:
            self._kept_groups = list(range(ngroups))
            return self._kept_groups
        md = f.metadata
        name_to_idx = {
            md.schema.column(i).name: i for i in range(md.num_columns)
        }
        dtypes = {fl.name: fl.dtype for fl in self.table_schema}
        kept = []
        for g in range(ngroups):
            rg = md.row_group(g)
            col_stats = {}
            for name, ci in name_to_idx.items():
                st = rg.column(ci).statistics
                if st is None or not st.has_min_max:
                    continue
                dt = dtypes.get(name)
                if dt is None:
                    continue
                col_stats[name] = (
                    _stat_value(st.min, dt), _stat_value(st.max, dt)
                )
            if all(
                _predicate_may_match(p, self.table_schema, col_stats)
                for p in self.predicates
            ):
                kept.append(g)
        self.metrics.add("row_groups_pruned", ngroups - len(kept))
        self._kept_groups = kept
        return kept

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[DeviceBatch]:
        # what a cache entry must have been read from: taken before the open
        file_id = _file_id(self.path)
        f = papq.ParquetFile(self.path)
        kept = self._pruned_groups(f, ctx.config.parquet_pruning())
        per = -(-len(kept) // self.partitions) if kept else 0
        groups = kept[partition * per : (partition + 1) * per]
        cols = self.projection if self.projection else None
        if not groups:
            yield DeviceBatch.empty(self._schema)
            return
        stream_mb = ctx.config.scan_stream_mb()
        if stream_mb:
            gbytes = self._projected_group_bytes(f, groups)
            if sum(gbytes) > stream_mb << 20:
                yield from self._execute_streaming(
                    f, file_id, groups, gbytes, ctx
                )
                return

        def table() -> tuple[pa.Table, frozenset]:
            with self.metrics.time("read_time"), obs_trace.phase(
                "task.scan_host"
            ) as ph:
                t = f.read_row_groups(groups, columns=cols)
                ph.nbytes = t.nbytes
            # column order must match the projected schema; narrow by
            # FILE-level statistics (all row groups), not this partition's
            # subset — partitions must share one physical layout
            return (
                t.select([fld.name for fld in self._schema]),
                self._narrowable_from_stats(f),
            )

        def batches(device_cache: dict | None) -> list[DeviceBatch]:
            return MemoryScanExec(
                table, self._schema, None, 1, self.batch_rows, device_cache
            ).batches(0, ctx)

        if self.scan_cache is None:
            yield from batches(None)
            return
        # the entry names exactly the row groups and columns served
        with self.scan_cache.entry(
            (tuple(groups), self._schema), file_id, ctx
        ) as e:
            out = batches(e.dev)
        yield from out

    # -- streaming (larger-than-memory) path --------------------------------

    # Host bytes per streamed slice: a few row groups read + converted at a
    # time, so peak host memory is one slice regardless of file size. Device
    # batches are handed downstream one at a time; streaming consumers
    # (partial aggregates, probe sides) fold and release them.
    STREAM_SLICE_BYTES = 1 << 30

    def _projected_group_bytes(
        self, f: "papq.ParquetFile", groups: list[int]
    ) -> list[int]:
        """Uncompressed byte size of each row group restricted to the
        projected columns — the memory the materialized path would commit."""
        md = f.metadata
        want = {fld.name for fld in self._schema}
        out = []
        for g in groups:
            rg = md.row_group(g)
            out.append(
                sum(
                    rg.column(ci).total_uncompressed_size
                    for ci in range(rg.num_columns)
                    if rg.column(ci).path_in_schema in want
                )
            )
        return out

    def _stream_dicts(
        self, f: "papq.ParquetFile", file_id: tuple[int, int],
        ctx: TaskContext,
    ) -> dict:
        """Whole-file dictionary per projected STRING column, so every
        streamed slice encodes identical codes (kept in the scan cache —
        the union pass reads just that column once)."""
        out = {}
        for fld in self._schema:
            if fld.dtype != DataType.STRING:
                continue
            if self.scan_cache is None:
                out[fld.name] = self._file_dict(f, fld.name)
                continue
            with self.scan_cache.entry(
                ("sdict", fld.name), file_id, ctx
            ) as e:
                if e.host is None:
                    e.host = self._file_dict(f, fld.name)
                out[fld.name] = e.host
        return out

    def _file_dict(self, f: "papq.ParquetFile", column: str):
        import pyarrow.compute as pc

        from ballista_tpu.columnar.batch import Dictionary

        vals: set = set()
        with self.metrics.time("dict_scan_time"):
            for rb in f.iter_batches(columns=[column], batch_size=1 << 20):
                uniq = pc.unique(rb.column(0))
                if pa.types.is_dictionary(uniq.type):
                    uniq = uniq.cast(uniq.type.value_type)
                vals.update(v for v in uniq.to_pylist() if v is not None)
        return Dictionary(tuple(sorted(vals)))

    def _execute_streaming(
        self,
        f: "papq.ParquetFile",
        file_id: tuple[int, int],
        groups: list[int],
        gbytes: list[int],
        ctx: TaskContext,
    ) -> Iterator[DeviceBatch]:
        from ballista_tpu.exec.pipeline import prefetch_slices

        batch_rows = self.batch_rows or ctx.config.tpu_batch_rows()
        narrow = self._narrowable_from_stats(f)
        dicts = self._stream_dicts(f, file_id, ctx)
        self.metrics.add("stream_slices", 0)
        names = [fld.name for fld in self._schema]
        slices: list[list[int]] = []
        cur: list[int] = []
        cur_b = 0
        for g, gb in zip(groups, gbytes):
            cur.append(g)
            cur_b += gb
            if cur_b >= self.STREAM_SLICE_BYTES:
                slices.append(cur)
                cur, cur_b = [], 0
        if cur:
            slices.append(cur)

        def load(gs: list[int]) -> list[DeviceBatch]:
            return self._load_slice(f, gs, names, batch_rows, narrow, dicts)

        # Double-buffered prefetch (ballista.tpu.prefetch_depth): a host
        # thread reads/decodes the NEXT slice and stages its device upload
        # while the current slice's batches compute downstream. depth=0
        # degrades to the serial read-compute-read loop.
        for batches in prefetch_slices(
            load, slices, ctx.config.prefetch_depth(), self.metrics
        ):
            self.metrics.add("stream_slices")
            for b in batches:
                self.metrics.add("output_rows", b.count_valid())
                yield b

    def _load_slice(
        self, f, groups, names, batch_rows, narrow, dicts
    ) -> list[DeviceBatch]:
        """Read + convert + stage one row-group slice. Runs on the
        prefetch worker when enabled; DeviceBatch.from_host starts the
        host->device transfer, so the next slice's upload overlaps the
        current slice's compute."""
        with self.metrics.time("read_time"), obs_trace.phase(
            "task.scan_host"
        ) as ph:
            t = f.read_row_groups(groups, columns=self.projection or None)
            ph.nbytes = t.nbytes
        t = t.select(names)
        return table_from_arrow(t, batch_rows, narrow, fixed_dicts=dicts)

    def _narrowable_from_stats(self, f: "papq.ParquetFile") -> frozenset:
        """INT64 columns whose min/max over EVERY row group (from parquet
        column statistics) fit int32; columns lacking statistics are left
        wide — a data-derived per-partition decision would flip layouts."""
        md = f.metadata
        name_to_dtype = {fl.name: fl.dtype for fl in self._schema}
        lo: dict[str, int] = {}
        hi: dict[str, int] = {}
        skip: set[str] = set()
        for g in range(md.num_row_groups):
            rg = md.row_group(g)
            for ci in range(rg.num_columns):
                col = rg.column(ci)
                name = col.path_in_schema
                if name_to_dtype.get(name) != DataType.INT64:
                    continue
                st = col.statistics
                if (
                    st is None
                    or not st.has_min_max
                    or not isinstance(st.min, int)
                ):
                    skip.add(name)
                    continue
                lo[name] = min(lo.get(name, st.min), st.min)
                hi[name] = max(hi.get(name, st.max), st.max)
        from ballista_tpu.columnar.arrow_interop import fits_int32

        return frozenset(
            name
            for name in lo
            if name not in skip and fits_int32(lo[name], hi[name])
        )
