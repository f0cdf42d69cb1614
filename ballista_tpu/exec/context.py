"""TpuContext: the single-process engine entry point.

The engine-side equivalent of DataFusion's SessionContext (which the
reference's BallistaContext builds on, ballista/rust/client/src/context.rs).
The distributed client context (``ballista_tpu.client``) wraps a scheduler
instead but exposes the same surface; this context is also what executors
use to run stage plans locally.
"""

from __future__ import annotations

import logging
import pathlib

import pyarrow as pa
import pyarrow.csv as pacsv
import pyarrow.parquet as papq

from ballista_tpu.columnar.arrow_interop import (
    batch_to_arrow,
    schema_from_arrow,
)
from ballista_tpu.config import BallistaConfig
from ballista_tpu.datatypes import Schema
from ballista_tpu.errors import PlanError, SqlError
from ballista_tpu.exec.base import (
    ExecutionPlan,
    TaskContext,
    UnknownPartitioning,
    run_with_capacity_retry,
)
from ballista_tpu.exec.planner import PhysicalPlanner, TableProvider
from ballista_tpu.exec.scan import (
    AvroScanExec,
    CsvScanExec,
    MemoryScanExec,
    ParquetScanExec,
    ScanStore,
)
from ballista_tpu.obs import trace as obs_trace
from ballista_tpu.plan.logical import LogicalPlan
from ballista_tpu.plan.optimizer import optimize
from ballista_tpu.sql import ast
from ballista_tpu.sql.parser import parse_sql
from ballista_tpu.sql.planner import Catalog, SqlPlanner
from ballista_tpu.tpch import all_schemas  # noqa: F401  (re-export convenience)

log = logging.getLogger(__name__)


# Serializes EXPLAIN ANALYZE runs: the verb flips the process-wide
# BALLISTA_TPU_NO_FUSE env flag for its execution window (see
# _explain_analyze), and two concurrent runs racing the save/restore
# could leave it latched on.
import threading as _threading  # noqa: E402

_ANALYZE_LOCK = _threading.Lock()


class _Registered:
    def __init__(self, kind: str, schema: Schema, **kw):
        self.kind = kind  # memory | csv | parquet
        self.schema = schema
        self.kw = kw


def _scans_system_table(logical) -> bool:
    """Does this logical plan reference any system.* table
    (docs/observability.md)? Such plans bypass the physical-plan cache —
    their scans must re-materialize fresh rows every execution."""
    from ballista_tpu.obs.history import SYSTEM_TABLE_SCHEMAS
    from ballista_tpu.plan.logical import TableScan

    def walk(p) -> bool:
        if isinstance(p, TableScan) and p.table_name in SYSTEM_TABLE_SCHEMAS:
            return True
        return any(walk(c) for c in p.children())

    return walk(logical)


class TpuContext(Catalog, TableProvider):
    """Register tables, run SQL, collect Arrow results."""

    def __init__(self, config: BallistaConfig | None = None):
        self.config = config or BallistaConfig()
        # UDF plugins (ref plugin/mod.rs: loaded once at context creation;
        # both the ballista.plugin_dir key and $BALLISTA_PLUGIN_DIR count)
        from ballista_tpu.plugin import load_plugins

        load_plugins(self.config.plugin_dir() or None)
        # compile-latency subsystem (docs/compile_cache.md): install the
        # configured capacity-bucket ladder before any batch is built, and
        # optionally AOT-prewarm the kernel vocabulary (latched process-
        # wide; 'background' threads wind down on their own — see
        # compilecache.prewarm)
        from ballista_tpu.columnar.batch import set_capacity_buckets
        from ballista_tpu.compilecache import metrics as compile_metrics
        from ballista_tpu.compilecache import start_prewarm

        compile_metrics.install()
        set_capacity_buckets(self.config.capacity_buckets())
        self._prewarm = start_prewarm(
            self.config.prewarm(), max_rows=self.config.tpu_batch_rows()
        )
        self.tables: dict[str, _Registered] = {}
        # what this context's file scans read and uploaded (exec/scan.py)
        self._scans = ScanStore()
        self._mesh_runtime = None
        self._mesh_checked = False
        # remembered adaptive-capacity growth (see run_with_capacity_retry)
        self._capacity_hint: dict = {}
        # cross-query plan-shape speculation cache (join strategies,
        # expansion capacities); cleared whenever table data changes
        self._plan_cache: dict = {}
        # persisted hints (compilecache/hints.py): loaded lazily at the
        # FIRST collect — registration clears _plan_cache, so an eager
        # load here would be wiped before the first query sees it
        from ballista_tpu.compilecache.hints import HintStore

        self._hints = HintStore()
        # physical plans cached by (optimized-logical display, config
        # digest): repeated query texts reuse the SAME operator instances
        # and therefore their jitted programs — otherwise every query
        # re-traces every per-instance jit (~0.2s/query of pure Python
        # lowering on q6-sized plans, and it grows with plan size)
        self._physical_cache: dict = {}
        # queryable history (docs/observability.md): the local engine's
        # own query log — every collect records a history row with its
        # measured cost vector, and the system.queries /
        # system.task_attempts tables are materialized from it on scan.
        # Lazily created (MemoryBackend; the distributed BallistaContext
        # overrides the system-table source with the scheduler's
        # persistent log instead).
        self._local_history = None
        self._local_query_seq = 0

    def mesh_runtime(self):
        """The ICI collective-shuffle runtime, when this process sees >= 2
        devices and ``ballista.tpu.collective_shuffle`` is on; None
        otherwise (single chip -> the local operator tier is already
        optimal). Created once; stage programs are cached across queries."""
        if not self.config.collective_shuffle():
            return None
        if not self._mesh_checked:
            self._mesh_checked = True
            import jax

            if len(jax.devices()) >= 2:
                from ballista_tpu.exec.mesh import MeshRuntime
                from ballista_tpu.parallel import make_mesh

                self._mesh_runtime = MeshRuntime(make_mesh())
        return self._mesh_runtime

    # -- registration (ref context.rs read_csv/read_parquet/register_*) ------
    def register_table(self, name: str, table: pa.Table) -> None:
        self.tables[name] = _Registered(
            "memory", schema_from_arrow(table.schema), table=table
        )
        # data changed: cached join strategies / capacities may be stale.
        # (They are deferred-validated anyway; clearing avoids a guaranteed
        # speculation-miss retry on the next query over this table.)
        self._plan_cache.clear()
        self._physical_cache.clear()

    def register_csv(
        self,
        name: str,
        path: str,
        schema: Schema | None = None,
        has_header: bool = True,
        delimiter: str = ",",
    ) -> None:
        if schema is None:
            t = pacsv.read_csv(
                path,
                parse_options=pacsv.ParseOptions(delimiter=delimiter),
            )
            schema = schema_from_arrow(t.schema)
        self.tables[name] = _Registered(
            "csv", schema, path=path, has_header=has_header, delimiter=delimiter
        )
        self._plan_cache.clear()
        self._physical_cache.clear()

    def register_parquet(self, name: str, path: str) -> None:
        schema = schema_from_arrow(papq.read_schema(path))
        self.tables[name] = _Registered("parquet", schema, path=path)
        self._plan_cache.clear()
        self._physical_cache.clear()

    def register_avro(self, name: str, path: str) -> None:
        """ref context.rs register_avro / read_avro. Schema comes from the
        file HEADER only — no data blocks decoded at registration (parity
        with register_parquet's footer-only read)."""
        from ballista_tpu.avro import read_avro_schema

        self.tables[name] = _Registered(
            "avro", schema_from_arrow(read_avro_schema(path)), path=path
        )
        self._plan_cache.clear()
        self._physical_cache.clear()

    def append_table(self, name: str, table: pa.Table) -> None:
        """Micro-batch append onto a registered MEMORY table (ROADMAP
        streaming ingest). Routes through :meth:`register_table` so the
        append inherits its invalidation contract verbatim — plan caches
        cleared, and ``_data_version()`` flips because the combined
        table is a new object with a new row count (stalelint's
        ``registered-data-append`` contract pins this routing)."""
        reg = self.tables.get(name)
        existing = reg.kw.get("table") if reg is not None else None
        if existing is None:
            raise PlanError(
                f"append_table: {name!r} is not a registered memory "
                "table (file-backed tables version by mtime; rewrite "
                "the file instead)"
            )
        if table.schema != existing.schema:
            raise PlanError(
                f"append_table: schema mismatch for {name!r}"
            )
        combined = pa.concat_tables([existing, table]).combine_chunks()
        self.register_table(name, combined)

    def deregister_table(self, name: str) -> None:
        self.tables.pop(name, None)
        self._plan_cache.clear()
        self._physical_cache.clear()

    # -- system tables (docs/observability.md) -------------------------------
    def _system_history(self):
        """The local query log backing system.queries/system.task_attempts
        (MemoryBackend: the local context's history is process-scoped;
        durable history is the scheduler's job)."""
        if self._local_history is None:
            from ballista_tpu.obs.history import HistoryStore
            from ballista_tpu.scheduler.state_backend import MemoryBackend

            self._local_history = HistoryStore(
                MemoryBackend(),
                retention_jobs=self.config.history_retention_jobs(),
            )
        return self._local_history

    def _system_table_rows(self, name: str) -> list[dict]:
        """The current rows of one system table. The distributed context
        overrides this to fetch the scheduler's persistent log."""
        from ballista_tpu.obs.history import SYSTEM_TABLE_KINDS

        kind = SYSTEM_TABLE_KINDS[name]
        if kind == "queries":
            return self._system_history().jobs()
        if kind == "task_attempts":
            return self._system_history().attempts()
        return []  # no cluster: the local engine has no executor roster

    def _refresh_system_table(self, name: str) -> None:
        """Materialize one system table's CURRENT rows as the registered
        memory table the ordinary scan path serves. Registered directly
        (not register_table): a refresh must not clear the plan caches —
        the physical-plan cache key already varies with the fresh table
        object via _data_version, so stale plans can never be served."""
        from ballista_tpu.obs import history as obs_history

        t = obs_history.system_table(name, self._system_table_rows(name))
        self.tables[name] = _Registered(
            "memory", obs_history.SYSTEM_TABLE_SCHEMAS[name], table=t
        )

    def _log_local_query(self, phys, wall_s: float, cpu_s: float,
                         compile_s: float) -> None:
        """Record one completed local collect into the query log —
        the engine observing itself through the same record shape the
        scheduler persists. Guarded by the caller."""
        from ballista_tpu.obs import history as obs_history
        from ballista_tpu.obs.qclass import plan_class

        import time as _time

        hist = self._system_history()
        self._local_query_seq += 1
        job_id = f"local-{self._local_query_seq:06d}"
        now = _time.time()
        cost = obs_history.cost_from_run(
            wall_seconds=wall_s, cpu_seconds=cpu_s, plan=phys,
            compile_seconds=compile_s,
        )
        qclass = plan_class(phys)
        hist.record_submit(
            job_id, query_class=qclass, submitted_s=now - wall_s
        )
        hist.record_terminal(
            job_id, "completed", query_class=qclass,
            submitted_s=now - wall_s, latency_s=wall_s, cost=cost,
        )

    # -- Catalog / TableProvider ---------------------------------------------
    def schema_of(self, table: str) -> Schema:
        from ballista_tpu.obs.history import SYSTEM_TABLE_SCHEMAS

        if table in SYSTEM_TABLE_SCHEMAS:
            # static schema — no fetch at plan time; scan() materializes
            # the fresh rows when the query actually executes
            return SYSTEM_TABLE_SCHEMAS[table]
        if table not in self.tables:
            raise PlanError(f"table {table!r} not found")
        return self.tables[table].schema

    def source_of(self, table: str):
        r = self.tables.get(table)
        if r is None or r.kind == "memory":
            return None
        if r.kind == "csv":
            return ("csv", r.kw["path"], r.kw["has_header"], r.kw["delimiter"])
        return (r.kind, r.kw["path"], False, ",")

    def scan(
        self, table: str, projection: list[str] | None, partitions: int
    ) -> ExecutionPlan:
        from ballista_tpu.obs.history import SYSTEM_TABLE_SCHEMAS

        if table in SYSTEM_TABLE_SCHEMAS:
            # refresh-on-scan: a system table always serves the rows as
            # of THIS query's planning, through the ordinary memory-scan
            # path (planlint verification and execution see nothing
            # special about it)
            self._refresh_system_table(table)
        r = self.tables.get(table)
        if r is None:
            raise PlanError(f"table {table!r} not found")
        # batch_rows resolves at execute time from the task's session
        # config, so it follows ballista.tpu.batch_rows across process
        # boundaries (decoded stage plans carry the config, not the knob)
        if r.kind == "memory":
            # table-lifetime device cache: warm queries re-serve resident
            # device arrays instead of re-uploading the table
            cache = r.kw.setdefault("device_cache", {})
            return MemoryScanExec(
                r.kw["table"], r.schema, projection, partitions,
                device_cache=cache,
            )
        # file scans share a context-lifetime cache too: parsed host
        # table + uploaded device batches, invalidated by file mtime + size
        scache = self._scans.for_path(r.kw["path"])
        if r.kind == "csv":
            return CsvScanExec(
                r.kw["path"], r.schema, r.kw["has_header"], r.kw["delimiter"],
                projection, partitions, scan_cache=scache,
            )
        if r.kind == "avro":
            return AvroScanExec(
                r.kw["path"], r.schema, projection, partitions,
                scan_cache=scache,
            )
        return ParquetScanExec(
            r.kw["path"], r.schema, projection, partitions,
            scan_cache=scache,
        )

    # -- DataFrame entry points (ref client context.rs:211-253 read_csv /
    # read_parquet / read_avro -> DataFrame; table() as in DataFusion) ------
    def _frame(self, logical: LogicalPlan) -> "DataFrame":
        """Frame factory — the cluster context overrides this so builder
        chains started from table()/read_* execute remotely."""
        return DataFrame(self, logical)

    def table(self, name: str) -> "DataFrame":
        from ballista_tpu.plan.logical import TableScan

        return self._frame(
            TableScan(name, self.schema_of(name), source=self.source_of(name))
        )

    def _auto_name(self, path: str, kind: str) -> str:
        """Derived registration name for read_*: the file stem, uniquified
        when a DIFFERENT source already holds it (re-reading the same file
        reuses the entry; '2024/data.csv' then '2025/data.csv' must not
        silently rebind frames built on the first)."""
        base = pathlib.Path(path).stem
        name = base
        i = 2
        while name in self.tables:
            r = self.tables[name]
            if r.kind == kind and r.kw.get("path") == path:
                return name
            name = f"{base}_{i}"
            i += 1
        return name

    def read_csv(
        self,
        path: str,
        schema: Schema | None = None,
        has_header: bool = True,
        delimiter: str = ",",
        name: str | None = None,
    ) -> "DataFrame":
        name = name or self._auto_name(path, "csv")
        self.register_csv(name, path, schema, has_header, delimiter)
        return self.table(name)

    def read_parquet(self, path: str, name: str | None = None) -> "DataFrame":
        name = name or self._auto_name(path, "parquet")
        self.register_parquet(name, path)
        return self.table(name)

    def read_avro(self, path: str, name: str | None = None) -> "DataFrame":
        name = name or self._auto_name(path, "avro")
        self.register_avro(name, path)
        return self.table(name)

    # -- SQL -----------------------------------------------------------------
    def sql_to_logical(self, sql: str) -> LogicalPlan:
        stmt = parse_sql(sql)
        if not isinstance(stmt, (ast.Select, ast.SetOp)):
            raise SqlError("only queries produce logical plans; use sql()")
        return SqlPlanner(self).plan(stmt)

    def _data_version(self) -> tuple:
        """Registered-data signature for the physical-plan cache key: a
        swapped memory table (object identity + row count) or a rewritten
        file (mtime) must produce a fresh plan — cached scan operators
        snapshot their table at construction. System tables are EXCLUDED:
        refresh-on-scan re-registers them every query, and letting that
        churn the signature would invalidate every cached user plan each
        time a dashboard polls system.queries (plans that scan a system
        table are never cached at all — see create_physical_plan)."""
        import os

        from ballista_tpu.obs.history import SYSTEM_TABLE_SCHEMAS

        sig = []
        for name in sorted(self.tables):
            if name in SYSTEM_TABLE_SCHEMAS:
                continue
            r = self.tables[name]
            t = r.kw.get("table")
            if t is not None:
                sig.append((name, id(t), t.num_rows))
            else:
                try:
                    mt = os.stat(r.kw["path"]).st_mtime
                except OSError:
                    mt = -1.0
                sig.append((name, r.kw["path"], mt))
        return tuple(sig)

    def create_physical_plan(
        self, logical: LogicalPlan, sql: str | None = None
    ) -> ExecutionPlan:
        optimized = optimize(logical)
        verify = self.config.verify_plans()
        if verify:
            # errors move left: prove the plan executable BEFORE running
            # it (schema agreement, column resolution, dtype legality).
            # ``sql`` (when the plan came from sql()) lets diagnostics
            # carry a source span. Cached physical plans below were
            # verified when first planned.
            from ballista_tpu.analysis import verify_logical

            verify_logical(optimized, sql=sql)
        # serde bytes, not display(): display renders aliased exprs by
        # alias name only, so textually different queries can share a
        # display — the proto encoding is structurally exact
        try:
            from ballista_tpu.serde import logical_to_proto

            fp = logical_to_proto(optimized).SerializeToString()
        except Exception:
            fp = None  # unserializable plan: just plan it fresh
        key = None
        if fp is not None and not _scans_system_table(optimized):
            # plans over system tables are NEVER cached: a cached scan
            # operator snapshots the rows it was planned against, and a
            # system table must serve the rows as of THIS query
            key = (fp, tuple(sorted(self.config.settings().items())),
                   self._data_version())
            cached = self._physical_cache.get(key)
            if cached is not None:
                from ballista_tpu.analysis import stalewitness

                if stalewitness.enabled() and stalewitness.should_sample(
                    "physical_plan_cache"
                ):
                    # staleness witness: re-plan fresh and compare the
                    # structural renders — a cached operator tree that
                    # no longer matches what the planner would produce
                    # for this (plan, settings, data-version) key is a
                    # stale hit
                    import hashlib

                    fresh = PhysicalPlanner(
                        self,
                        self.config.default_shuffle_partitions(),
                        mesh_runtime=self.mesh_runtime(),
                    ).plan(optimized)
                    stalewitness.check(
                        "physical_plan_cache",
                        key[0][:16],
                        hashlib.sha256(
                            cached.display().encode()
                        ).hexdigest(),
                        hashlib.sha256(
                            fresh.display().encode()
                        ).hexdigest(),
                        version=key[2],
                    )
                # Metrics stay per-query, as with a fresh plan. (The
                # returned instance is SHARED across identical queries:
                # a caller holding it across another run of the same
                # text sees that run's metrics, not a snapshot.)
                def _reset(p):
                    p.metrics.reset()
                    for c in p.children():
                        _reset(c)

                _reset(cached)
                return cached
            if len(self._physical_cache) >= 128:
                # parameterized query streams (distinct literals per
                # request) must not retain operator trees + compiled
                # programs without bound; dropping everything is fine —
                # a re-plan costs ~ms and recompiles hit the XLA cache
                self._physical_cache.clear()
                # instance-held join build tables die with their plans;
                # reset the shared HBM tally so admission doesn't starve
                self._plan_cache.pop("__build_cache_bytes__", None)
        partitions = self.config.default_shuffle_partitions()
        phys = PhysicalPlanner(
            self, partitions, mesh_runtime=self.mesh_runtime()
        ).plan(optimized)
        if verify:
            from ballista_tpu.analysis import verify_physical

            verify_physical(phys, sql=sql)
        if key is not None:
            self._physical_cache[key] = phys
        return phys

    def sql(self, sql: str) -> "DataFrame":
        stmt = parse_sql(sql)
        if isinstance(stmt, ast.CreateExternalTable):
            self._create_external_table(stmt)
            return DataFrame.empty_ok(self)
        if isinstance(stmt, ast.DropTable):
            if stmt.name not in self.tables and not stmt.if_exists:
                raise PlanError(f"table {stmt.name!r} not found")
            self.deregister_table(stmt.name)
            return DataFrame.empty_ok(self)
        if isinstance(stmt, ast.ShowTables):
            t = pa.table({"table_name": pa.array(sorted(self.tables))})
            return DataFrame.from_arrow(self, t)
        if isinstance(stmt, ast.ShowColumns):
            schema = self.schema_of(stmt.table)
            t = pa.table(
                {
                    "column_name": pa.array([f.name for f in schema]),
                    "data_type": pa.array([f.dtype.value for f in schema]),
                    "nullable": pa.array([f.nullable for f in schema]),
                }
            )
            return DataFrame.from_arrow(self, t)
        if isinstance(stmt, ast.Explain):
            logical = SqlPlanner(self).plan(stmt.query)
            optimized = optimize(logical)
            if stmt.analyze:
                return self._explain_analyze(optimized, sql)
            rows = [
                ("logical_plan", logical.display()),
                ("optimized_plan", optimized.display()),
            ]
            # one physical plan serves both VERBOSE display and VERIFY —
            # the report must describe the plan the user sees; planned
            # with mesh_runtime so it is also the plan that would execute
            phys = None
            if stmt.verbose or stmt.verify:
                phys = PhysicalPlanner(
                    self,
                    self.config.default_shuffle_partitions(),
                    mesh_runtime=self.mesh_runtime(),
                ).plan(optimized)
            if stmt.verbose:
                rows.append(("physical_plan", phys.display()))
            if stmt.verify:
                rows.append(
                    ("verification", self._verify_report(optimized, phys, sql))
                )
            t = pa.table(
                {
                    "plan_type": pa.array([r[0] for r in rows]),
                    "plan": pa.array([r[1] for r in rows]),
                }
            )
            return DataFrame.from_arrow(self, t)
        if isinstance(stmt, (ast.Select, ast.SetOp)):
            df = DataFrame(self, SqlPlanner(self).plan(stmt))
            df._sql = sql  # verifier diagnostics carry a source span
            return df
        raise SqlError(f"unsupported statement {type(stmt).__name__}")

    def _explain_analyze(self, optimized: LogicalPlan, sql: str | None):
        """EXPLAIN ANALYZE (docs/observability.md): plan, instrument every
        physical operator (obs.profile), EXECUTE the query to completion,
        and return the plan re-printed with measured rows/bytes/elapsed
        per operator plus a run summary. A fresh (uncached) physical plan
        keeps the metrics this run's own; results are drained, not
        returned — the verb exists to measure, and the measured counters
        are exactly the stats substrate the AQE roadmap item re-plans
        from."""
        import contextlib
        import time as _time

        from ballista_tpu.obs import profile

        phys = PhysicalPlanner(
            self,
            self.config.default_shuffle_partitions(),
            mesh_runtime=self.mesh_runtime(),
        ).plan(optimized)
        if self.config.verify_plans():
            from ballista_tpu.analysis import verify_physical

            verify_physical(phys, sql=sql)
        # the one caller that syncs: the verb reports device-complete time
        profile.instrument_plan(phys, sync=True)
        part = phys.output_partitioning()
        n = part.n

        def run(ctx: TaskContext) -> int:
            # fresh metrics per attempt: a capacity-overflow retry
            # re-executes the same instrumented tree, and accumulating
            # across attempts would print double-counted rows/elapsed
            profile.reset_plan_metrics(phys)
            rows = 0
            for p in range(n):
                for b in phys.execute(p, ctx):
                    rows += 1
            return rows

        mode = self.config.trace()
        if mode != "off":
            # fetch/spill/compile events of this run join a fresh trace
            obs_trace.configure(mode)
            span_cm = obs_trace.span(
                "explain_analyze",
                trace_id=obs_trace.new_trace_id(),
                attrs={"sql": (sql or "")[:200]},
            )
        else:
            span_cm = contextlib.nullcontext()
        self._hints.load_once(self._capacity_hint, self._plan_cache)
        import os

        # per-operator attribution: Filter/Projection chains normally fuse
        # into one jitted program whose inner operators never execute
        # individually (exec/pipeline.py) — ANALYZE runs unfused so every
        # operator in the printed tree carries its own measured
        # rows/bytes/elapsed (the summary row says so; production timings
        # with fusion can only be equal or better). The env flag is
        # process-wide: the lock serializes concurrent ANALYZE runs (a
        # save/restore race could latch NO_FUSE on), and an unrelated
        # query whose chain FIRST executes inside this window runs
        # unfused — a transient perf effect, never a correctness one,
        # accepted for a deliberate profiling verb.
        t0 = _time.perf_counter()
        with _ANALYZE_LOCK:
            prev_no_fuse = os.environ.get("BALLISTA_TPU_NO_FUSE")
            os.environ["BALLISTA_TPU_NO_FUSE"] = "1"
            try:
                with span_cm:
                    run_with_capacity_retry(
                        self.config, run, hint=self._capacity_hint,
                        plan_cache=self._plan_cache,
                    )
            finally:
                if prev_no_fuse is None:
                    os.environ.pop("BALLISTA_TPU_NO_FUSE", None)
                else:
                    os.environ["BALLISTA_TPU_NO_FUSE"] = prev_no_fuse
        elapsed = _time.perf_counter() - t0
        with obs_trace.phase("task.hints_save"):
            self._hints.mark(self._capacity_hint, self._plan_cache)
        from ballista_tpu.scheduler.aqe import narrate as aqe_narrate

        rows = [
            ("physical_plan (analyzed)", profile.annotated_display(phys)),
            ("analyze_summary",
             f"total_elapsed={elapsed:.6f}s, fusion=off "
             "(per-operator attribution)"),
            # AQE narration (docs/aqe.md): the distributed query class
            # this statement maps to and the learned strategies a
            # cluster submission would apply from planning time
            ("aqe", aqe_narrate(self, optimized)),
        ]
        t = pa.table(
            {
                "plan_type": pa.array([r[0] for r in rows]),
                "plan": pa.array([r[1] for r in rows]),
            }
        )
        return DataFrame.from_arrow(self, t)

    def _verify_report(self, optimized: LogicalPlan, phys, sql: str) -> str:
        """EXPLAIN VERIFY body: run the logical + physical verifier passes
        over the ALREADY-planned physical tree (the same one VERBOSE
        displays) and render their reports; a verification failure becomes
        report text (EXPLAIN must not raise — it exists to show the
        diagnosis)."""
        from ballista_tpu.analysis import verify_logical, verify_physical
        from ballista_tpu.errors import PlanVerificationError

        lines = []
        try:
            lines.append(verify_logical(optimized, sql=sql).summary())
            lines.append(verify_physical(phys, sql=sql).summary())
        except PlanVerificationError as e:
            lines.append(f"FAILED: {e}")
        return "\n".join(lines)

    def _create_external_table(self, stmt: ast.CreateExternalTable) -> None:
        if stmt.name in self.tables:
            if stmt.if_not_exists:
                return
            raise PlanError(f"table {stmt.name!r} already exists")
        schema = None
        if stmt.columns is not None:
            from ballista_tpu.datatypes import Field

            schema = Schema(
                [Field(c.name, c.dtype, c.nullable) for c in stmt.columns]
            )
        if stmt.stored_as == "csv":
            self.register_csv(
                stmt.name, stmt.location, schema, stmt.has_header, stmt.delimiter
            )
        elif stmt.stored_as == "avro":
            self.register_avro(stmt.name, stmt.location)
        else:
            self.register_parquet(stmt.name, stmt.location)


class DataFrame:
    """Lazy query handle with a builder API (ref: DataFusion DataFrame via
    BallistaContext; the transformation surface mirrors the reference's
    Python bindings — select/filter/aggregate/sort/limit/join,
    ref:python/src/dataframe.rs:55-137). Each method returns a NEW frame
    over an extended logical plan; ``collect`` materializes. Works
    identically on the local TpuContext and the cluster BallistaContext
    (RemoteDataFrame inherits these and executes remotely)."""

    def __init__(self, ctx: TpuContext, logical: LogicalPlan):
        self.ctx = ctx
        self.logical = logical
        self._const: pa.Table | None = None
        # source SQL when this frame came from sql() — lets plan
        # verification diagnostics point at a line/column. Builder-derived
        # frames drop it (their plan no longer matches the text).
        self._sql: str | None = None

    # -- builder -------------------------------------------------------------
    def _derive(self, logical: LogicalPlan) -> "DataFrame":
        if self._const is not None:
            raise PlanError("cannot build on a constant result frame")
        return type(self)(self.ctx, logical)

    @staticmethod
    def _expr(e):
        from ballista_tpu.expr.logical import col_or_expr

        return col_or_expr(e)

    def schema(self) -> Schema:
        if self._const is not None:
            from ballista_tpu.columnar.arrow_interop import schema_from_arrow

            return schema_from_arrow(self._const.schema)
        return self.logical.schema()

    def select(self, *exprs) -> "DataFrame":
        from ballista_tpu.plan.logical import Projection

        return self._derive(
            Projection(self.logical, tuple(self._expr(e) for e in exprs))
        )

    def select_columns(self, *names: str) -> "DataFrame":
        return self.select(*names)

    def filter(self, predicate) -> "DataFrame":
        from ballista_tpu.plan.logical import Filter

        return self._derive(Filter(self.logical, self._expr(predicate)))

    where = filter

    def aggregate(self, group_by: list, aggs: list) -> "DataFrame":
        """Aggregates may be aliased (``F.sum("v").alias("total")``); the
        execution layer wants BARE aggregate expressions (the SQL planner
        renames through a projection, and so does this)."""
        from ballista_tpu.expr import logical as L
        from ballista_tpu.plan.logical import Aggregate, Projection

        groups = tuple(self._expr(e) for e in group_by)
        bare, out_names = [], []
        for e in aggs:
            e = self._expr(e)
            if isinstance(e, L.Alias):
                bare.append(e.expr)
                out_names.append(e.aname)
            else:
                bare.append(e)
                out_names.append(None)
        plan = Aggregate(self.logical, groups, tuple(bare))
        if any(n is not None for n in out_names):
            proj = [L.col(g.name()) for g in groups]
            for b, n in zip(bare, out_names):
                c = L.col(b.name())
                proj.append(c if n is None else c.alias(n))
            plan = Projection(plan, tuple(proj))
        return self._derive(plan)

    def sort(self, *exprs) -> "DataFrame":
        """Accepts ``col("x")`` (ascending), ``col("x").sort(False)``, or
        plan-level SortExpr values."""
        from ballista_tpu.plan.logical import Sort, SortExpr

        sort_exprs = []
        for e in exprs:
            if isinstance(e, SortExpr):
                sort_exprs.append(e)
            else:
                sort_exprs.append(self._expr(e).sort())
        return self._derive(Sort(self.logical, tuple(sort_exprs)))

    def limit(self, count: int, skip: int = 0) -> "DataFrame":
        from ballista_tpu.plan.logical import Limit

        return self._derive(Limit(self.logical, skip, count))

    def join(
        self,
        right: "DataFrame",
        join_keys: tuple[list[str], list[str]] | list[str],
        how: str = "inner",
    ) -> "DataFrame":
        """``join_keys`` is either ``(left_cols, right_cols)`` (the
        reference bindings' shape) or a single list of shared column
        names."""
        from ballista_tpu.plan.logical import Join, JoinType

        if (
            isinstance(join_keys, tuple)
            and len(join_keys) == 2
            and not isinstance(join_keys[0], str)
        ):
            lks, rks = list(join_keys[0]), list(join_keys[1])
            if len(lks) != len(rks):
                raise PlanError(
                    f"join_keys sides differ in length: {len(lks)} vs "
                    f"{len(rks)}"
                )
        else:
            lks = rks = list(join_keys)
        try:
            jt = JoinType(how)
        except ValueError:
            raise PlanError(f"unknown join type {how!r}") from None
        on = tuple(
            (self._expr(a), self._expr(b)) for a, b in zip(lks, rks)
        )
        return self._derive(Join(self.logical, right.logical, on, jt))

    def union(self, other: "DataFrame", all: bool = False) -> "DataFrame":
        from ballista_tpu.plan.logical import Distinct, Union

        u = Union((self.logical, other.logical), all=True)
        return self._derive(u if all else Distinct(u))

    def distinct(self) -> "DataFrame":
        from ballista_tpu.plan.logical import Distinct

        return self._derive(Distinct(self.logical))

    def alias(self, name: str) -> "DataFrame":
        from ballista_tpu.plan.logical import SubqueryAlias

        return self._derive(SubqueryAlias(self.logical, name))

    @classmethod
    def from_arrow(cls, ctx: TpuContext, table: pa.Table) -> "DataFrame":
        df = cls.__new__(cls)
        df.ctx = ctx
        df.logical = None
        df._const = table
        df._sql = None
        return df

    @classmethod
    def empty_ok(cls, ctx: TpuContext) -> "DataFrame":
        return cls.from_arrow(ctx, pa.table({"result": pa.array(["ok"])}))

    def collect(self) -> pa.Table:
        return self.collect_with_plan()[0]

    def collect_with_plan(self) -> tuple:
        """(table, executed physical plan). The plan handle lets callers
        read per-operator metrics of THIS run (spill bytes/passes,
        prefetch hits) after it completes — re-calling
        create_physical_plan would hand back a fresh tree with reset
        metrics. The out-of-core tests consume this; plain collect() is
        the (table-only) user surface."""
        if self._const is not None:
            return self._const, None
        phys = self.ctx.create_physical_plan(self.logical, sql=self._sql)
        part = phys.output_partitioning()
        n = part.n if isinstance(part, UnknownPartitioning) else part.n

        def run(ctx: TaskContext) -> list:
            out = []
            for p in range(n):
                for b in phys.execute(p, ctx):
                    rb = batch_to_arrow(b)
                    if rb.num_rows:
                        out.append(rb)
            return out

        # run_with_capacity_retry raises deferred device checks in one
        # batched fetch and, on aggregate-capacity overflow, re-runs the
        # plan with the capacity grown to the reported group count; the
        # context-level hint makes warm re-runs start at the grown size,
        # and the persisted hint file makes COLD runs start there too
        self.ctx._hints.load_once(
            self.ctx._capacity_hint, self.ctx._plan_cache
        )
        # cost accounting (docs/observability.md): wall/CPU measured
        # around the run plus a process compile-seconds delta (a DELTA,
        # not a claim — in-proc standalone clusters' executor tasks own
        # the exactly-once claim ledger), logged with the query-class
        # fingerprint into the local query log system.queries serves
        import time as _time

        accounting = self.ctx.config.cost_accounting()
        if accounting:
            from ballista_tpu.compilecache import metrics as compile_metrics

            t0, c0 = _time.perf_counter(), _time.thread_time()
            with compile_metrics.delta() as comp_d:
                record_batches = run_with_capacity_retry(
                    self.ctx.config, run, hint=self.ctx._capacity_hint,
                    plan_cache=self.ctx._plan_cache
                )
            try:
                self.ctx._log_local_query(
                    phys,
                    _time.perf_counter() - t0,
                    _time.thread_time() - c0,
                    float(comp_d.value.get("compile_seconds", 0.0)),
                )
            except Exception:  # noqa: BLE001 — the query log is
                # observability; it must never fail a collect
                log.exception("local query-log record failed")
        else:
            record_batches = run_with_capacity_retry(
                self.ctx.config, run, hint=self.ctx._capacity_hint,
                plan_cache=self.ctx._plan_cache
            )
        with obs_trace.phase("task.hints_save"):
            self.ctx._hints.mark(
                self.ctx._capacity_hint, self.ctx._plan_cache
            )
        if not record_batches:
            from ballista_tpu.columnar.arrow_interop import schema_to_arrow

            return pa.table(
                {
                    f.name: pa.array([], type=t.type)
                    for f, t in zip(
                        phys.schema(), schema_to_arrow(phys.schema())
                    )
                }
            ), phys
        return pa.Table.from_batches(record_batches), phys

    def to_pandas(self):
        return self.collect().to_pandas()

    def show(self, limit: int = 20) -> None:
        t = self.collect()
        print(t.slice(0, limit).to_pandas().to_string(index=False))

    def explain(self) -> str:
        return optimize(self.logical).display() if self.logical else "<const>"
