"""Compile-latency observability: process-wide counters over jax's
monitoring events.

Cold-start work is invisible in operator metrics — tracing and XLA
compilation happen inside jit dispatch, not inside any ExecutionPlan — so
this module taps ``jax.monitoring`` (the same event stream jax's own
telemetry uses) and keeps process-global counters:

- ``traces`` / ``trace_seconds`` — jaxpr traces (every distinct
  (kernel, shape, dtype, static-arg) signature traces once per process;
  the count is the live measure of the compiled-program vocabulary).
- ``backend_compiles`` / ``compile_seconds`` — XLA backend compile
  REQUESTS and the wall time spent inside them (persistent-cache hits
  still pass through here, cheaply).
- ``persistent_cache_hits`` / ``persistent_cache_misses`` — the on-disk
  XLA cache (docs/compile_cache.md): a miss is a real XLA compile.
- ``cache_retrieval_seconds`` — time spent deserializing cached
  executables (the cost floor of a cache-hit cold start).
- ``jit_cache_hits`` / ``jit_cache_misses`` — the shared jitted-callable
  cache (compilecache.tracecache), recorded by that module.
- ``prewarmed_signatures`` / ``prewarm_seconds`` — AOT prewarm progress
  (compilecache.prewarm).
- ``phase.<name>.seconds`` / ``.count`` / ``.bytes`` — the host phases of
  the served path (``obs.trace.phase``, docs/observability.md), and for
  ``task.d2h`` the same three per call site (``...:<site>``).
- ``agg.capacity_retries`` / ``agg.sort_passes`` / ``agg.dense_passes`` /
  ``agg.dense_factored_passes`` / ``agg.groups_out`` — the grouped
  aggregate (docs/observability.md): tasks run again after a
  ``CapacityError``, device passes dispatched by kind (and of the dense
  ones, those whose counts and integer sums took the factorized one-hot),
  rows the final aggregates emitted. Declared at 0, so that a
  reader tells "none" from "a program without the counter".
- ``holistic.tasks`` / ``holistic.rows_sorted`` / ``holistic.sort_passes``
  — the window and percentile operators (docs/observability.md): tasks
  that ran one, the live rows of every sort they dispatched, the argsort
  passes of those sorts. Declared at 0 likewise.
- ``dict_predicate.entries`` / ``dict_predicate.reused`` — string predicates
  over dictionaries (``columnar/dict_util.py predicate_table``): entries
  walked by the evaluations of ``LIKE``, ``substr`` and string ``IN``
  tables, and tables taken from the dictionary's memo instead. Declared at
  0 likewise.
- ``join.noninner.tasks`` / ``join.noninner.probe_rows`` /
  ``join.noninner.unmatched_rows`` — ``LEFT``, ``SEMI`` and ``ANTI`` joins
  (``exec/joins.py``): tasks that ran one, the live rows of their preserved
  side, and those an outer or anti join emitted without a match. Declared
  at 0 likewise.
- ``join.builds`` / ``join.build_rows`` / ``join.probe_rows`` /
  ``join.key_remaps`` — every ``HashJoinExec`` (``exec/joins.py``): probe
  tables built (``build_side``, rebuilds after a dictionary remap
  included), their live rows, the live probe rows of every join kind (of
  which ``join.noninner.probe_rows`` is the part that is not inner), and
  probe batches whose dictionary unification changed the build side.
  ``join.build_gather_bytes`` / ``join.builds_in_place`` — the bytes each
  build's finisher gathered through its sort's permutation (the key columns:
  static capacity x itemsize, no device read; and a payload gathered into
  sorted order for a probe batch or an expansion output larger than the
  build), and the builds
  whose payload stayed in arrival order (``ops/join.py BuildTable``,
  ``exec/joins.py HashJoinExec._rows_for``). Summed from the
  operators' metrics as a task ends; declared at 0 likewise.
- ``poll.rpcs`` / ``poll.wakes_by_status`` (executor) and ``poll.holds`` /
  ``poll.holds_granted`` / ``poll.holds_timed_out`` (scheduler) — the pull
  loop's hand-off (docs/observability.md): ``PollWork`` calls sent, waits
  between polls that a finished task ended, idle executors' polls the
  scheduler held, and those that ended in a grant or at the bound.
  Declared at 0 likewise.
- ``status.rpcs`` / ``status.holds`` / ``status.holds_ended_by_status`` /
  ``status.holds_timed_out`` / ``status.holds_over_budget`` (scheduler) —
  the client's hand-off (docs/observability.md): ``GetJobStatus`` calls
  served, those held for a job that had not ended, and the holds its end
  released, that ran out at the bound, or that the budget of held calls
  had no room for. Declared at 0 likewise.
- ``hints.marks`` / ``hints.writes_skipped_unchanged`` /
  ``hints.entries_job_scoped_skipped`` / ``hints_saved`` / ``hints_loaded``
  — the plan-hint store (compilecache/hints.py): tasks and collects that
  marked it, writer passes that found the fingerprint where it was,
  entries a pass left out because their key carries a job id (summed over
  passes), files written, entries merged at load. The first three and the
  writer's phase seconds are declared at 0 likewise.
- ``shuffle.split_batches`` / ``shuffle.split_device_ordered`` — the
  hash exchange's writer (``executor/shuffle.py split_batch``): batches it
  split among two or more output partitions, and of them those whose
  bucket order came from the device's compaction. Declared at 0 likewise.
- ``op.<family>.self_seconds`` — the operators' own time on their task
  threads (``obs.trace.stretch``: each operator's ``self_s``), summed by
  ``OP_FAMILIES`` as a task ends. Declared at 0 likewise.
- ``subquery.agg_rows`` / ``subquery.agg_groups`` /
  ``subquery.agg_self_seconds`` — the aggregates that decorrelate a scalar
  subquery (``HashAggregateExec`` marked ``subquery``): live rows into
  their partials, groups out of their finals, and their ``self_s``, which
  ``op.aggregate.self_seconds`` counts too; summed as a task ends.
  ``subquery.agg_reduced`` — +1 a task that ran such a partial over an
  input cut by its reduction (the semi join to the outer query's key
  domain, ``HashJoinExec`` marked ``reduction``). Declared at 0 likewise.

Counters surface per executor through the heartbeat -> scheduler REST
path (docs/compile_cache.md).
"""

from __future__ import annotations

import threading

_LOCK = threading.Lock()
AGG_COUNTERS = (
    "agg.capacity_retries", "agg.sort_passes", "agg.dense_passes",
    "agg.dense_factored_passes", "agg.groups_out",
)
# the operators that need every row of a group in one place (exec/window.py,
# exec/percentile.py), summed from their metrics as a task ends
HOLISTIC_COUNTERS = (
    "holistic.tasks", "holistic.rows_sorted", "holistic.sort_passes",
)
# string predicates over dictionaries (columnar/dict_util.py): entries
# evaluated, and tables reused from a dictionary's memo
DICT_PREDICATE_COUNTERS = ("dict_predicate.entries", "dict_predicate.reused")
# the joins that preserve a side (exec/joins.py LEFT, SEMI, ANTI), summed
# from their metrics as a task ends
NONINNER_JOIN_COUNTERS = (
    "join.noninner.tasks", "join.noninner.probe_rows",
    "join.noninner.unmatched_rows",
)
# every hash join (exec/joins.py HashJoinExec), summed from its metrics as a
# task ends: the operator's metric of each, by counter
JOIN_COUNTERS = {
    "join.builds": "builds", "join.build_rows": "build_rows",
    "join.probe_rows": "probe_rows", "join.key_remaps": "key_remaps",
    "join.build_gather_bytes": "build_gather_bytes",
    "join.builds_in_place": "builds_in_place",
}
# the pull loop's hand-off (docs/serving.md): polls sent and waits ended by
# a finished task (executor); polls held, and how a hold ended (scheduler)
POLL_COUNTERS = (
    "poll.rpcs", "poll.wakes_by_status", "poll.holds", "poll.holds_granted",
    "poll.holds_timed_out",
)
# the client's hand-off (docs/serving.md): status calls served and held,
# and how a hold ended (scheduler)
STATUS_COUNTERS = (
    "status.rpcs", "status.holds", "status.holds_ended_by_status",
    "status.holds_timed_out", "status.holds_over_budget",
)
# the plan-hint store's hand-off to its writer (compilecache/hints.py), with
# the writer's phase: 0 and not absent in a process whose writer never woke
HINT_COUNTERS = (
    "hints.marks", "hints.writes_skipped_unchanged",
    "hints.entries_job_scoped_skipped", "phase.executor.hints_write.seconds",
)
# the hash exchange's split of a batch (executor/shuffle.py split_batch):
# batches split, and those the device put in bucket order
SHUFFLE_COUNTERS = ("shuffle.split_batches", "shuffle.split_device_ordered")
# operator classes by family, for the operators' own time
# (obs.trace.stretch): a class listed nowhere is "other"
OP_FAMILIES = {
    "scan": ("MemoryScanExec", "ParquetScanExec", "CsvScanExec",
             "AvroScanExec"),
    "pipeline": ("FilterExec", "ProjectionExec", "RenameExec",
                 "CoalescePartitionsExec", "GlobalLimitExec", "UnionExec",
                 "EmptyExec"),
    "aggregate": ("HashAggregateExec", "MeshAggregateExec"),
    "join": ("HashJoinExec", "CrossJoinExec", "MeshJoinExec"),
    "holistic": ("SortExec", "WindowExec", "PercentileExec", "MeshSortExec",
                 "MeshWindowExec"),
    "exchange": ("HashRepartitionExec", "ShuffleReaderExec",
                 "ShuffleWriterExec"),
    "other": (),
}
OP_COUNTERS = tuple(f"op.{family}.self_seconds" for family in OP_FAMILIES)
# the aggregates that decorrelate a scalar subquery (exec/aggregate.py
# HashAggregateExec.subquery), summed from their metrics as a task ends
SUBQUERY_COUNTERS = (
    "subquery.agg_rows", "subquery.agg_groups", "subquery.agg_self_seconds",
    "subquery.agg_reduced",
)
_OP_COUNTER = {
    operator: f"op.{family}.self_seconds"
    for family, operators in OP_FAMILIES.items() for operator in operators
}


def op_counter(operator: str) -> str:
    """The ``op.<family>.self_seconds`` counter of an operator class name."""
    return _OP_COUNTER.get(operator, "op.other.self_seconds")


_COUNTERS: dict[str, float] = dict.fromkeys(
    AGG_COUNTERS + HOLISTIC_COUNTERS + DICT_PREDICATE_COUNTERS
    + NONINNER_JOIN_COUNTERS + tuple(JOIN_COUNTERS) + POLL_COUNTERS
    + STATUS_COUNTERS + HINT_COUNTERS + SHUFFLE_COUNTERS + OP_COUNTERS
    + SUBQUERY_COUNTERS, 0
)
_INSTALLED = False

# jax monitoring event -> (counter incremented per event, duration-sum
# counter or None). Count events exist for both listener kinds; duration
# events arrive only on the duration listener.
_EVENT_COUNTERS = {
    "/jax/core/compile/jaxpr_trace_duration": ("traces", "trace_seconds"),
    "/jax/core/compile/backend_compile_duration": (
        "backend_compiles", "compile_seconds",
    ),
    "/jax/compilation_cache/cache_hits": ("persistent_cache_hits", None),
    "/jax/compilation_cache/cache_misses": ("persistent_cache_misses", None),
    "/jax/compilation_cache/cache_retrieval_time_sec": (
        None, "cache_retrieval_seconds",
    ),
}


def add(name: str, value: float = 1) -> None:
    """Record a counter increment (used by tracecache/prewarm too)."""
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + value


def add_many(pairs) -> None:
    """Several increments under one lock acquisition (obs.trace.phase)."""
    with _LOCK:
        for name, value in pairs:
            _COUNTERS[name] = _COUNTERS.get(name, 0) + value


def _on_event(event: str, **kw) -> None:
    counter, _ = _EVENT_COUNTERS.get(event, (None, None))
    if counter is not None:
        add(counter)


def _on_duration(event: str, duration: float, **kw) -> None:
    counter, seconds = _EVENT_COUNTERS.get(event, (None, None))
    if counter is not None:
        add(counter)
    if seconds is not None:
        add(seconds, duration)


def install() -> None:
    """Register the jax.monitoring listeners (idempotent; listeners are
    append-only in jax, so double-registration would double-count)."""
    global _INSTALLED
    with _LOCK:
        if _INSTALLED:
            return
        import jax.monitoring

        # register under the lock so a concurrent caller cannot observe
        # _INSTALLED and proceed before the listeners actually exist.
        # count-only events fire the plain listener; duration events fire
        # the duration listener (NOT both) — no double-counting
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _INSTALLED = True


def snapshot() -> dict[str, float]:
    """Current counters (rounded; installs listeners on first use so a
    metrics reader never sees a silently-uninstrumented process)."""
    install()
    with _LOCK:
        return {
            k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in sorted(_COUNTERS.items())
        }


class delta:
    """Context manager capturing the counter delta across a block::

        with metrics.delta() as d:
            run_query()
        d.value["traces"]  # signatures traced by run_query
    """

    def __enter__(self) -> "delta":
        self._before = snapshot()
        self.value: dict[str, float] = {}
        return self

    def __exit__(self, *exc) -> bool:
        after = snapshot()
        self.value = {
            k: round(v - self._before.get(k, 0), 4)
            for k, v in after.items()
            if v != self._before.get(k, 0)
        }
        return False
