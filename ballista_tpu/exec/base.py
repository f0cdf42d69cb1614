"""ExecutionPlan protocol, partitioning, task context, metrics.

Mirrors the slice of DataFusion's physical-plan API the reference depends
on: `schema()`, `output_partitioning()`, `execute(partition)` streaming
record batches, and per-operator metrics
(`ExecutionPlanMetricsSet`, see SURVEY.md §5 Tracing — the reference's
ShuffleWriterExec records write_time/repart_time at shuffle_writer.rs:80-106).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Iterator

from ballista_tpu.columnar.batch import DeviceBatch
from ballista_tpu.config import BallistaConfig
from ballista_tpu.datatypes import Schema
from ballista_tpu.expr import logical as L


@dataclasses.dataclass(frozen=True)
class UnknownPartitioning:
    n: int


@dataclasses.dataclass(frozen=True, eq=False)
class HashPartitioning:
    exprs: tuple[L.Expr, ...]
    n: int


Partitioning = UnknownPartitioning | HashPartitioning


@dataclasses.dataclass
class TaskContext:
    """Per-task runtime state (the reference builds one from session props at
    executor/src/execution_loop.rs:146-167)."""

    config: BallistaConfig = dataclasses.field(default_factory=BallistaConfig)
    session_id: str = ""
    job_id: str = ""
    work_dir: str = ""
    # Adaptive retry: when a previous attempt overflowed the aggregate group
    # capacity, the retry runs with this override (wins over config/plan).
    agg_capacity_override: int | None = None
    # Deferred on-device error flags (bool scalars). Fetching a scalar is
    # a blocking host round-trip (cost not measured on the attached chip),
    # so capacity checks enqueue here and the task boundary fetches them
    # all in ONE device_get (raise_deferred) instead of one sync per
    # operator.
    deferred_checks: list = dataclasses.field(default_factory=list)
    # Cross-run plan-shape cache (join build-strategy flags, expansion
    # output capacities), owned by the context/executor and shared across
    # queries. Entries are SPECULATIVE: every use must queue a validation
    # flag via defer_speculation; a fired flag discards the run and the
    # driver retries without the stale entry.
    plan_cache: dict | None = None
    # validation flags for plan_cache entries: (flag, message, cache_keys)
    speculative_checks: list = dataclasses.field(default_factory=list)
    # (cache_key, device scalar) pairs written to plan_cache at a CLEAN
    # task boundary (see defer_learn)
    learned_values: list = dataclasses.field(default_factory=list)
    # callables run at a CLEAN task boundary only (see defer_commit)
    clean_commits: list = dataclasses.field(default_factory=list)
    # per-run scratch (e.g. which cache keys THIS run has already synced:
    # later batches of the same run must keep syncing/maxing, not
    # speculate against a value a smaller earlier batch just wrote)
    run_state: dict = dataclasses.field(default_factory=dict)
    # Join build-table caching lives on PLAN INSTANCES; callers whose
    # instances are per-task throwaways (the distributed executor decodes
    # a fresh plan per task) must turn it off, or the shared HBM tally
    # counts entries that die with the task and admission starves.
    cache_builds: bool = True
    # Lazily-created grace-hash spill manager (exec/spill.py); owned by the
    # attempt — run_with_capacity_retry closes it (deleting the files) at
    # every attempt boundary, so retries never see stale buckets.
    spill: object | None = None
    # Eager-shuffle location poller (docs/shuffle.md), injected by a
    # scheduler-connected executor: callable (job_id, stage_id, partition)
    # -> executor.reader.ShuffleLocationsView | None. None in local
    # contexts — eager ShuffleReaderExec plans refuse to run without it.
    shuffle_locations: object | None = None

    def spill_manager(self):
        """The attempt's SpillManager, created on first spill. Files land
        under the executor work_dir (shuffle-TTL-swept if the process
        dies) or the shared temp spill root for local contexts; an
        explicit ballista.tpu.spill_dir overrides both."""
        if self.spill is None:
            import os

            from ballista_tpu.exec.spill import SpillManager

            base = self.config.spill_dir() or None
            if base is None and self.work_dir:
                base = os.path.join(
                    self.work_dir, self.job_id or "local", "spill"
                )
            self.spill = SpillManager(
                base, self.config.spill_budget_mb() << 20
            )
        return self.spill

    def close_spills(self) -> None:
        if self.spill is not None:
            self.spill.close()
            self.spill = None

    def _start_async_copy(self, *values) -> None:
        """Start a device->host copy of each scalar NOW so raise_deferred's
        resolution overlaps the run's final result fetch instead of paying
        its own blocking round trip. Best-effort: a platform without
        async copies falls back to the batched fetch."""
        if self.run_state.get("_async_copy_bad"):
            return
        for v in values:
            if v is None or isinstance(v, (bool, int, float)):
                continue  # host-native: nothing to copy
            try:
                copy = getattr(v, "copy_to_host_async", None)
                if copy is not None:
                    copy()
                elif hasattr(v, "__array__") and type(v).__module__ not in (
                    "numpy",
                ):
                    # a device array WITHOUT async copies: per-value
                    # resolution would pay one round trip each — keep the
                    # batched fetch path instead
                    self.run_state["_async_copy_bad"] = True
                    return
            except Exception:
                self.run_state["_async_copy_bad"] = True
                return

    def defer_check(self, flag, message: str, required=None) -> None:
        """Queue a device bool ``flag``; if it fires at the task boundary the
        task fails with ``message``. ``required`` (device int scalar) is the
        capacity that would have sufficed — carried on the raised
        CapacityError so the driver can retry adaptively."""
        self._start_async_copy(flag, required)
        self.deferred_checks.append((flag, message, required))

    def defer_speculation(self, flag, message: str, cache_keys: list) -> None:
        """Queue a device bool validating a plan_cache speculation; if it
        fires, the task raises SpeculationMiss carrying ``cache_keys`` so
        the driver can invalidate and re-run. Rides the same single batched
        fetch as defer_check — zero extra round trips."""
        self._start_async_copy(flag)
        self.speculative_checks.append((flag, message, list(cache_keys)))

    def defer_learn(self, cache_key, value) -> None:
        """Queue a device scalar whose value should be LEARNED into the
        plan cache at the task boundary (rides the same batched fetch as
        defer_check). Values for the same key are AND-ed for bools /
        max-ed for ints across the run's batches; nothing is written if
        the run fails its checks."""
        if self.plan_cache is not None:
            self._start_async_copy(value)
            self.learned_values.append((cache_key, value))

    def defer_commit(self, fn) -> None:
        """Queue a host-side cache mutation to run ONLY if this task ends
        clean. A run that fails a deferred check (capacity overflow,
        speculation miss) may have computed results from truncated
        intermediates — committing caches mid-run would poison retries
        with data the failed attempt produced (observed: a SEMI build
        table cached from an overflowed HAVING subquery)."""
        self.clean_commits.append(fn)

    def raise_deferred(self) -> None:
        if (
            not self.deferred_checks
            and not self.speculative_checks
            and not self.learned_values
            and not self.clean_commits
        ):
            return
        from ballista_tpu.errors import (
            CapacityError,
            ExecutionError,
            SpeculationMiss,
        )
        from ballista_tpu.ops.fetch import fetch_arrays

        import jax.numpy as jnp

        n = len(self.deferred_checks)
        ns = len(self.speculative_checks)
        # keep host-native values (python ints/bools) OUT of the device
        # path: wrapping them in jnp.asarray would mint fresh device
        # scalars whose resolution costs a round trip each
        queued = (
            [f for f, _, _ in self.deferred_checks]
            + [r if r is not None else 0 for _, _, r in self.deferred_checks]
            + [f for f, _, _ in self.speculative_checks]
            + [v for _, v in self.learned_values]
        )
        if not self.run_state.get("_async_copy_bad"):
            # every queued device scalar started its host copy at queue
            # time (_start_async_copy) and the run's result fetch has
            # since drained the device queue, so these resolve without a
            # fresh round trip each
            import numpy as _np

            from ballista_tpu.obs import trace as obs_trace

            with obs_trace.phase("task.d2h", site="deferred_checks") as ph:
                fetched = [_np.asarray(v) for v in queued]
                ph.nbytes = sum(v.nbytes for v in fetched)
        else:
            fetched = fetch_arrays(
                [jnp.asarray(v) for v in queued], site="deferred_checks"
            )
        flags, reqs = fetched[:n], fetched[n : 2 * n]
        spec_flags = fetched[2 * n : 2 * n + ns]
        learned = fetched[2 * n + ns :]
        checks = self.deferred_checks
        spec_checks = self.speculative_checks
        learn_entries = self.learned_values
        commits = self.clean_commits
        self.deferred_checks = []
        self.speculative_checks = []
        self.learned_values = []
        self.clean_commits = []
        # speculation misses first: the run's output is invalid regardless
        # of what the hard checks say (a stale strategy can mask them)
        spec_fired = [
            (m, keys)
            for (f_, m, keys), f in zip(spec_checks, spec_flags)
            if bool(f)
        ]
        if spec_fired:
            invalid = [k for _, keys in spec_fired for k in keys]
            raise SpeculationMiss(
                "; ".join(dict.fromkeys(m for m, _ in spec_fired)),
                invalid_keys=invalid,
            )
        fired = [
            (m, int(r))
            for (f_, m, req), f, r in zip(checks, flags, reqs)
            if bool(f)
        ]
        if not fired:
            for fn in commits:
                fn()
            # clean run: commit learned plan-shape facts (AND for bools so
            # one unsorted batch at a site vetoes the clustered fast path;
            # max for ints so capacities cover every batch)
            if self.plan_cache is not None:
                for (key, _), val in zip(learn_entries, learned):
                    v = val.item() if hasattr(val, "item") else val
                    prev = self.plan_cache.get(key)
                    if isinstance(v, bool) or str(getattr(val, "dtype", "")) == "bool":
                        v = bool(v)
                        self.plan_cache[key] = (
                            v if prev is None else (prev and v)
                        )
                    else:
                        v = int(v)
                        if (
                            isinstance(key, tuple)
                            and key
                            and key[0] == "dec_sum_last"
                        ):
                            # merge-site decimal scales REPLACE rather than
                            # max: the first run's merge inputs are inexact
                            # (plain-float partials) and would otherwise
                            # veto forever; each run re-learns from its own
                            # inputs until they are exact
                            self.plan_cache[key] = v
                        else:
                            self.plan_cache[key] = (
                                v if prev is None else max(prev, v)
                            )
            return
        msg = "; ".join(dict.fromkeys(m for m, _ in fired))
        required = max((r for _, r in fired), default=0)
        if any(req is not None for (_, _, req), f in zip(checks, flags) if bool(f)):
            raise CapacityError(msg, required=required)
        raise ExecutionError(msg)


# Hard ceiling for adaptive aggregate-capacity growth (groups). 32M groups
# x ~8B per state column is a few hundred MB of state on a 16GB chip, and
# the sort-based grouping's transients stay low-GB at that size — SF=100
# q18 (60M distinct orderkeys per 4-way partition) is the sizing case.
# Beyond it the query needs a hash-repartitioned (multi-partition)
# aggregate instead.
AGG_CAPACITY_HARD_MAX = 1 << 25

# Guards the process-global JAX profiler (see run_with_capacity_retry).
import threading as _threading  # noqa: E402

_PROFILER_LOCK = _threading.Lock()

# Bound for a long-lived plan-strategy cache (executor lifetime spans its
# whole job history; parameterized query streams mint fresh keys forever).
PLAN_CACHE_MAX_ENTRIES = 4096

# Keys eviction must never remove: the shared HBM tally for instance-held
# join build tables is an accounting cell, not a learned strategy.
_PLAN_CACHE_STICKY = ("__build_cache_bytes__",)


def evict_plan_cache(
    plan_cache: dict,
    pinned=(),
    max_entries: int = PLAN_CACHE_MAX_ENTRIES,
) -> int:
    """Bound ``plan_cache`` by evicting oldest-first (dict insertion
    order), down to half of ``max_entries`` so eviction amortizes instead
    of firing per insert. ``pinned`` keys survive: a task running against
    a job snapshot must not lose the entries that snapshot was taken
    from mid-attempt (the commit-back ``update`` would resurrect them
    anyway, but the flush/resurrect churn defeats the learned-strategy
    warm start). Returns the number of entries evicted; meters
    ``plan_cache_flush`` / ``plan_cache_evicted`` so soak runs can see
    cache pressure instead of silent drops."""
    if len(plan_cache) <= max_entries:
        return 0
    keep = set(pinned)
    keep.update(_PLAN_CACHE_STICKY)
    target = max_entries // 2
    evicted = 0
    for k in list(plan_cache):
        if len(plan_cache) <= target:
            break
        if k in keep:
            continue
        del plan_cache[k]
        evicted += 1
    if evicted:
        from ballista_tpu.compilecache import metrics

        metrics.add("plan_cache_flush")
        metrics.add("plan_cache_evicted", evicted)
    return evicted


def run_with_capacity_retry(
    config: BallistaConfig,
    fn,
    hint: dict | None = None,
    plan_cache: dict | None = None,
    pinned_cache_keys=(),
    **ctx_fields,
):
    """Centralized execution driver: build a TaskContext, run ``fn(ctx)``,
    raise any deferred device checks, and on a CapacityError retry with the
    capacity grown to fit (exact when the kernel reported the true group
    count, else doubled). Every entry point that executes plans —
    DataFrame.collect, the executor's shuffle-write task, the mesh runner —
    routes through here so the deferred-check invariant cannot be missed
    (a forgotten raise_deferred would silently truncate results).

    ``hint``: a caller-owned mutable dict remembering the capacity a
    previous run grew to (key ``"agg_capacity"``) — warm re-runs of the
    same workload then start at the working capacity instead of paying the
    overflow+retry round every time."""
    from ballista_tpu.errors import CapacityError, SpeculationMiss

    override: int | None = (hint or {}).get("agg_capacity")
    if override is not None and override <= config.agg_capacity():
        override = None
    if plan_cache is not None:
        # bound a long-lived executor's cache across its job history —
        # oldest-first, never the entries the current job's snapshot is
        # pinned to (``pinned_cache_keys``)
        evict_plan_cache(plan_cache, pinned=pinned_cache_keys)
    spec_misses = 0
    while True:
        ctx = TaskContext(
            config=config,
            agg_capacity_override=override,
            plan_cache=plan_cache,
            **ctx_fields,
        )
        try:
            profile_dir = config.profile_dir()
            # the JAX profiler is process-global (one active trace); with
            # concurrent executor tasks only the first gets traced, the
            # rest run unprofiled rather than failing
            if profile_dir and _PROFILER_LOCK.acquire(blocking=False):
                try:
                    # SURVEY §5 tracing: device-time profiling via the
                    # XLA/JAX profiler, wrapping exactly one task attempt
                    # (TensorBoard reads the trace dir)
                    import jax

                    with jax.profiler.trace(profile_dir):
                        out = fn(ctx)
                finally:
                    _PROFILER_LOCK.release()
            else:
                out = fn(ctx)
            ctx.raise_deferred()
            if override is not None and hint is not None:
                hint["agg_capacity"] = max(
                    hint.get("agg_capacity", 0), override
                )
            return out
        except SpeculationMiss as e:
            # a cached plan-shape guess went stale: invalidate + re-run
            ctx.deferred_checks.clear()
            ctx.speculative_checks.clear()
            ctx.clean_commits.clear()
            if plan_cache is not None:
                for k in e.invalid_keys:
                    plan_cache.pop(k, None)
            spec_misses += 1
            if spec_misses > 3:  # each retry removes its stale entries;
                # >3 means something re-poisons the cache every run
                raise
        except CapacityError as e:
            ctx.deferred_checks.clear()
            ctx.speculative_checks.clear()
            ctx.clean_commits.clear()
            # the whole of ``fn`` runs again: a window that counts one of
            # these ran a task twice (docs/observability.md)
            from ballista_tpu.compilecache import metrics

            metrics.add("agg.capacity_retries")
            base = override or config.agg_capacity()
            need = max(e.required + 1, base * 2)
            # grown capacities snap to the capacity-bucket ladder: an
            # adaptive retry then lands on the same compiled-program
            # signature as every other operator at that bucket instead of
            # minting a fresh power-of-two vocabulary entry
            # (docs/compile_cache.md)
            from ballista_tpu.columnar.batch import round_capacity

            new_cap = round_capacity(need)
            if need <= AGG_CAPACITY_HARD_MAX < new_cap:
                # a coarse ladder (e.g. 2048:3) can overshoot the hard
                # max on a need the old pow2 growth served; the clamped
                # capacity is off-ladder but the retry still succeeds
                new_cap = AGG_CAPACITY_HARD_MAX
            if new_cap > AGG_CAPACITY_HARD_MAX or (
                override is not None and new_cap <= override
            ):
                raise
            override = new_cap
        finally:
            # grace-hash spill files are attempt-scoped: every exit from
            # an attempt (success, retry, failure) deletes them so a retry
            # never reads a previous attempt's buckets and a long-lived
            # executor never accretes spill data
            ctx.close_spills()


class Metrics:
    """Per-operator counters/timers (ref: DataFusion metrics sets)."""

    def __init__(self) -> None:
        self.counters: dict[str, int] = {}
        self.timers: dict[str, float] = {}

    def add(self, name: str, v: int = 1) -> None:
        # a counter's first value is kept as it comes: ``0 + v`` of a device
        # scalar would be one more program dispatched
        had = self.counters.get(name)
        self.counters[name] = v if had is None else had + v

    def reset(self) -> None:
        self.counters.clear()
        self.timers.clear()

    def time(self, name: str):
        return _Timer(self, name)

    def summary(self) -> dict[str, float]:
        """Resolved counters + timers in STABLE form: keys sorted (dict
        insertion order followed recording order, so two runs of the same
        query could render differently — flaky test assertions and noisy
        diffs), counters as python ints/floats (device scalars recorded
        without syncing on the hot path resolve here, at report time),
        timers always float seconds rounded to microsecond precision."""
        out: dict[str, float] = dict(self.counters)
        import jax

        lazy = [k for k, v in out.items() if isinstance(v, jax.Array)]
        if lazy:
            from ballista_tpu.obs import trace as obs_trace

            # one blocking read per device scalar still unresolved
            with obs_trace.phase("task.d2h", site="operator_metrics"):
                for k in lazy:
                    out[k] = int(out[k])
        for k, v in out.items():
            if not isinstance(v, (int, float)):
                out[k] = int(v)  # numpy scalars
        out.update({k: round(float(v), 6) for k, v in self.timers.items()})
        return dict(sorted(out.items()))

    def format(self) -> str:
        """Pinned display form (tests assert on it verbatim): sorted
        ``k=v`` pairs, timers with an ``s`` suffix so a counter named like
        a timer cannot be misread as one."""
        s = self.summary()
        parts = [
            f"{k}={v}s" if k in self.timers else f"{k}={v}"
            for k, v in s.items()
        ]
        return "[" + ", ".join(parts) + "]"


def plan_counters(plan, names) -> dict[str, int]:
    """Sum the named metric counters over a whole plan tree — the most
    recent run's values (collect resets per-operator metrics per query).
    The out-of-core/prefetch reporting surface of the out-of-core
    tests, via DataFrame.collect_with_plan."""
    out = {n: 0 for n in names}

    def walk(p) -> None:
        for n in names:
            v = p.metrics.counters.get(n)
            if v is not None:
                out[n] += int(v)
        for c in p.children():
            walk(c)

    walk(plan)
    return out


class _Timer:
    def __init__(self, m: Metrics, name: str):
        self.m = m
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.m.timers[self.name] = self.m.timers.get(self.name, 0.0) + (
            time.perf_counter() - self.t0
        )
        return False


class ExecutionPlan:
    """Base physical operator. Subclasses implement ``execute`` returning an
    iterator of DeviceBatch for one output partition."""

    def __init__(self) -> None:
        self.metrics = Metrics()

    def schema(self) -> Schema:
        raise NotImplementedError

    def children(self) -> list["ExecutionPlan"]:
        return []

    def output_partitioning(self) -> Partitioning:
        return UnknownPartitioning(1)

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[DeviceBatch]:
        raise NotImplementedError

    # -- display -------------------------------------------------------------
    def describe(self) -> str:
        return type(self).__name__

    def display(self, with_metrics: bool = False) -> str:
        lines: list[str] = []

        def walk(node: "ExecutionPlan", depth: int) -> None:
            line = "  " * depth + node.describe()
            if with_metrics and (node.metrics.counters or node.metrics.timers):
                line += f"  metrics={node.metrics.format()}"
            lines.append(line)
            for c in node.children():
                walk(c, depth + 1)

        walk(self, 0)
        return "\n".join(lines)


def replace_children(
    plan: ExecutionPlan, children: list["ExecutionPlan"]
) -> ExecutionPlan:
    """THE sanctioned child-rebind primitive: rebuild an operator with new
    children, mutating the known child slots in place when identity
    changed. Every structural plan mutation in the tree must route through
    here or through the certified rewrite API (ballista_tpu/rewrite.py) —
    the eqlint no-uncertified-mutation rule (analysis/eqlint.py) flags
    direct plan-field writes anywhere else. Callers that need
    copy-on-write semantics pass a ``copy.copy`` of ``plan``
    (distributed_plan.remove_unresolved_shuffles, rewrite._rebuild)."""
    from ballista_tpu.errors import PlanError

    old = plan.children()
    if len(old) != len(children):
        raise PlanError("child arity mismatch")
    if all(a is b for a, b in zip(old, children)):
        return plan
    # mutate the known child slots
    if hasattr(plan, "input") and len(children) == 1:
        plan.input = children[0]
        return plan
    if hasattr(plan, "left") and len(children) == 2:
        plan.left, plan.right = children
        return plan
    if hasattr(plan, "inputs"):
        plan.inputs = list(children)
        return plan
    raise PlanError(f"cannot rebuild {type(plan).__name__} with new children")


def execute_to_batches(
    plan: ExecutionPlan, ctx: TaskContext
) -> list[DeviceBatch]:
    """Run every output partition of a plan and collect the batches (the
    reference's ``collect_stream``, core/src/utils.rs:95)."""
    part = plan.output_partitioning()
    n = part.n if isinstance(part, UnknownPartitioning) else part.n
    out: list[DeviceBatch] = []
    for p in range(n):
        out.extend(plan.execute(p, ctx))
    return out
