"""Hash-join operator (COLLECT build side + streamed probe).

ref: HashJoinExecNode with PartitionMode COLLECT_LEFT / PARTITIONED
(ballista.proto:474-487, serde physical_plan mod.rs:438-523). Here the
build side is always collected (broadcast within a process; the distributed
planner repartitions both sides first for PARTITIONED mode), sorted once by
packed key, and probed with the vectorized binary-search kernel.

Build-side choice: the preserved/probe side is fixed for LEFT/SEMI/ANTI
(the left input is probe); for INNER the operator builds the right side and
falls back to building the left if the right has duplicate keys (PK-FK
detection at runtime, since there are no table statistics yet).
"""

from __future__ import annotations

import functools
import threading
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from ballista_tpu.columnar.batch import DeviceBatch
from ballista_tpu.columnar.dict_util import merge_dictionaries, remap_codes
from ballista_tpu.datatypes import DataType, Field, Schema
from ballista_tpu.errors import ExecutionError, PlanError
from ballista_tpu.exec.base import ExecutionPlan, TaskContext
from ballista_tpu.expr import logical as L
from ballista_tpu.expr.physical import compile_expr
from ballista_tpu.columnar.batch import round_capacity
from ballista_tpu.ops.compact import compact
from ballista_tpu.ops.concat import concat_batches
from ballista_tpu.ops.fetch import read_array
from ballista_tpu.ops.join import (
    JoinSide,
    build_side,
    expand_join,
    probe_counts,
    probe_side,
)
from ballista_tpu.plan.logical import JoinType


def _collect(plan: ExecutionPlan, ctx: TaskContext) -> DeviceBatch:
    batches = []
    part = plan.output_partitioning()
    for p in range(part.n):
        batches.extend(plan.execute(p, ctx))
    if not batches:
        return DeviceBatch.empty(plan.schema())
    return concat_batches(batches)


def _collect_partition(
    plan: ExecutionPlan, ctx: TaskContext, partition: int
) -> DeviceBatch:
    """PARTITIONED-mode build collection: only this task's hash bucket."""
    batches = list(plan.execute(partition, ctx))
    if not batches:
        return DeviceBatch.empty(plan.schema())
    return concat_batches(batches)


def _probe_rows(rows, pb: DeviceBatch):
    """The operator's running count of live probe rows after ``pb``
    (``join.probe_rows``, docs/observability.md). Every program that probes
    a batch carries it in and out, so counting dispatches nothing."""
    return rows + jnp.sum(pb.valid, dtype=jnp.int64)


# build_side host-composes cached sort passes (wrapping it in another jit
# would re-inline the sorts into one slow-compiling program — don't); the
# probe is a single fast-compiling program per shape.
@functools.lru_cache(maxsize=None)
def _jit_probe(probe_keys: tuple, kind: JoinSide, contiguous: bool = False):

    def join_probe(bt, pb, rows):
        out = probe_side(
            bt, pb, list(probe_keys), kind, contiguous=contiguous
        )
        return out, _probe_rows(rows, pb)

    return jax.jit(join_probe)


@functools.lru_cache(maxsize=None)
def _jit_counts(probe_keys: tuple):

    def join_probe_counts(bt, pb, rows):
        return probe_counts(bt, pb, list(probe_keys)), _probe_rows(rows, pb)

    return jax.jit(join_probe_counts)


@functools.lru_cache(maxsize=None)
def _jit_expand_total(preserve_probe: bool):
    """Output rows the expansion will need (host-fetched for sizing)."""

    def join_expand_total(pb, count):
        if preserve_probe:  # LEFT: unmatched live probe rows emit one row
            eff = jnp.where(pb.valid, jnp.maximum(count, 1), 0)
        else:
            eff = count
        return jnp.sum(eff)

    return jax.jit(join_expand_total)


@functools.lru_cache(maxsize=None)
def _jit_unmatched(build_col: int | None):
    """The operator's running count of preserved rows a LEFT or ANTI join
    emitted without a match, after one more output batch: a device scalar
    for ``join.noninner.unmatched_rows`` (docs/observability.md), carried
    from batch to batch. ``build_col`` is a build-side key column of a LEFT
    join's output, which is NULL exactly in the rows that found no match;
    None for ANTI, every row of whose output is unmatched."""

    def join_unmatched_rows(unmatched, out):
        miss = out.valid
        if build_col is not None:
            miss = miss & out.nulls[build_col]
        return unmatched + jnp.sum(miss, dtype=jnp.int64)

    return jax.jit(join_unmatched_rows)


# the running counts' start: a host scalar, so that the first batch of a
# task dispatches the same program as the rest and nothing else
_NO_ROWS = np.int64(0)


class HashJoinExec(ExecutionPlan):
    def __init__(
        self,
        left: ExecutionPlan,
        right: ExecutionPlan,
        on: list[tuple[L.Expr, L.Expr]],
        join_type: JoinType,
        filter: L.Expr | None = None,
        partition_mode: str = "collect",
        reduction: bool = False,
    ) -> None:
        """``partition_mode``: "collect" broadcasts the whole build side to
        every probe task (the reference's COLLECT_LEFT); "partitioned"
        assumes BOTH inputs are hash-partitioned on the join keys (the
        planner inserts HashRepartitionExec) and each task joins only its
        bucket (ref PartitionMode, ballista.proto:474-487). ``reduction``
        marks the semi join below a decorrelated subquery's aggregate
        (``plan.logical.Join.reduction``): it runs as any other, and the
        executor counts the aggregates it feeds (``subquery.agg_reduced``)."""
        super().__init__()
        if partition_mode not in ("collect", "partitioned"):
            raise PlanError(f"bad join partition mode {partition_mode!r}")
        self.left = left
        self.right = right
        self.on = list(on)
        self.join_type = join_type
        self.filter = filter
        self.partition_mode = partition_mode
        self.reduction = reduction
        self._build_cache: dict = {}
        # task slots share this instance's cached tables: a table's sorted
        # payload is made, and a cache slot written, under this lock
        self._build_lock = threading.Lock()
        # build-strategy flags (dups/overflow of the collected right side)
        # are partition-invariant: compute once, reuse across partitions
        self._decide_flags: tuple[bool, bool] | None = None
        self._decide_from_cache = False
        # the last dictionary merge of a string key, (build dictionary,
        # probe dictionary, merged and both remaps): a probe input's batches
        # share one dictionary, so it is made once a task
        self._last_merge: tuple | None = None
        ls, rs = left.schema(), right.schema()
        for a, b in self.on:
            if not (isinstance(a, L.Column) and isinstance(b, L.Column)):
                raise PlanError("join keys must be columns (planner projects)")
        if join_type in (JoinType.SEMI, JoinType.ANTI):
            self._schema = ls
        elif join_type == JoinType.LEFT:
            self._schema = ls.join(
                Schema([Field(f.name, f.dtype, True) for f in rs])
            )
        elif join_type == JoinType.INNER:
            self._schema = ls.join(rs)
        else:
            raise PlanError(f"join type {join_type} not supported on device yet")

    def schema(self) -> Schema:
        return self._schema

    def children(self) -> list[ExecutionPlan]:
        return [self.left, self.right]

    def output_partitioning(self):
        return self.left.output_partitioning()

    def describe(self) -> str:
        on = ", ".join(f"{a.name()} = {b.name()}" for a, b in self.on)
        f = f", filter={self.filter.name()}" if self.filter is not None else ""
        mark = ", reduction" if self.reduction else ""
        return (
            f"HashJoinExec({self.join_type.value}, "
            f"{self.partition_mode}{mark}): on=[{on}]{f}"
        )

    # -- dictionaries ---------------------------------------------------------
    def _unify_key_dicts(
        self, build: DeviceBatch, probe: DeviceBatch,
        build_keys: list[int], probe_keys: list[int],
    ) -> tuple[DeviceBatch, DeviceBatch]:
        """String join keys must share a dictionary: both sides' codes are
        remapped onto the sorted union of the two. A side whose dictionary
        is the union already keeps its batch and its codes, so a build whose
        dictionary holds every string of a probe batch comes back as it
        went in and is not built again; a remap of the build side counts
        ``key_remaps`` (its caller rebuilds the probe table). The last
        merge is kept: the probe batches of one input share a dictionary."""
        remapped = False
        for bi, pi in zip(build_keys, probe_keys):
            bf = build.schema.fields[bi]
            pf = probe.schema.fields[pi]
            if bf.dtype != DataType.STRING and pf.dtype != DataType.STRING:
                continue
            bd = build.dictionaries.get(bf.name)
            pd_ = probe.dictionaries.get(pf.name)
            if bd is None or pd_ is None:
                raise ExecutionError(
                    f"string join key {bf.name!r} missing dictionary"
                )
            if bd == pd_:
                continue
            last = self._last_merge
            if last is not None and last[0] is bd and last[1] is pd_:
                merged, rb, rp = last[2]
            else:
                merged, rb, rp = merge_dictionaries(bd, pd_)
                merged = next((d for d in (bd, pd_) if d == merged), merged)
                self._last_merge = (bd, pd_, (merged, rb, rp))
            if merged is not bd:
                remapped = True
                build = self._with_codes(build, bi, rb, merged)
            if merged is not pd_:
                probe = self._with_codes(probe, pi, rp, merged)
        if remapped:
            self.metrics.add("key_remaps")
        return build, probe

    @staticmethod
    def _with_codes(batch: DeviceBatch, i: int, table, merged) -> DeviceBatch:
        """``batch`` with column ``i``'s codes remapped through ``table``
        onto the dictionary ``merged``."""
        cols = list(batch.columns)
        cols[i] = remap_codes(batch.columns[i], table)
        dicts = dict(batch.dictionaries)
        dicts[batch.schema.fields[i].name] = merged
        return DeviceBatch(
            schema=batch.schema, columns=tuple(cols), valid=batch.valid,
            nulls=batch.nulls, dictionaries=dicts,
        )

    # -- cross-run build-table cache ------------------------------------------
    # A warm suite re-collects and re-sorts every build side each run
    # (~170ms for a 1.5M-row build on a v5e; the SEMI build of q18 even
    # re-runs its whole HAVING subquery). Built tables are cached on THIS
    # plan instance: the context's physical-plan cache keys instances by
    # the registered-data signature + config, so any data or config change
    # discards the instance — and the cache with it. Admission is gated by
    # an HBM budget shared through ctx.plan_cache
    # (ballista.tpu.build_cache_mb). String-keyed builds are skipped
    # (per-probe dictionary unification can rebuild them).

    def _build_cache_put(self, ctx, slot, build_batch, bt, key_idxs) -> None:
        if slot in self._build_cache or bt is None:
            return
        cache = ctx.plan_cache if ctx is not None else None
        if cache is None or not getattr(ctx, "cache_builds", True):
            return
        schema = build_batch.schema
        if any(
            schema.fields[i].dtype == DataType.STRING for i in key_idxs
        ):
            return
        budget = ctx.config.build_cache_mb() << 20
        if budget <= 0:
            return
        # each array once: the table holds the build batch's own columns
        # (its rows stay where they lie), and an exact key column is ``keys``
        held = {
            id(a): a.nbytes
            for a in (*build_batch.columns, *bt.batch.columns, bt.perm,
                      bt.keys, *bt.key_cols, bt.lut2)
            if a is not None
        }
        size = sum(held.values())

        def commit():
            # COMMIT ONLY AT A CLEAN TASK BOUNDARY: a run that fails its
            # deferred checks (capacity overflow in the subquery feeding a
            # SEMI build, a stale speculation) computed this table from
            # truncated intermediates — caching it would poison every
            # retry and every later query sharing the slot.
            with self._build_lock:
                if slot in self._build_cache:
                    return
                # a payload the task's probes gathered into sorted order
                # takes the place of the arrival batch and ``perm``, in no
                # more bytes than ``size`` counted for them (``_rows_for``)
                table = (
                    bt.in_sorted_order()[0] if bt.sorted_batch is not None
                    else bt
                )
                used = cache.get("__build_cache_bytes__", 0)
                if used + size > budget:
                    self.metrics.add("build_cache_skip")
                    return
                cache["__build_cache_bytes__"] = used + size
                self._build_cache[slot] = (table.batch, table)
                self.metrics.add("build_cache_store")

        ctx.defer_commit(commit)

    # -- execution ------------------------------------------------------------
    def execute(self, partition: int, ctx: TaskContext) -> Iterator[DeviceBatch]:
        ls, rs = self.left.schema(), self.right.schema()
        left_keys = [L.resolve_field_index(ls, a.cname) for a, _ in self.on]
        right_keys = [L.resolve_field_index(rs, b.cname) for _, b in self.on]

        if self.partition_mode == "partitioned":
            yield from self._execute_partitioned(
                partition, ctx, left_keys, right_keys
            )
            return

        learned = (
            self._learned_flip(ctx, left_keys, right_keys)
            if self.join_type == JoinType.INNER
            else None
        )
        budget = ctx.config.hbm_budget_mb() << 20
        if (
            budget
            and learned is None
            and not any(
                s in self._build_cache
                for s in (("bt_probe", None), ("bt_right",), ("bt_flip",))
            )
        ):
            # Skip the grace-budget probe when a warm path already proved
            # the budget moot: a LEARNED flip strategy builds the (unique,
            # small) LEFT side and streams the right — probing would
            # collect or spill the full right subtree, the exact cost that
            # path exists to avoid; and a cross-run cached build table
            # means the side fit in HBM and was admitted — re-executing
            # its subtree (q18's HAVING aggregate) would forfeit the
            # build-cache speedup and strand the collected batch in the
            # never-consumed stash.
            grace = self._grace_build(ctx, right_keys, budget)
            if grace is not None:
                yield from self._execute_grace(
                    partition, ctx, grace, left_keys, right_keys
                )
                return

        try:
            if self.join_type == JoinType.INNER:
                yield from self._execute_inner(
                    partition, ctx, left_keys, right_keys, learned
                )
                return

            # LEFT/SEMI/ANTI: left is preserved => left probes, right builds.
            yield from self._probe_loop(
                partition, ctx, lambda: self._collect_right(ctx),
                left_keys, right_keys, self._KIND[self.join_type],
            )
        finally:
            # Drop an unconsumed grace-probe stash on EVERY exit — empty
            # probe side, a downstream exception, an abandoned generator
            # (LIMIT) — or the collected build side stays pinned in HBM on
            # this plan instance, which outlives the run in the
            # cross-query physical-plan cache.
            c = getattr(self, "_grace_under", None)
            if c is not None and c[0] is ctx:
                self._grace_under = None

    _KIND = {
        JoinType.INNER: JoinSide.INNER,
        JoinType.LEFT: JoinSide.LEFT,
        JoinType.SEMI: JoinSide.SEMI,
        JoinType.ANTI: JoinSide.ANTI,
    }

    def _execute_partitioned(
        self, partition, ctx, left_keys, right_keys
    ) -> Iterator[DeviceBatch]:
        """PARTITIONED mode: both inputs are hash-partitioned on the join
        keys, so this task's bucket is join-complete on its own. Duplicate
        build keys take the m:n expansion path per bucket — no flip, no
        single-partition funnel (every bucket runs in parallel)."""
        yield from self._probe_loop(
            partition, ctx,
            lambda: _collect_partition(self.right, ctx, partition),
            left_keys, right_keys, self._KIND[self.join_type],
        )

    # -- grace-hash out-of-core path ------------------------------------------
    # Bucket fan-out of the spill files. Passes K (a power of two dividing
    # this) group consecutive buckets, so K is chosen AFTER the build side's
    # true size is known without re-spilling: (h % 64) % K == h % K for
    # K | 64, keeping build and probe routing aligned at any K.
    _GRACE_BUCKETS = 64

    def _collect_right(self, ctx: TaskContext) -> DeviceBatch:
        """The collected build side; reuses the batch the grace-budget
        probe collected when it decided the side fits in HBM (avoiding a
        second full execution of the build subtree). One-shot: the stash
        is dropped on consumption so the plan instance never pins the
        collected side in HBM past the caller's own reference — the
        flip-streaming INNER path frees its local refs before streaming
        specifically to avoid holding a fact-sized batch."""
        c = getattr(self, "_grace_under", None)
        if c is not None and c[0] is ctx:
            self._grace_under = None
            return c[1]
        return _collect(self.right, ctx)

    def _grace_build(self, ctx: TaskContext, right_keys, budget: int):
        """Collect the build side under the HBM budget. Returns None when
        it fits (stashing the collected batch for the normal paths), else
        (spill set, K passes): batches collected so far plus the rest of
        the stream are hash-routed to host bucket files and the join runs
        bucket-range by bucket-range (_execute_grace). Decided once per
        task context — every probe partition shares the spilled build."""
        cached = getattr(self, "_grace_cache", None)
        if cached is not None and cached[0] is ctx:
            return cached[1]
        from ballista_tpu.exec.spill import (
            choose_passes,
            device_nbytes,
            spill_batch_by_keys,
        )

        keys = tuple(right_keys)
        batches: list[DeviceBatch] = []
        nbytes = 0
        sset = None
        spilled = 0
        part = self.right.output_partitioning()
        with self.metrics.time("build_time"):
            for p in range(part.n):
                for b in self.right.execute(p, ctx):
                    nbytes += device_nbytes(b)
                    if sset is None and nbytes * 2 > budget:
                        # crossed the budget (a build costs the raw side,
                        # its sorted keys and permutation, and the probe's
                        # gathers beside it; ~2x the side): switch to
                        # spilling, draining what is already resident
                        sset = ctx.spill_manager().new_set(
                            f"join-build-{id(self):x}", self._GRACE_BUCKETS
                        )
                        for prev in batches:
                            spilled += spill_batch_by_keys(sset, prev, keys)
                        batches.clear()
                    if sset is None:
                        batches.append(b)
                    else:
                        spilled += spill_batch_by_keys(sset, b, keys)
        if sset is None:
            build = (
                concat_batches(batches)
                if batches
                else DeviceBatch.empty(self.right.schema())
            )
            self._grace_under = (ctx, build)
            self._grace_cache = (ctx, None)
            return None
        sset.finish_writes()
        self.metrics.add("spill_bytes", spilled)
        k = choose_passes(nbytes, budget, self._GRACE_BUCKETS)
        # recorded once per grace DECISION, not per probe partition —
        # plan_counters sums operator counters, and a per-partition add
        # would report k x partitions for a k-pass join
        self.metrics.add("spill_passes", k)
        self._grace_cache = (ctx, (sset, k))
        return (sset, k)

    def _execute_grace(
        self, partition, ctx, grace, left_keys, right_keys
    ) -> Iterator[DeviceBatch]:
        """Grace-hash join: both sides are hash-routed to aligned host
        bucket files; each pass loads one bucket range's build side,
        builds it with the ordinary kernels, and streams that range's
        probe rows through the ordinary probe/expansion. Equal keys share
        a bucket by the hash split, so the concatenated pass outputs are
        exactly the one-shot join for every supported join type (the
        preserved side of LEFT/SEMI/ANTI appears in exactly one bucket)."""
        from ballista_tpu.columnar.arrow_interop import table_from_arrow
        from ballista_tpu.exec.shrink import maybe_shrink
        from ballista_tpu.exec.spill import (
            spill_batch_by_keys,
            tables_string_dicts,
        )

        sset, k = grace
        kind = self._KIND[self.join_type]
        pset = ctx.spill_manager().new_set(
            f"join-probe-{id(self):x}-{partition}", self._GRACE_BUCKETS
        )
        spilled = 0
        with self.metrics.time("spill_time"):
            for b in self.left.execute(partition, ctx):
                spilled += spill_batch_by_keys(pset, b, tuple(left_keys))
        pset.finish_writes()
        self.metrics.add("spill_bytes", spilled)
        batch_rows = ctx.config.tpu_batch_rows()
        group = self._GRACE_BUCKETS // k
        site = self.display() + "|grace"
        for pass_i in range(k):
            buckets = range(pass_i * group, (pass_i + 1) * group)
            ptabs = [
                t
                for bk in buckets
                if (t := pset.read(bk)) is not None and t.num_rows
            ]
            if not ptabs:
                continue  # no probe rows: nothing to emit for any kind

            # one union dictionary set for the pass so every probe chunk
            # shares codes — per-chunk dictionaries would make
            # _unify_key_dicts rebuild (re-sort) the build side per chunk
            pass_dicts = tables_string_dicts(ptabs)

            def probe_batches(ptabs=ptabs, pass_dicts=pass_dicts):
                # convert lazily, one batch_rows chunk at a time: K bounds
                # the BUILD side's residency, not the probe side's, so a
                # probe-heavy range must stream through device memory
                # batch by batch rather than materialize whole. narrowing
                # OFF on BOTH sides: probe and build key columns must
                # share one physical width within a pass.
                for t in ptabs:
                    for off in range(0, t.num_rows, batch_rows):
                        yield from table_from_arrow(
                            t.slice(off, batch_rows), batch_rows,
                            frozenset(), fixed_dicts=pass_dicts,
                        )

            btabs = [
                t
                for bk in buckets
                if (t := sset.read(bk)) is not None and t.num_rows
            ]
            if not btabs:
                # build side empty for this range: INNER/SEMI emit nothing,
                # ANTI preserves every probe row, LEFT preserves with a
                # nulled build side
                if kind in (JoinSide.INNER, JoinSide.SEMI):
                    continue
                # every probe row is live and goes out unmatched: the host
                # holds their count
                n = sum(t.num_rows for t in ptabs)
                self.metrics.add("probe_rows", n)
                self.metrics.add("noninner_unmatched_rows", n)
                c = self.metrics.counters
                c["noninner_probe_rows"] = c["probe_rows"]
                for pb in probe_batches():
                    yield (
                        pb
                        if kind == JoinSide.ANTI
                        else self._null_extend(pb)
                    )
                continue
            with self.metrics.time("build_time"):
                bb_parts: list[DeviceBatch] = []
                for t in btabs:
                    bb_parts.extend(
                        table_from_arrow(t, 1 << 62, frozenset())
                    )
                bb = (
                    concat_batches(bb_parts)
                    if len(bb_parts) > 1
                    else bb_parts[0]
                )
                bt = self._build(bb, right_keys)
            for pb in probe_batches():
                bb2, pb2 = self._unify_key_dicts(
                    bb, pb, right_keys, left_keys
                )
                if bb2 is not bb:
                    with self.metrics.time("build_time"):
                        bt = self._build(bb2, right_keys)
                    bb = bb2
                out = self._probe_or_expand(
                    bt, pb2, left_keys, kind, ctx, None, partition
                )
                if kind in (JoinSide.INNER, JoinSide.LEFT):
                    out = self._restore_column_order(out, pb2, bt.batch, True)
                self._count_noninner(kind, out, right_keys)
                self.metrics.add("output_batches")
                yield maybe_shrink(out, ctx, site, partition)
        pset.close()

    def _count_noninner(
        self, kind: JoinSide, out: DeviceBatch, right_keys: list[int],
    ) -> None:
        """One probe batch of a join that preserves its left side, into the
        operator's metrics: its probe rows, which the probe program has
        added to ``probe_rows``, are ``noninner_probe_rows`` too, and a
        LEFT or ANTI join adds the preserved rows it emitted without a match
        to ``noninner_unmatched_rows``. The executor sums them into
        ``join.noninner.*`` as the task ends. The counts stay device scalars
        until the task's metrics are read."""
        if kind == JoinSide.INNER:
            return
        c = self.metrics.counters
        c["noninner_probe_rows"] = c["probe_rows"]
        unmatched = c.get("noninner_unmatched_rows", _NO_ROWS)
        build_col = len(self.left.schema()) + right_keys[0]
        if kind == JoinSide.ANTI:
            unmatched = _jit_unmatched(None)(unmatched, out)
        elif kind == JoinSide.LEFT and out.nulls[build_col] is not None:
            unmatched = _jit_unmatched(build_col)(unmatched, out)
        c["noninner_unmatched_rows"] = unmatched

    def _build(self, batch: DeviceBatch, key_idxs: list[int]):
        """``build_side``, counted: +1 ``builds`` and its live rows (a
        device scalar the build has already) into ``build_rows``, the bytes
        its finisher gathered through the sort's permutation (from static
        shapes) into ``build_gather_bytes``, and +1 ``builds_in_place`` (its
        payload stays in arrival order, unless ``_rows_for`` gathers it);
        summed into ``join.*`` as the task ends."""
        bt = build_side(batch, key_idxs)
        self.metrics.add("builds")
        self.metrics.add("build_rows", bt.n)
        self.metrics.add("build_gather_bytes", bt.gather_bytes())
        self.metrics.add("builds_in_place")
        return bt

    def _null_extend(self, pb: DeviceBatch) -> DeviceBatch:
        """LEFT-join rows for an empty build range: probe columns pass
        through, build columns are all-null."""
        from ballista_tpu.columnar.batch import Dictionary

        cols = list(pb.columns)
        nulls = list(pb.nulls)
        dicts = dict(pb.dictionaries)
        for f in self.right.schema():
            cols.append(jnp.zeros(pb.capacity, dtype=f.dtype.to_np()))
            nulls.append(jnp.ones(pb.capacity, dtype=bool))
            if f.dtype == DataType.STRING:
                dicts[f.name] = Dictionary(())
        return DeviceBatch(
            schema=self._schema,
            columns=tuple(cols),
            valid=pb.valid,
            nulls=tuple(nulls),
            dictionaries=dicts,
        )

    def _probe_loop(
        self, partition, ctx, collect_build, left_keys, right_keys, kind
    ) -> Iterator[DeviceBatch]:
        """Shared probe driver: unify key dictionaries per probe batch,
        rebuild only when remapping changed the build side (overflow is
        checked inside _probe_or_expand's flag fetch), probe or expand,
        relabel the output to the plan schema. The collected+built build
        side is cached across runs (a SEMI build may wrap a whole subquery
        — q18 re-ran its HAVING aggregate every warm run before this)."""
        from ballista_tpu.exec.shrink import maybe_shrink

        slot = (
            "bt_probe",
            partition if self.partition_mode == "partitioned" else None,
        )
        build_batch, bt = self._build_cache.get(slot, (None, None))
        site = None
        fp = self._strategy_key(self.right, right_keys, ctx, partition)
        for b in self.left.execute(partition, ctx):
            if build_batch is None:
                with self.metrics.time("build_time"):
                    build_batch = collect_build()
            bb, pb = self._unify_key_dicts(build_batch, b, right_keys, left_keys)
            if bt is None or bb is not build_batch:
                with self.metrics.time("build_time"):
                    bt = self._build(bb, right_keys)
                build_batch = bb
                self._build_cache_put(ctx, slot, build_batch, bt, right_keys)
            out = self._probe_or_expand(
                bt, pb, left_keys, kind, ctx, fp, partition
            )
            if kind in (JoinSide.INNER, JoinSide.LEFT):
                # probe++build == left++right; relabel to the plan schema
                out = self._restore_column_order(out, pb, bt.batch, True)
            self._count_noninner(kind, out, right_keys)
            self.metrics.add("output_batches")
            # selective joins (q18's SEMI against a tiny HAVING set) leave
            # a near-empty batch at full probe capacity — re-bucket so the
            # rest of the plan runs at the data's true scale
            if site is None:
                site = self.display()
            yield maybe_shrink(out, ctx, site, partition)

    def _learned_flip(self, ctx, left_keys, right_keys):
        """(left strategy key, left flags) when the plan cache holds a
        LEARNED flip-streaming INNER strategy — right side can't serve as
        a unique build (dups/overflow) but the left can, with int keys
        (no dictionary unification, so the collected right would be
        decision input only). None otherwise. Consulted BEFORE the
        grace-budget probe in execute(): that probe collects (or spills)
        the whole right subtree, the exact cost the flip path avoids."""
        cache = ctx.plan_cache
        if cache is None:
            return None
        ls, rs = self.left.schema(), self.right.schema()
        if any(
            ls.fields[i].dtype == DataType.STRING for i in left_keys
        ) or any(rs.fields[i].dtype == DataType.STRING for i in right_keys):
            return None
        rflags = cache.get(self._strategy_key(self.right, right_keys, ctx))
        if rflags is None or not (rflags[0] or rflags[1]):
            return None
        lfp = self._strategy_key(self.left, left_keys, ctx)
        lflags = cache.get(lfp)
        if lflags is None or lflags[0] or lflags[1]:
            return None
        return lfp, lflags

    def _execute_inner(
        self, partition, ctx, left_keys, right_keys, learned
    ) -> Iterator[DeviceBatch]:
        """INNER: build the right side. If it has duplicate keys, prefer
        flipping to build a unique left side (fixed-capacity probe, no
        expansion); if BOTH sides have duplicates, run the m:n expansion
        join with the right side as build. ``learned`` is execute()'s
        _learned_flip result (computed once — each probe renders both
        subtrees' display strings for the plan-cache keys)."""
        ls, rs = self.left.schema(), self.right.schema()
        if learned is not None:
            # Cached-flip fast path: when prior runs LEARNED that the
            # right side cannot serve as a unique build (dups/overflow)
            # and the left CAN, skip collecting the right entirely —
            # collecting a 60M-row fact side, concat-ing it, and sorting
            # it for a strategy decision we already know was >200s/run of
            # SF=10 q18. Int keys need no dictionary unification, so the
            # collected right was ONLY the decision input. The left's
            # uniqueness is still deferred-validated (stale -> retry via
            # the general path); the right's "has dups" bit needs NO
            # validation — a unique-left build probe is correct whether
            # or not the probe side has duplicates.
            lfp, lflags = learned
            if partition != 0:
                return
            from ballista_tpu.exec.shrink import maybe_shrink

            cached = self._build_cache.get(("bt_flip",))
            if cached is not None:
                left_batch, lbt = cached
            else:
                with self.metrics.time("build_time"):
                    left_batch = _collect(self.left, ctx)
                    lbt = self._build(left_batch, left_keys)
                self._build_cache_put(
                    ctx, ("bt_flip",), left_batch, lbt, left_keys
                )
            ctx.defer_speculation(
                lbt.spec_flag(),
                "cached join build strategy went stale (flip side "
                "no longer unique)",
                [lfp, ("join_lut", lfp)],
            )
            contig = self._contig_probe(lbt, lflags, True, ctx, lfp)
            site = self.display()
            rpart = self.right.output_partitioning()
            for p in range(rpart.n):
                for b in self.right.execute(p, ctx):
                    if not contig:
                        # per-batch: the general path gates the LUT
                        # on the COLLECTED probe capacity, which the
                        # stream never materializes — re-offering
                        # each batch converges to the same decision
                        # (the helper early-outs once attached or
                        # once the domain is learned unusable)
                        self._maybe_attach_lut(
                            lbt, b.capacity, ctx, lfp
                        )
                    joined = self._probe_with_filter(
                        lbt, b, right_keys, JoinSide.INNER, contig
                    )
                    out = self._restore_column_order(
                        joined, b, lbt.batch, build_is_right=False
                    )
                    self.metrics.add("output_batches")
                    yield maybe_shrink(out, ctx, site, 0)
            return

        cached_r = self._build_cache.get(("bt_right",))
        if cached_r is not None:
            right_batch = cached_r[0]
        else:
            with self.metrics.time("build_time"):
                right_batch = self._collect_right(ctx)

        iter_first = iter(self.left.execute(partition, ctx))
        first = next(iter_first, None)
        if first is None:
            return

        # Decide the build strategy from the UN-unified right batch: dup and
        # collision-overflow flags on the original codes are identical on
        # every partition, so all partitions take the same branch. (Deciding
        # after dictionary unification with this partition's first probe
        # batch could disagree with partition 0 — and a disagreeing
        # partition would silently emit nothing.)
        # The flags come from (in preference order): this plan instance, the
        # cross-query plan cache (no sync — validated by a deferred flag;
        # stale entries trigger an invalidate-and-retry), or a blocking
        # fetch off a fresh build of the un-unified right side.
        cache = ctx.plan_cache
        fp = self._strategy_key(self.right, right_keys, ctx)
        decide = None
        flags = None
        from_cache = False
        if cache is not None:
            # the cache is authoritative when present — a SpeculationMiss
            # retry invalidates IT, so the per-instance memo must not be
            # consulted (it would replay the stale decision forever)
            got = cache.get(fp)
            if got is not None:
                flags, from_cache = got, True
        elif self._decide_flags is not None:
            flags, from_cache = self._decide_flags, self._decide_from_cache
        if flags is None:
            with self.metrics.time("build_time"):
                decide = self._build(right_batch, right_keys)
            flags = decide.flags()
            if cache is not None:
                cache[fp] = flags
        self._decide_flags = flags
        self._decide_from_cache = from_cache
        bt_dups, bt_ovf = flags[0], flags[1]
        if bt_dups or bt_ovf:
            # Right side can't serve as a unique build (dups, or a hash-mode
            # collision run past the probe window). Deterministic across
            # partitions: emit all output from partition 0, nothing
            # elsewhere.
            if partition != 0:
                return
            with self.metrics.time("build_time"):
                left_batch = _collect(self.left, ctx)
            lb, rb = self._unify_key_dicts(
                left_batch, right_batch, left_keys, right_keys
            )
            with self.metrics.time("build_time"):
                lbt = self._build(lb, left_keys)
            lfp = self._strategy_key(self.left, left_keys, ctx)
            lflags = cache.get(lfp) if cache is not None else None
            l_from_cache = lflags is not None
            if lflags is None:
                lflags = lbt.flags()
                if cache is not None:
                    cache[lfp] = lflags
            lbt_dups, lbt_ovf = lflags[0], lflags[1]
            if not lbt_dups and not lbt_ovf:
                # flip: build (unique) left, probe the right side
                if l_from_cache:
                    ctx.defer_speculation(
                        lbt.spec_flag(),
                        "cached join build strategy went stale (flip side "
                        "no longer unique)",
                        [lfp, ("join_lut", lfp)],
                    )
                contig = self._contig_probe(
                    lbt, lflags, l_from_cache, ctx, lfp
                )
                if not contig:
                    self._maybe_attach_lut(lbt, rb.capacity, ctx, lfp)
                key_strings = any(
                    ls.fields[i].dtype == DataType.STRING
                    for i in left_keys
                ) or any(
                    rs.fields[i].dtype == DataType.STRING
                    for i in right_keys
                )
                if key_strings:
                    # string keys were dictionary-unified against the
                    # COLLECTED right; probe it in one shot
                    joined = self._probe_with_filter(
                        lbt, rb, right_keys, JoinSide.INNER, contig
                    )
                    out = self._restore_column_order(
                        joined, rb, lbt.batch, build_is_right=False
                    )
                    self.metrics.add("output_batches")
                    yield out
                    return
                # int keys: STREAM the probe side batch-by-batch. The
                # collected right is a fact table in the common flip shape
                # (TPC-H puts lineitem on the join's right), and probing
                # it as ONE program allocates gather intermediates at the
                # FULL collected capacity — 64M rows x ~10 columns at
                # SF=10, an instant HBM OOM. Streaming probes at scan
                # batch granularity instead; the collected copy is only
                # the strategy-decision input and is dropped here.
                from ballista_tpu.exec.shrink import maybe_shrink

                # free the collected right AND the decide build, which
                # holds it beside its keys, before streaming
                right_batch = rb = lb = decide = None
                site = self.display()
                rpart = self.right.output_partitioning()
                for p in range(rpart.n):
                    for b in self.right.execute(p, ctx):
                        joined = self._probe_with_filter(
                            lbt, b, right_keys, JoinSide.INNER, contig
                        )
                        out = self._restore_column_order(
                            joined, b, lbt.batch, build_is_right=False
                        )
                        self.metrics.add("output_batches")
                        yield maybe_shrink(out, ctx, site, 0)
                return
            # both sides duplicated: m:n expansion, building whichever side
            # has no collision overflow (expansion needs countable runs)
            if bt_ovf and not lbt_ovf:
                if l_from_cache:
                    # expansion only needs countable runs: validate the
                    # cached "no collision overflow" bit, not uniqueness
                    ctx.defer_speculation(
                        lbt.run_overflow,
                        "cached join build strategy went stale (collision "
                        "overflow appeared)",
                        [lfp, ("join_lut", lfp)],
                    )
                self._maybe_attach_lut(lbt, rb.capacity, ctx, lfp)
                joined = self._expand_with_filter(
                    lbt, rb, right_keys, JoinSide.INNER, ctx, lfp, 0
                )
                out = self._restore_column_order(
                    joined, rb, lbt.batch, build_is_right=False
                )
            else:
                with self.metrics.time("build_time"):
                    rbt = self._build(rb, right_keys)
                # expansion cannot count collision-overflowed runs. If the
                # branch came from cached flags, treat a firing as a stale
                # speculation (fresh flags may pick the other build side);
                # otherwise it is a hard limit — defer either way (single
                # task-boundary fetch)
                if from_cache:
                    ctx.defer_speculation(
                        rbt.run_overflow,
                        "cached join build strategy went stale (collision "
                        "overflow appeared)",
                        [fp, ("join_lut", fp)],
                    )
                else:
                    ctx.defer_check(
                        rbt.run_overflow,
                        "join build side has a packed-hash collision run "
                        "longer than the probe window; use an integer join "
                        "key or reduce build size",
                    )
                self._maybe_attach_lut(rbt, lb.capacity, ctx, fp)
                out = self._expand_with_filter(
                    rbt, lb, left_keys, JoinSide.INNER, ctx, fp, 0
                )
            self.metrics.add("output_batches")
            yield out
            return

        def _validate(bt):
            # Validation WITHOUT a sync, fetched once at the task boundary.
            # A stale cached decision retries through the plan cache; a
            # same-run contradiction (post-unification remapped codes
            # introducing a collision run / apparent dups — partition-local,
            # so no silent fallback is sound) fails loudly. Integer keys
            # avoid packing entirely.
            if from_cache:
                ctx.defer_speculation(
                    bt.spec_flag(),
                    "cached join build strategy went stale (build side no "
                    "longer unique)",
                    [fp, ("join_lut", fp)],
                )
            else:
                ctx.defer_check(
                    bt.spec_flag(),
                    "join build side has duplicate keys or a packed-hash "
                    "collision run after dictionary unification; use "
                    "integer join keys",
                )

        bb, pb = self._unify_key_dicts(right_batch, first, right_keys, left_keys)
        if bb is right_batch and cached_r is not None:
            bt = cached_r[1]  # cross-run cache hit: no collect, no sort
            _validate(bt)
        elif bb is right_batch and decide is not None:
            bt = decide  # common case: unification was a no-op, reuse
            self._build_cache_put(
                ctx, ("bt_right",), right_batch, bt, right_keys
            )
        else:
            with self.metrics.time("build_time"):
                bt = self._build(bb, right_keys)
            _validate(bt)
            if bb is right_batch:
                self._build_cache_put(
                    ctx, ("bt_right",), right_batch, bt, right_keys
                )
        base = bb

        def _rest():
            yield first
            yield from iter_first

        # contiguity applies only while bt matches the build the flags
        # describe: a dictionary-unification rebuild REMAps key codes (a
        # contiguous code range can gain holes), and _validate only covers
        # dups/overflow — so a rebuilt build conservatively drops the
        # range-probe fast path instead of trusting stale flags.
        contig = (
            self._contig_probe(bt, flags, from_cache, ctx, fp)
            if bb is right_batch
            else False
        )
        for b in _rest():
            bb2, pb = self._unify_key_dicts(base, b, right_keys, left_keys)
            if bb2 is not base:
                with self.metrics.time("build_time"):
                    bt = self._build(bb2, right_keys)
                _validate(bt)
                contig = False
                base = bb2
            if not contig:
                self._maybe_attach_lut(bt, pb.capacity, ctx, fp)
            joined = self._probe_with_filter(
                bt, pb, left_keys, JoinSide.INNER, contig
            )
            out = self._restore_column_order(joined, pb, bt.batch, True)
            self.metrics.add("output_batches")
            yield out

    # Probes below this capacity don't amortize a table build (the
    # searchsorted scan method is cheap on small query vectors anyway).
    _LUT_MIN_PROBE = 1 << 17

    def _maybe_attach_lut(self, bt, probe_cap: int, ctx, fp) -> None:
        """Attach a direct-address probe table (ops/join.attach_lut) when
        the build has exact int keys over a bounded domain and the probe
        is big. The domain comes from the build's one-trip flags fetch
        (cold) or the plan cache (warm — validated by a deferred device
        flag, so an outgrown domain triggers invalidate-and-retry instead
        of silently dropping matches)."""
        from ballista_tpu.ops.join import (
            LUT_MAX_DOMAIN,
            attach_lut,
            lut_stale,
        )

        if (
            bt.lut2 is not None
            or bt.mode != "exact"
            or probe_cap < self._LUT_MIN_PROBE
        ):
            return
        cache = ctx.plan_cache if ctx is not None else None
        key = ("join_lut", fp) if fp else None
        if any(
            bt.batch.schema.fields[i].dtype == DataType.STRING
            for i in bt.key_idxs
        ):
            # dictionary-coded key domains GROW mid-task: every probe
            # batch that unifies new strings into the build dictionary
            # extends the code range, so a cached domain re-poisons the
            # cache on every attempt — learn the first build's range,
            # outgrow it on the next unification, invalidate, relearn —
            # until the speculation-retry bound fails the task (observed
            # when an AQE build-side flip promoted a dict-keyed build
            # under a >LUT-threshold probe). Dict-keyed builds take the
            # fresh-flags path on every (re)build instead: one memoized
            # flags fetch per rebuild, and the attached domain is the
            # build's true current one, so it can never go stale.
            cache, key = None, None
        cached = cache.get(key) if (cache is not None and key) else None
        if cached == 0:  # learned: contiguous or domain too wide
            return
        if cached is not None:
            attach_lut(bt, cached)
            ctx.defer_speculation(
                lut_stale(bt, cached),
                "cached join probe-table domain went stale (keys outgrew "
                "it)",
                [key],
            )
            return
        flags = bt.flags()  # one fetch, memoized per build
        contig = len(flags) > 2 and bool(flags[2])
        lo, hi = (flags[3], flags[4]) if len(flags) > 4 else (0, -1)
        domain = hi - lo + 1
        if contig or domain <= 0 or domain > LUT_MAX_DOMAIN:
            if cache is not None and key:
                cache[key] = 0
            return
        size = round_capacity(domain)
        attach_lut(bt, size)
        if cache is not None and key:
            cache[key] = size

    def _strategy_key(self, side_plan, keys: list[int], ctx, partition=None):
        """Cross-query plan-cache key for a build side: structural plan
        display + key indexes, scoped by job id (one executor serves many
        jobs whose reader plans can collide structurally) and, in
        hash-partitioned mode, by the bucket (each bucket's build data is
        different). Purely a speculation key — staleness is caught by
        deferred validation flags, never trusted blindly."""
        bucket = partition if self.partition_mode == "partitioned" else None
        return (
            "join_flags",
            getattr(ctx, "job_id", ""),
            side_plan.display(),
            tuple(keys),
            bucket,
        )

    # -- expansion (duplicate-build) path -------------------------------------
    def _probe_or_expand(
        self,
        bt,
        probe: DeviceBatch,
        probe_keys: list[int],
        kind: JoinSide,
        ctx=None,
        fp=None,
        partition: int = 0,
    ) -> DeviceBatch:
        """Unique build -> fixed-capacity probe; duplicated build -> m:n
        expansion (ref: DataFusion HashJoinExec m:n semantics, serde
        physical_plan mod.rs:438-523). With a plan cache, the branch comes
        from the cached flags with deferred validation — no blocking sync."""
        cache = ctx.plan_cache if ctx is not None else None
        cached = cache.get(fp) if (cache is not None and fp) else None
        if cached is not None:
            dups, _overflow = cached[0], cached[1]
            if not dups:
                ctx.defer_speculation(
                    bt.spec_flag(),
                    "cached join build strategy went stale (build side no "
                    "longer unique)",
                    [fp, ("join_lut", fp)],
                )
                contig = self._contig_probe(bt, cached, True, ctx, fp)
                if not contig:
                    self._maybe_attach_lut(bt, probe.capacity, ctx, fp)
                return self._probe_with_filter(
                    bt, probe, probe_keys, kind, contig
                )
            # expansion also handles a unique build; only collision
            # overflow invalidates it
            ctx.defer_speculation(
                bt.run_overflow,
                "cached join build strategy went stale (collision overflow "
                "appeared)",
                [fp, ("join_lut", fp)],
            )
            self._maybe_attach_lut(bt, probe.capacity, ctx, fp)
            return self._expand_with_filter(
                bt, probe, probe_keys, kind, ctx, fp, partition
            )
        flags = bt.flags()
        dups, overflow = flags[0], flags[1]
        if cache is not None and fp and not overflow:
            # never cache an overflowing build: the overflow is a hard
            # deterministic error below, and a cached entry would prepend a
            # wasted speculative run to every future occurrence
            cache[fp] = flags
        if overflow:
            bt.check_overflow()
        if not dups:
            contig = self._contig_probe(bt, flags, False, ctx, fp)
            if not contig:
                self._maybe_attach_lut(bt, probe.capacity, ctx, fp)
            return self._probe_with_filter(
                bt, probe, probe_keys, kind, contig
            )
        self._maybe_attach_lut(bt, probe.capacity, ctx, fp)
        return self._expand_with_filter(
            bt, probe, probe_keys, kind, ctx, fp, partition
        )

    def _expand_with_filter(
        self,
        bt,
        probe: DeviceBatch,
        probe_keys: list[int],
        kind: JoinSide,
        ctx=None,
        fp=None,
        partition: int = 0,
    ) -> DeviceBatch:
        """Expansion join: count matches per probe row, size the output on
        host (bucketed static capacity), then one jitted expand+filter+
        finalize program. SEMI/ANTI never expand without a residual filter
        (the match bit is enough). The output capacity sync is skipped on
        warm runs via the plan cache (deferred-validated)."""
        with self.metrics.time("probe_time"):
            (first, count, _), rows = _jit_counts(tuple(probe_keys))(
                bt, probe, self._rows_so_far()
            )
        self.metrics.counters["probe_rows"] = rows

        if kind in (JoinSide.SEMI, JoinSide.ANTI) and self.filter is None:
            from ballista_tpu.compilecache import shared_callable

            def build():
                keep_match = kind == JoinSide.SEMI

                def join_semi_mask(pb, count):
                    m = count > 0
                    return pb.with_valid(
                        pb.valid & (m if keep_match else ~m)
                    )

                return jax.jit(join_semi_mask)

            fn = shared_callable(
                ("join_semi_counts", tuple(probe_keys), kind), build
            )
            with self.metrics.time("probe_time"):
                return fn(probe, count)

        preserve = kind == JoinSide.LEFT
        cache = ctx.plan_cache if ctx is not None else None
        cap_key = ("expand_cap", fp, kind.name, partition) if fp else None
        out_cap = cache.get(cap_key) if (cache is not None and cap_key) else None
        synced = (
            ctx.run_state.setdefault("synced_caps", set())
            if ctx is not None
            else set()
        )
        if out_cap is not None and cap_key not in synced:
            # warm path: reuse an EARLIER RUN's capacity, validate on device
            # (rides the task-boundary fetch); a grown join output triggers
            # invalidate-and-retry, which re-syncs and re-caches. Keys this
            # run itself synced are excluded — an earlier smaller batch's
            # write must not turn later batches speculative mid-run (the
            # validation would fire every retry, never converging).
            total_dev = _jit_expand_total(preserve)(probe, count)
            ctx.defer_speculation(
                total_dev > out_cap,
                "cached expansion-join capacity went stale (output grew)",
                [cap_key],
            )
        else:
            with self.metrics.time("probe_time"):
                total = int(
                    read_array(
                        _jit_expand_total(preserve)(probe, count),
                        "join.expand_total",
                    )
                )
            out_cap = round_capacity(max(total, 1))
            if cache is not None and cap_key:
                cache[cap_key] = max(out_cap, cache.get(cap_key) or 0)
                synced.add(cap_key)

        from ballista_tpu.compilecache import expr_key, shared_callable

        key = (
            "join_expand", tuple(probe_keys), kind, out_cap,
            expr_key(self.filter),
        )

        def build():
            filt = self.filter

            def join_expand(bt, pb, first, count):
                if kind == JoinSide.LEFT:
                    eff = jnp.where(pb.valid, jnp.maximum(count, 1), 0)
                    ekind = JoinSide.LEFT
                else:
                    # INNER, or SEMI/ANTI with residual filter: pairs only
                    eff = count
                    ekind = JoinSide.INNER
                batch, i, k, real = expand_join(
                    bt, pb, first, count, eff, out_cap, ekind
                )
                if filt is None:
                    return batch  # INNER/LEFT, finalized by expand_join
                cv = compile_expr(filt, batch.schema).evaluate(batch)
                passes = cv.values.astype(bool)
                if cv.nulls is not None:
                    passes = passes & ~cv.nulls
                passes = passes & real
                if kind == JoinSide.INNER:
                    return batch.with_valid(batch.valid & passes)
                # any passing match per probe row (scatter-max)
                ap = (
                    jnp.zeros(pb.capacity, dtype=bool)
                    .at[i]
                    .max(passes, mode="drop")
                )
                if kind == JoinSide.SEMI:
                    return pb.with_valid(pb.valid & ap)
                if kind == JoinSide.ANTI:
                    return pb.with_valid(pb.valid & ~ap)
                # LEFT with residual filter: keep passing rows; probe rows
                # with no passing match keep their k==0 row, build side
                # nulled (LEFT JOIN ... ON key AND residual semantics, q13)
                null_row = (k == 0) & ~ap[i] & batch.valid
                new_valid = batch.valid & (passes | null_row)
                n_probe = len(pb.schema)
                nulls = list(batch.nulls)
                for ci in range(n_probe, len(batch.schema)):
                    m = nulls[ci]
                    miss = ~passes
                    nulls[ci] = miss if m is None else (m | miss)
                return DeviceBatch(
                    schema=batch.schema,
                    columns=batch.columns,
                    valid=new_valid,
                    nulls=tuple(nulls),
                    dictionaries=dict(batch.dictionaries),
                )

            return jax.jit(join_expand)

        fn = shared_callable(key, build)
        bt = self._rows_for(bt, out_cap)  # the expansion reads out_cap rows
        with self.metrics.time("probe_time"):
            return fn(bt, probe, first, count)

    def _contig_probe(self, bt, flags, from_cache, ctx, fp) -> bool:
        """Whether to take the contiguous-key probe path. Fresh flags are
        authoritative for this build; cached flags are speculative and get
        a deferred validation against the actual build's device flag."""
        contig = len(flags) > 2 and bool(flags[2])
        if contig and from_cache and ctx is not None and fp:
            import jax.numpy as jnp

            flag = (
                bt.contiguous
                if bt.contiguous is not None
                else jnp.ones((), bool)
            )
            ctx.defer_speculation(
                ~flag,
                "cached contiguous-build-key speculation went stale",
                [fp, ("join_lut", fp)],
            )
        return contig

    def _probe_with_filter(
        self,
        bt,
        probe: DeviceBatch,
        probe_keys: list[int],
        kind: JoinSide,
        contiguous: bool = False,
    ) -> DeviceBatch:
        """Probe (jitted); apply the residual join filter to match
        semantics."""
        c = self.metrics.counters
        if kind in (JoinSide.INNER, JoinSide.LEFT) or self.filter is not None:
            bt = self._rows_for(bt, probe.capacity)  # the payload is read
        if self.filter is None:
            with self.metrics.time("probe_time"):
                out, c["probe_rows"] = _jit_probe(
                    tuple(probe_keys), kind, contiguous
                )(bt, probe, self._rows_so_far())
            return out
        from ballista_tpu.compilecache import expr_key, shared_callable

        key = (
            "join_probe_filter", tuple(probe_keys), kind, contiguous,
            expr_key(self.filter),
        )

        def build():
            filt = self.filter
            pk = list(probe_keys)

            def join_probe_filter(bt, probe, rows):
                return filtered(bt, probe), _probe_rows(rows, probe)

            def filtered(bt, probe):
                # Residual filters see probe ++ build columns: join LEFT-like
                # first, evaluate, then adjust validity per join kind.
                joined = probe_side(
                    bt, probe, pk, JoinSide.LEFT, contiguous=contiguous
                )
                matched = probe_side(
                    bt, probe, pk, JoinSide.INNER, contiguous=contiguous
                ).valid
                phys = compile_expr(filt, joined.schema)
                cv = phys.evaluate(joined)
                passes = cv.values.astype(bool)
                if cv.nulls is not None:
                    passes = passes & ~cv.nulls
                full_match = matched & passes
                if kind == JoinSide.SEMI:
                    return probe.with_valid(probe.valid & full_match)
                if kind == JoinSide.ANTI:
                    return probe.with_valid(probe.valid & ~full_match)
                if kind == JoinSide.INNER:
                    return joined.with_valid(full_match)
                # LEFT: keep probe rows; null the build side on no full match
                bcols_start = len(probe.schema)
                nulls = list(joined.nulls)
                for i in range(bcols_start, len(joined.schema)):
                    m = nulls[i]
                    miss = ~full_match
                    nulls[i] = miss if m is None else (m | miss)
                return DeviceBatch(
                    schema=joined.schema,
                    columns=joined.columns,
                    valid=probe.valid,
                    nulls=tuple(nulls),
                    dictionaries=dict(joined.dictionaries),
                )

            return jax.jit(join_probe_filter)

        fn = shared_callable(key, build)
        with self.metrics.time("probe_time"):
            out, c["probe_rows"] = fn(bt, probe, self._rows_so_far())
        return out

    def _rows_for(self, bt, rows: int):
        """The build table a program that reads ``rows`` build rows (a probe
        batch's capacity, an expansion's output capacity) reads the payload
        of. The build's rows stay where they lie, and each row read pays one
        more gather (``perm``) for that; where the program reads more rows
        than the build has slots, it reads every build row many times over,
        so there the payload is gathered into sorted order once a build
        (``BuildTable.in_sorted_order``), which then counts as not in place
        and its payload's bytes as gathered. A cached table's sorted copy
        takes the place of its arrival batch in the cache, in no more bytes
        than the budget counted for the batch and ``perm``. Decided on
        static shapes."""
        if bt.perm is None or rows <= bt.keys.shape[0]:
            return bt
        with self._build_lock:
            sorted_bt, gathered = bt.in_sorted_order()
            if gathered:
                self.metrics.add("builds_in_place", -1)
                self.metrics.add("build_gather_bytes", gathered)
                for slot, (_, table) in list(self._build_cache.items()):
                    if table is bt:
                        self._build_cache[slot] = (sorted_bt.batch, sorted_bt)
        return sorted_bt

    def _rows_so_far(self):
        """The running ``probe_rows`` a probe program carries on."""
        return self.metrics.counters.get("probe_rows", _NO_ROWS)

    def _restore_column_order(
        self,
        joined: DeviceBatch,
        probe: DeviceBatch,
        build: DeviceBatch,
        build_is_right: bool,
    ) -> DeviceBatch:
        """probe_side outputs probe++build; the plan schema is left++right."""
        if build_is_right:
            return DeviceBatch(
                schema=self._schema,
                columns=joined.columns,
                valid=joined.valid,
                nulls=joined.nulls,
                dictionaries=self._rename_dicts(joined, self._schema),
            )
        # joined = right ++ left; reorder to left ++ right
        n_probe = len(probe.schema)
        cols = joined.columns[n_probe:] + joined.columns[:n_probe]
        nulls = joined.nulls[n_probe:] + joined.nulls[:n_probe]
        out = DeviceBatch(
            schema=self._schema,
            columns=cols,
            valid=joined.valid,
            nulls=nulls,
            dictionaries=self._rename_dicts(joined, self._schema),
        )
        return out

    @staticmethod
    def _rename_dicts(joined: DeviceBatch, schema: Schema):
        # dictionaries are name-keyed; schema order changes don't affect them
        return dict(joined.dictionaries)


class UnionExec(ExecutionPlan):
    """ref: UnionExecNode — concatenates child partitions positionally."""

    def __init__(self, inputs: list[ExecutionPlan]) -> None:
        super().__init__()
        self.inputs = list(inputs)
        self._schema = inputs[0].schema()

    def schema(self) -> Schema:
        return self._schema

    def children(self) -> list[ExecutionPlan]:
        return list(self.inputs)

    def output_partitioning(self):
        from ballista_tpu.exec.base import UnknownPartitioning

        return UnknownPartitioning(
            sum(i.output_partitioning().n for i in self.inputs)
        )

    def describe(self) -> str:
        return f"UnionExec: {len(self.inputs)} inputs"

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[DeviceBatch]:
        p = partition
        for child in self.inputs:
            n = child.output_partitioning().n
            if p < n:
                for b in child.execute(p, ctx):
                    if b.schema.names != self._schema.names:
                        # positional union: rename columns to first input
                        b = DeviceBatch(
                            schema=self._schema,
                            columns=b.columns,
                            valid=b.valid,
                            nulls=b.nulls,
                            dictionaries={
                                self._schema.fields[
                                    b.schema.index_of(k)
                                ].name: v
                                for k, v in b.dictionaries.items()
                            },
                        )
                    yield b
                return
            p -= n
        raise ExecutionError(f"union partition {partition} out of range")


class EmptyExec(ExecutionPlan):
    """ref: EmptyExecNode (produce_one_row for SELECT <literals>)."""

    def __init__(self, produce_one_row: bool, schema: Schema) -> None:
        super().__init__()
        self.produce_one_row = produce_one_row
        self._schema = schema

    def schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        return f"EmptyExec: rows={1 if self.produce_one_row else 0}"

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[DeviceBatch]:
        import numpy as np

        if not self.produce_one_row:
            yield DeviceBatch.empty(self._schema)
            return
        arrays = [np.zeros(1, f.dtype.to_np()) for f in self._schema]
        yield DeviceBatch.from_host(self._schema, arrays, num_rows=1)


class CrossJoinExec(ExecutionPlan):
    """Cross join where one side is a single-row relation (the shape the
    optimizer leaves behind for uncorrelated scalar subqueries, q11/q22):
    the single row's columns broadcast onto every row of the other side.
    General many-x-many cross joins are rejected (nothing in TPC-H needs
    them and they explode on static shapes)."""

    def __init__(self, left: ExecutionPlan, right: ExecutionPlan) -> None:
        super().__init__()
        self.left = left
        self.right = right
        self._schema = left.schema().join(right.schema())

    def schema(self) -> Schema:
        return self._schema

    def children(self) -> list[ExecutionPlan]:
        return [self.left, self.right]

    def output_partitioning(self):
        return self.left.output_partitioning()

    def describe(self) -> str:
        return "CrossJoinExec(broadcast-1-row)"

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[DeviceBatch]:
        one = _collect(self.right, ctx)
        one = compact(one)
        n = one.num_rows(site="cross_join.rows")
        if n != 1:
            raise ExecutionError(
                f"CrossJoinExec supports a 1-row broadcast side, got {n} "
                "rows; general cross joins are not supported on device"
            )
        r_schema = self.right.schema()
        for b in self.left.execute(partition, ctx):
            cols = list(b.columns)
            nulls = list(b.nulls)
            dicts = dict(b.dictionaries)
            for i, f in enumerate(r_schema):
                v = one.columns[i][0]
                cols.append(jnp.broadcast_to(v, (b.capacity,)))
                m = one.nulls[i]
                if m is None:
                    nulls.append(None)
                else:
                    nulls.append(jnp.broadcast_to(m[0], (b.capacity,)))
                d = one.dictionaries.get(f.name)
                if d is not None:
                    dicts[f.name] = d
            yield DeviceBatch(
                schema=self._schema,
                columns=tuple(cols),
                valid=b.valid,
                nulls=tuple(nulls),
                dictionaries=dicts,
            )
