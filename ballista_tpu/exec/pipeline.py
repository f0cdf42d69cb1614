"""Row-pipeline operators: Filter, Projection, and batch-function fusion.

Filter and Projection are pure per-batch device functions. The OUTERMOST
operator of a Filter/Projection chain fuses the whole chain into ONE
jitted program (``fusable_chain`` + ``fused_batch_fn``): every separate
dispatch is a launch and an HBM round trip of its own (cost not measured
on the attached chip), so a q6-shaped plan
(four pushed-down filter conjuncts + a measure projection) costs one
program per batch instead of five (SURVEY.md §7 "Stage DAG vs jit fusion
boundary"; the hot loop replaced is the per-batch stream in ref
shuffle_writer.rs:214-256). Adaptive shrink runs ONCE on the fused
output — seeing the chain's cumulative selectivity, which is strictly
more informative than each filter's own.
"""

from __future__ import annotations

from typing import Callable, Iterator

import jax

from ballista_tpu.columnar.batch import DeviceBatch
from ballista_tpu.datatypes import Field, Schema
from ballista_tpu.exec.base import ExecutionPlan, TaskContext
from ballista_tpu.expr import logical as L
from ballista_tpu.expr.physical import compile_expr


def prefetch_slices(load, items, depth: int, metrics=None):
    """Double-buffered pipeline: run ``load(item)`` on ONE background host
    thread, keeping up to ``depth`` results in flight beyond the one being
    consumed, and yield results in order.

    This is the compute/IO overlap primitive of the streamed scan
    (exec/scan.py): while the device works through slice i's batches, the
    worker reads/decodes slice i+1 and stages its host->device transfer —
    so scan-bound queries hide parquet decode behind device time. A single
    worker keeps host memory bounded at ``depth + 1`` slices and preserves
    read order (parquet readers are not safely shared across concurrent
    readers anyway).

    ``metrics`` (a Metrics set) records ``prefetch_hits`` (result was
    ready when the consumer asked) vs ``prefetch_misses`` (consumer had to
    wait — the first slice always misses, IO-bound pipelines mostly miss).
    """
    items = list(items)
    if depth <= 0 or len(items) <= 1:
        for it in items:
            yield load(it)
        return
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    from ballista_tpu.analysis import reswitness

    ex = ThreadPoolExecutor(max_workers=1, thread_name_prefix="scan-prefetch")
    pool_tok = reswitness.acquire("thread-pool", "scan-prefetch")
    try:
        pending: deque = deque()
        idx = 0
        # fill to depth, not depth+1: one result is always held by the
        # consumer after the first yield, so residency is depth+1 slices
        while idx < len(items) and len(pending) < depth:
            pending.append(ex.submit(load, items[idx]))
            idx += 1
        while pending:
            fut = pending.popleft()
            if metrics is not None:
                metrics.add(
                    "prefetch_hits" if fut.done() else "prefetch_misses"
                )
            out = fut.result()
            if idx < len(items):
                pending.append(ex.submit(load, items[idx]))
                idx += 1
            yield out
    finally:
        # an abandoned consumer (LIMIT) must not leave the worker reading
        # a file the caller is about to close
        ex.shutdown(wait=True, cancel_futures=True)
        reswitness.release(pool_tok)


def fusable_chain(plan: ExecutionPlan):
    """(source, ops): the maximal Filter/Projection chain hanging off
    ``plan``, ops innermost-first; source is the first non-fusable input."""
    ops: list[ExecutionPlan] = []
    p = plan
    while isinstance(p, (FilterExec, ProjectionExec)):
        ops.append(p)
        p = p.input
    ops.reverse()
    return p, ops


def fused_batch_fn(ops: list) -> Callable[[DeviceBatch], DeviceBatch]:
    """One jitted program for the whole chain (inner jits inline when the
    composition is traced). Shared across plan instances by the chain's
    canonical signature: the executor decodes a fresh plan per task, and
    without sharing every attempt/repeat re-traced the whole chain
    (compilecache/tracecache.py)."""
    fns = [op.batch_fn() for op in ops]
    if len(fns) == 1:
        return fns[0]

    from ballista_tpu.compilecache import shared_callable

    # class names only: capturing the operators would pin their plan
    # subtrees (scan tables, device batches) in the process-wide cache
    scopes = [type(op).__name__ for op in ops]
    steps = "_".join(op._cache_key()[0] for op in ops)

    def build():
        def pipeline_fused(batch: DeviceBatch) -> DeviceBatch:
            for scope, f in zip(scopes, fns):
                # a fusion's op_name leads back to its physical operator
                with jax.named_scope(scope):
                    batch = f(batch)
            return batch

        # e.g. pipeline_filter_project: the program's name on the trace
        pipeline_fused.__name__ = f"pipeline_{steps}"
        return jax.jit(pipeline_fused)

    return shared_callable(
        ("fused_chain",) + tuple(op._cache_key() for op in ops), build
    )


class _FusedPipeline:
    """Shared execute() body for the outermost operator of a chain."""

    _fused: tuple | None = None  # (source, fn, shrink_site, n_ops)

    def _fused_parts(self):
        if self._fused is None:
            import os

            if os.environ.get("BALLISTA_TPU_NO_FUSE"):
                source, ops = self.input, [self]
            else:
                source, ops = fusable_chain(self)
            fn = fused_batch_fn(ops)
            # one shrink for the chain, at the OUTERMOST filter's site
            # (stable identity for the learned-capacity cache)
            shrink_site = next(
                (o.display() for o in reversed(ops)
                 if isinstance(o, FilterExec)),
                None,
            )
            self._fused = (source, fn, shrink_site, len(ops))
        return self._fused

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[DeviceBatch]:
        from ballista_tpu.exec.shrink import maybe_shrink

        source, fn, shrink_site, n_ops = self._fused_parts()
        timer = "filter_time" if isinstance(self, FilterExec) else "project_time"
        for b in source.execute(partition, ctx):
            with self.metrics.time(timer):
                out = fn(b)
            self.metrics.add("input_batches")
            self.metrics.counters["fused_ops"] = n_ops
            if shrink_site is not None:
                out = maybe_shrink(out, ctx, shrink_site, partition)
            yield out


class FilterExec(_FusedPipeline, ExecutionPlan):
    """ref: FilterExecNode (ballista.proto:457-460). Clears validity bits;
    no data movement (compaction is explicit where layout matters)."""

    def __init__(self, input: ExecutionPlan, predicate: L.Expr) -> None:
        super().__init__()
        self.input = input
        self.predicate = predicate
        self._fn: Callable[[DeviceBatch], DeviceBatch] | None = None

    def schema(self) -> Schema:
        return self.input.schema()

    def children(self) -> list[ExecutionPlan]:
        return [self.input]

    def output_partitioning(self):
        return self.input.output_partitioning()

    def describe(self) -> str:
        return f"FilterExec: {self.predicate.name()}"

    def _cache_key(self) -> tuple:
        from ballista_tpu.compilecache import expr_key, schema_key

        return (
            "filter",
            expr_key(self.predicate),
            schema_key(self.input.schema()),
        )

    def batch_fn(self) -> Callable[[DeviceBatch], DeviceBatch]:
        if self._fn is None:
            from ballista_tpu.compilecache import shared_callable

            def build():
                phys = compile_expr(self.predicate, self.input.schema())

                def pipeline_filter(batch: DeviceBatch) -> DeviceBatch:
                    cv = phys.evaluate(batch)
                    keep = cv.values.astype(bool)
                    if cv.nulls is not None:
                        keep = keep & ~cv.nulls  # NULL predicate = drop row
                    return batch.with_valid(batch.valid & keep)

                return jax.jit(pipeline_filter)

            self._fn = shared_callable(self._cache_key(), build)
        return self._fn

class ProjectionExec(_FusedPipeline, ExecutionPlan):
    """ref: ProjectionExecNode (ballista.proto:441-444)."""

    def __init__(self, input: ExecutionPlan, exprs: list[L.Expr]) -> None:
        super().__init__()
        self.input = input
        self.exprs = list(exprs)
        ins = input.schema()
        self._schema = Schema(
            [Field(e.name(), e.data_type(ins), e.nullable(ins)) for e in self.exprs]
        )
        self._fn: Callable[[DeviceBatch], DeviceBatch] | None = None

    def schema(self) -> Schema:
        return self._schema

    def children(self) -> list[ExecutionPlan]:
        return [self.input]

    def output_partitioning(self):
        return self.input.output_partitioning()

    def describe(self) -> str:
        return "ProjectionExec: " + ", ".join(e.name() for e in self.exprs)

    def _cache_key(self) -> tuple:
        from ballista_tpu.compilecache import expr_key, schema_key

        return (
            "project",
            tuple(expr_key(e) for e in self.exprs),
            schema_key(self.input.schema()),
        )

    def batch_fn(self) -> Callable[[DeviceBatch], DeviceBatch]:
        if self._fn is None:
            from ballista_tpu.compilecache import shared_callable

            ins = self.input.schema()
            out_schema = self._schema

            def build():
                phys = [compile_expr(e, ins) for e in self.exprs]

                def pipeline_project(batch: DeviceBatch) -> DeviceBatch:
                    cols, nulls, dicts = [], [], {}
                    import numpy as np

                    for field, p in zip(out_schema, phys):
                        cv = p.evaluate(batch)
                        vals = cv.values
                        want = field.dtype.to_np()
                        if vals.dtype != want and not (
                            want == np.int64 and vals.dtype == np.int32
                        ):
                            # int32 is a permitted physical form of a
                            # logical INT64 column (arrow_interop
                            # narrowing) — widening it here would undo the
                            # narrowing right before the sorts/gathers it
                            # exists for
                            vals = vals.astype(want)
                        cols.append(vals)
                        nulls.append(cv.nulls)
                        if cv.dictionary is not None:
                            dicts[field.name] = cv.dictionary
                    return batch.with_columns(out_schema, cols, nulls, dicts)

                return jax.jit(pipeline_project)

            self._fn = shared_callable(self._cache_key(), build)
        return self._fn


class CoalescePartitionsExec(ExecutionPlan):
    """Merge all input partitions into one stream (ref: DataFusion
    CoalescePartitionsExec — the stage-boundary operator the distributed
    planner splits on, scheduler/src/planner.rs:104-132)."""

    def __init__(self, input: ExecutionPlan) -> None:
        super().__init__()
        self.input = input

    def schema(self) -> Schema:
        return self.input.schema()

    def children(self) -> list[ExecutionPlan]:
        return [self.input]

    def describe(self) -> str:
        return "CoalescePartitionsExec"

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[DeviceBatch]:
        assert partition == 0, "coalesce has a single output partition"
        part = self.input.output_partitioning()
        for p in range(part.n):
            yield from self.input.execute(p, ctx)


class RenameExec(ExecutionPlan):
    """Schema rename (SubqueryAlias): same columns, requalified names."""

    def __init__(self, input: ExecutionPlan, new_schema: Schema) -> None:
        super().__init__()
        self.input = input
        self._schema = new_schema

    def schema(self) -> Schema:
        return self._schema

    def children(self) -> list[ExecutionPlan]:
        return [self.input]

    def output_partitioning(self):
        return self.input.output_partitioning()

    def describe(self) -> str:
        return f"RenameExec: {self._schema.names}"

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[DeviceBatch]:
        old = self.input.schema()
        for b in self.input.execute(partition, ctx):
            dicts = {}
            for i, (of, nf) in enumerate(zip(old, self._schema)):
                d = b.dictionaries.get(b.schema.fields[i].name)
                if d is not None:
                    dicts[nf.name] = d
            yield DeviceBatch(
                schema=self._schema,
                columns=b.columns,
                valid=b.valid,
                nulls=b.nulls,
                dictionaries=dicts,
            )
