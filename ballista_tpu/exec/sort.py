"""Sort and limit operators.

ref: SortExecNode / LimitExecNode (ballista.proto:560-575). SortExec gathers
its (single) input partition into one batch and runs the fused multi-key
``lax.sort`` kernel; with a fetch bound it is a TopK (sort then truncate —
the sort is already one fused XLA op, so a separate partial-TopK brings
nothing on TPU until batches far exceed HBM).
"""

from __future__ import annotations

import functools
from typing import Iterator

import jax
import jax.numpy as jnp


@functools.lru_cache(maxsize=None)
def _fetch_program(cap: int, fetch: int):
    def limit_mask(b):
        keep = jnp.arange(cap) < fetch
        return b.with_valid(b.valid & keep)

    return jax.jit(limit_mask)

from ballista_tpu.columnar.batch import DeviceBatch
from ballista_tpu.datatypes import Schema
from ballista_tpu.errors import PlanError
from ballista_tpu.exec.base import (
    ExecutionPlan,
    TaskContext,
    UnknownPartitioning,
)
from ballista_tpu.expr import logical as L
from ballista_tpu.ops.concat import concat_batches
from ballista_tpu.ops.fetch import read_array
from ballista_tpu.ops.sort import SortKey, sort_batch
from ballista_tpu.plan.logical import SortExpr


class SortExec(ExecutionPlan):
    def __init__(
        self,
        input: ExecutionPlan,
        sort_exprs: list[SortExpr],
        fetch: int | None = None,
    ) -> None:
        super().__init__()
        self.input = input
        self.sort_exprs = list(sort_exprs)
        self.fetch = fetch
        self._fn = None
        from ballista_tpu.ops.sort import resolve_sort_keys

        self._keys: list[SortKey] = resolve_sort_keys(
            input.schema(), self.sort_exprs
        )

    def schema(self) -> Schema:
        return self.input.schema()

    def children(self) -> list[ExecutionPlan]:
        return [self.input]

    def output_partitioning(self):
        return UnknownPartitioning(1)

    def describe(self) -> str:
        ks = ", ".join(
            f"{s.expr.name()} {'ASC' if s.ascending else 'DESC'}"
            for s in self.sort_exprs
        )
        f = f", fetch={self.fetch}" if self.fetch is not None else ""
        return f"SortExec: [{ks}]{f}"

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[DeviceBatch]:
        from ballista_tpu.columnar.batch import round_capacity
        from ballista_tpu.ops.sort import gather_batch, sort_perm

        assert partition == 0
        batches = []
        part = self.input.output_partitioning()
        for p in range(part.n):
            batches.extend(self.input.execute(p, ctx))
        if not batches:
            return
        merged = concat_batches(batches)
        # sort_perm host-composes cached argsort passes — no outer jit
        # (that would re-inline the sorts into one slow-compiling program).
        with self.metrics.time("sort_time"):
            if self.fetch is not None:
                # TopK: invalid rows sort last, so slicing the PERMUTATION
                # to the fetch bound makes the gather (and everything
                # downstream, including the result fetch to host) scale
                # with the limit, not the input capacity.
                m = min(
                    round_capacity(max(self.fetch, 8)), merged.capacity
                )
                perm = sort_perm(merged, self._keys)[:m]
                out = gather_batch(merged, perm)
                out = _fetch_program(m, self.fetch)(out)
            else:
                out = sort_batch(merged, self._keys)
        yield out


class GlobalLimitExec(ExecutionPlan):
    """skip/fetch over the single merged input partition (ref:
    GlobalLimitExecNode ballista.proto:567-571)."""

    def __init__(self, input: ExecutionPlan, skip: int, fetch: int | None) -> None:
        super().__init__()
        self.input = input
        self.skip = skip
        self.fetch = fetch

    def schema(self) -> Schema:
        return self.input.schema()

    def children(self) -> list[ExecutionPlan]:
        return [self.input]

    def output_partitioning(self):
        return UnknownPartitioning(1)

    def describe(self) -> str:
        return f"GlobalLimitExec: skip={self.skip}, fetch={self.fetch}"

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[DeviceBatch]:
        assert partition == 0

        def batches():
            part = self.input.output_partitioning()
            for p in range(part.n):
                yield from self.input.execute(p, ctx)

        def mask(b, skip, fetch):
            # rank of live rows within the batch (order-preserving)
            rank = jnp.cumsum(b.valid.astype(jnp.int32)) - 1
            keep = b.valid & (rank >= skip)
            if fetch is not None:
                keep = keep & (rank < skip + fetch)
            return b.with_valid(keep)

        it = batches()
        first = next(it, None)
        if first is None:
            return
        second = next(it, None)
        if second is None:
            # single-batch stream (the common shape under a coalesce/sort):
            # pure device masking, no host sync
            out = mask(first, self.skip, self.fetch)
            if self.fetch is not None:
                # host-known live-row ceiling: to_host can skip its
                # count sync and fetch a tight slice directly
                out.host_rows_max = self.fetch
            yield out
            return
        remaining_skip = self.skip
        remaining = self.fetch

        def _rest():
            yield first
            yield second
            yield from it

        for b in _rest():
            if remaining is not None and remaining <= 0:
                return
            out = mask(b, remaining_skip, remaining)
            # multi-batch streams need the live count to carry skip/fetch
            # across batches — one scalar sync per batch, rare shape
            n_live = int(
                read_array(
                    jnp.sum(b.valid.astype(jnp.int32)), "limit.live_count"
                )
            )
            taken = max(0, n_live - remaining_skip)
            if remaining is not None:
                taken = min(taken, remaining)
                remaining -= taken
            remaining_skip = max(0, remaining_skip - n_live)
            yield out
