"""Holistic percentile operator: sort-based exact continuous percentiles.

DataFusion computes approx_percentile_cont through a mergeable t-digest
accumulator (what the reference gets for free); a sort-first engine gets
the EXACT answer cheaper: sort all rows by (group keys, value), find the
per-group segment [ps, pe] over non-null live values, and gather the two
bracketing order statistics at ``t = q * (cnt - 1)`` for linear
interpolation — no data-dependent loops, one sort + a handful of n-sized
vector ops. Works a partition of its input at a time: the planner puts
every row of a group into one partition (``PhysicalPlanner._whole_groups``:
the hash exchange on the group keys, or the gather into one), and the
optimizer only plans this node below a join on the group keys, so one row
per group goes outward.
"""

from __future__ import annotations

import functools
from typing import Iterator

import jax
import jax.numpy as jnp

from ballista_tpu.columnar.batch import DeviceBatch
from ballista_tpu.datatypes import DataType, Field, Schema
from ballista_tpu.errors import PlanError
from ballista_tpu.exec.base import (
    ExecutionPlan,
    TaskContext,
    UnknownPartitioning,
)
from ballista_tpu.expr import logical as L
from ballista_tpu.ops.aggregate import running_count
from ballista_tpu.ops.concat import concat_batches
from ballista_tpu.ops.perm import holistic_perm, holistic_take
from ballista_tpu.ops.sort import SortKey, argsort_count, sort_passes


@functools.lru_cache(maxsize=None)
def _pct_program(
    key_nulls: tuple, val_has_null: bool, qs: tuple, cap: int
):
    """On rows sorted by (group keys, value) with null values LAST within
    each group: per-group segment edges over live non-null values, then
    interpolated gathers per percentile. Returns (per-q value arrays,
    per-q null flags, group-start flags) all in SORTED row space."""

    def percentile_interp(
        key_cols, key_nmasks, val, val_nmask, valid_sorted
    ):
        cap_i = jnp.arange(cap, dtype=jnp.int32)
        changed = jnp.zeros(cap, dtype=bool).at[0].set(True)
        for col, nm in zip(key_cols, key_nmasks):
            zc = (
                col if nm is None
                else jnp.where(nm, jnp.zeros_like(col), col)
            )
            changed = changed | jnp.concatenate(
                [jnp.ones(1, bool), zc[1:] != zc[:-1]]
            )
            if nm is not None:
                changed = changed | jnp.concatenate(
                    [jnp.ones(1, bool), nm[1:] != nm[:-1]]
                )
        changed = changed | jnp.concatenate(
            [jnp.zeros(1, bool), valid_sorted[1:] != valid_sorted[:-1]]
        )
        ps = jax.lax.cummax(jnp.where(changed, cap_i, 0))
        live = valid_sorted if val_nmask is None else (
            valid_sorted & ~val_nmask
        )
        # live rows of a group are its prefix (value-nulls sort last), so
        # the live count per row's group is a difference of running
        # counts: below 2^31, as the capacity is
        cnt_cs = running_count(live, jnp.int32)
        nxt = jnp.flip(
            jax.lax.cummin(jnp.flip(jnp.where(changed, cap_i, cap)))
        )
        pe = jnp.concatenate([nxt[1:], jnp.full(1, cap, jnp.int32)]) - 1
        pre = jnp.where(ps > 0, cnt_cs[jnp.clip(ps - 1, 0, cap - 1)], 0)
        cnt = cnt_cs[jnp.clip(pe, 0, cap - 1)] - pre

        vf = val.astype(jnp.float64)
        outs, nulls = [], []
        for q in qs:
            t = q * jnp.maximum(cnt - 1, 0).astype(jnp.float64)
            lo = jnp.floor(t).astype(jnp.int64)
            hi = jnp.ceil(t).astype(jnp.int64)
            frac = t - lo.astype(jnp.float64)
            vlo = vf[jnp.clip(ps + lo, 0, cap - 1)]
            vhi = vf[jnp.clip(ps + hi, 0, cap - 1)]
            outs.append(vlo * (1.0 - frac) + vhi * frac)
            nulls.append(cnt == 0)
        return outs, nulls, changed & valid_sorted

    return jax.jit(percentile_interp)


class PercentileExec(ExecutionPlan):
    """One output row per group: group keys + interpolated percentiles.
    Output rows surface at each group's first sorted position; the batch
    stays at input capacity with validity on those rows (downstream
    shrink re-buckets when worthwhile)."""

    def __init__(
        self, input: ExecutionPlan, group_exprs, group_names, requests
    ) -> None:
        super().__init__()
        self.input = input
        self.group_exprs = list(group_exprs)
        self.group_names = list(group_names)
        self.requests = list(requests)
        ins = input.schema()
        for e in self.group_exprs:
            if not isinstance(e, L.Column):
                raise PlanError(
                    "percentile group keys must be columns "
                    "(the optimizer projects first)"
                )
        vals = {v.name() for v, _, _ in self.requests}
        if len(vals) != 1:
            raise PlanError(
                "one Percentile node serves a single value expression; "
                "the optimizer splits per value"
            )
        v = self.requests[0][0]
        if not isinstance(v, L.Column):
            raise PlanError(
                "percentile value must be a column "
                "(the optimizer projects first)"
            )
        self._gk = [L.resolve_field_index(ins, e.cname) for e in self.group_exprs]
        self._vi = L.resolve_field_index(ins, v.cname)
        if ins.fields[self._vi].dtype == DataType.STRING:
            raise PlanError("percentile over STRING is not supported")
        self._schema = Schema(
            [
                Field(n, e.data_type(ins), e.nullable(ins))
                for e, n in zip(self.group_exprs, self.group_names)
            ]
            + [Field(n, DataType.FLOAT64, True) for _, _, n in self.requests]
        )

    def schema(self) -> Schema:
        return self._schema

    def children(self) -> list[ExecutionPlan]:
        return [self.input]

    def output_partitioning(self):
        return UnknownPartitioning(self.input.output_partitioning().n)

    def describe(self) -> str:
        g = ", ".join(e.name() for e in self.group_exprs)
        r = ", ".join(
            f"{n}=p{q:g}({e.name()})" for e, q, n in self.requests
        )
        return f"PercentileExec: groupBy=[{g}], [{r}]"

    def execute(
        self, partition: int, ctx: TaskContext
    ) -> Iterator[DeviceBatch]:
        from ballista_tpu.exec.shrink import maybe_shrink

        # the order of the rows is nothing to a sort: by capacity, so that
        # batches a shuffle read delivered in another order (two map outputs
        # fetched at once) concatenate through the program of the last time
        batches = sorted(
            self.input.execute(partition, ctx), key=lambda b: -b.capacity
        )
        if not batches:
            return
        b = concat_batches(batches) if len(batches) > 1 else batches[0]
        # sort: group keys asc, then value asc with NULL values LAST (so
        # each group's live values form a prefix of its segment)
        keys = [SortKey(col=i, ascending=True) for i in self._gk]
        keys.append(
            SortKey(col=self._vi, ascending=True, nulls_first=False)
        )
        # for the executor's ``holistic.*`` counters; the live rows stay a
        # device scalar until the task's metrics are read
        self.metrics.add("rows_sorted", jnp.sum(b.valid, dtype=jnp.int64))
        self.metrics.add(
            "sort_passes", argsort_count(b.columns, b.nulls, keys)
        )
        with self.metrics.time("sort_time"):
            perm = holistic_perm(
                sort_passes(b.columns, b.nulls, b.valid, keys)
            )
            # one stacked-by-dtype random-access pass for every column +
            # mask + validity (the optimizer projects the input down to
            # exactly keys + value, so whole-batch gather is minimal)
            cols, nmasks, valid_sorted = holistic_take(
                list(b.columns), list(b.nulls), b.valid, perm
            )

        key_pairs = [(cols[i], nmasks[i]) for i in self._gk]
        val, val_null = cols[self._vi], nmasks[self._vi]
        prog = _pct_program(
            tuple(b.nulls[i] is not None for i in self._gk),
            b.nulls[self._vi] is not None,
            tuple(q for _, q, _ in self.requests),
            b.capacity,
        )
        with self.metrics.time("pct_time"):
            outs, nulls, starts = prog(
                [c for c, _ in key_pairs],
                [m for _, m in key_pairs],
                val,
                val_null,
                valid_sorted,
            )
        cols = [c for c, _ in key_pairs] + list(outs)
        nmasks = [m for _, m in key_pairs] + list(nulls)
        out = DeviceBatch(
            schema=self._schema,
            columns=tuple(cols),
            valid=starts,
            nulls=tuple(nmasks),
            dictionaries={
                n: d
                for n, d in zip(
                    self.group_names,
                    (
                        b.dictionaries.get(b.schema.fields[i].name)
                        for i in self._gk
                    ),
                )
                if d is not None
            },
        )
        self.metrics.add("output_batches")
        yield maybe_shrink(out, ctx, self.display(), partition)
