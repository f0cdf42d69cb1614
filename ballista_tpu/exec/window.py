"""Window operator: ranking, aggregates over frames, and LAG/LEAD.

The DataFusion WindowAggExec role (ref ballista.proto:531 WindowAggExecNode
with PhysicalWindowExprNode + WindowFrame, ballista.proto:352-366 /
datafusion.proto:236-277). TPU-native design: sort by (partition keys,
order keys) via the cached sort passes, then ONE cached jitted finisher
per (shape, function, frame) computes the whole output column on the
sorted rows and scatters it back to the ORIGINAL row positions through
the permutation — the operator appends columns without reordering its
input. Window expressions sharing identical sort keys share one sort.

Aggregates over frames reduce by PREFIX SUMS, not per-row loops: on the
sorted rows, sum over any [lo, hi] row window is cs[hi] - cs[lo-1]
(float prefixes ride the blocked triangular-matmul path from
ops/aggregate — no data-dependent control flow, all gathers are n-sized
vector ops). ROWS frames clamp per-row bounds to the partition;
RANGE frames snap to peer-group edges. MIN/MAX over running frames use a
segmented Hillis-Steele doubling scan (log2(n) masked shifts); bounded
ROWS frames for MIN/MAX are rejected (no prefix trick exists).
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
def _rank_program(
    part_nulls: tuple, order_nulls: tuple, fname: str, cap: int
):
    """Cached finisher keyed on (null-mask pattern of partition keys,
    null-mask pattern of order keys, function, capacity). Inputs are the
    SORTED key columns (+ their null masks where the pattern says so) and
    the permutation; output is the rank column at ORIGINAL row positions.
    Gathers/cumsums plus one unique-index permutation scatter."""

    def window_rank(part_cols, part_nmasks, order_cols, order_nmasks, perm):
        # positions and ranks are below 2^31 (capacities are): counted in
        # int32, which the TPU carries whole, and widened once at the end
        idx = jnp.arange(cap, dtype=jnp.int32)
        part_changed = (
            _changed_of(part_cols, part_nmasks, cap)
            if part_cols
            else jnp.zeros(cap, dtype=bool).at[0].set(True)
        )
        order_changed = (
            _changed_of(order_cols, order_nmasks, cap)
            if order_cols
            else jnp.zeros(cap, dtype=bool)
        )
        start = jax.lax.cummax(jnp.where(part_changed, idx, 0))
        if fname == "row_number":
            vals = idx - start + 1
        elif fname == "rank":
            peer_start = jax.lax.cummax(
                jnp.where(part_changed | order_changed, idx, 0)
            )
            vals = peer_start - start + 1
        else:  # dense_rank
            dr = running_count(part_changed | order_changed, jnp.int32)
            dr_at_start = jax.lax.cummax(jnp.where(part_changed, dr, 0))
            vals = dr - dr_at_start + 1
        # back to original row order: out[perm[i]] = vals[i] (perm is a
        # permutation -> unique indices)
        return (
            jnp.zeros(cap, dtype=jnp.int64)
            .at[perm]
            .set(vals.astype(jnp.int64), unique_indices=True)
        )

    return jax.jit(window_rank)


def _changed_of(cols, nulls, cap):
    changed = jnp.zeros(cap, dtype=bool).at[0].set(True)
    for col, nm in zip(cols, nulls):
        zc = col if nm is None else jnp.where(nm, jnp.zeros_like(col), col)
        changed = changed | jnp.concatenate(
            [jnp.ones(1, dtype=bool), zc[1:] != zc[:-1]]
        )
        if nm is not None:
            changed = changed | jnp.concatenate(
                [jnp.ones(1, dtype=bool), nm[1:] != nm[:-1]]
            )
    return changed


def _region_edges(changed, cap):
    """Per-row start and end (inclusive) of the region the row is in,
    given boundary markers. Start: running max of marked indices. End:
    next marker minus one (flip/cummin trick)."""
    idx = jnp.arange(cap, dtype=jnp.int32)
    start = jax.lax.cummax(jnp.where(changed, idx, 0))
    nxt = jnp.flip(jax.lax.cummin(jnp.flip(jnp.where(changed, idx, cap))))
    end = jnp.concatenate([nxt[1:], jnp.full(1, cap, jnp.int32)]) - 1
    return start, end


def _seg_running_minmax(v, ps, is_min: bool):
    """Segmented prefix min/max: Hillis-Steele doubling with a
    partition-start guard (the unrolled-associative-scan alternative takes
    minutes to compile at these lengths)."""
    cap = v.shape[0]
    idx = jnp.arange(cap, dtype=jnp.int32)
    steps = max(1, (cap - 1).bit_length())

    def body(k, v):
        off = jnp.left_shift(jnp.int32(1), k)
        prev = jnp.roll(v, off)
        ok = idx - off >= ps
        merged = jnp.minimum(v, prev) if is_min else jnp.maximum(v, prev)
        return jnp.where(ok, merged, v)

    return jax.lax.fori_loop(0, steps, body, v)


@functools.lru_cache(maxsize=None)
def _agg_window_program(
    fname: str,
    frame_key,  # None | (units, st, sn, et, en)
    has_order: bool,
    part_nulls: tuple,
    order_nulls: tuple,
    arg_dtype: str,
    arg_has_null: bool,
    out_dtype: str,
    offset: int,
    cap: int,
):
    """Aggregate / lag / lead window finisher on SORTED rows. Returns the
    output column and its null mask at ORIGINAL row positions."""

    def window_frame(part_cols, part_nmasks, order_cols, order_nmasks,
                     arg, arg_nmask, valid_sorted, perm):
        idx = jnp.arange(cap, dtype=jnp.int32)
        part_changed = _changed_of(part_cols, part_nmasks, cap)
        # the dead tail (invalid rows sort last) forms its own region so
        # live frames never cross into it; dead outputs are masked anyway
        part_changed = part_changed | jnp.concatenate(
            [jnp.zeros(1, bool), valid_sorted[1:] != valid_sorted[:-1]]
        )
        ps, pe = _region_edges(part_changed, cap)

        live = valid_sorted if arg_nmask is None else (
            valid_sorted & ~arg_nmask
        )

        if fname in ("lag", "lead"):
            src = idx - offset if fname == "lag" else idx + offset
            ok = (src >= ps) & (src <= pe) & valid_sorted
            srcc = jnp.clip(src, 0, cap - 1)
            vals = arg[srcc]
            nulls = ~ok
            if arg_nmask is not None:
                nulls = nulls | arg_nmask[srcc]
            out_vals = jnp.where(nulls, jnp.zeros_like(vals), vals)
            return (
                jnp.zeros(cap, vals.dtype).at[perm].set(
                    out_vals, unique_indices=True
                ),
                jnp.zeros(cap, bool).at[perm].set(
                    nulls, unique_indices=True
                ),
            )

        # frame bounds [lo, hi] in sorted row space
        if frame_key is None:
            if has_order:
                # SQL default: RANGE UNBOUNDED PRECEDING .. CURRENT ROW
                peer_changed = part_changed | _changed_of(
                    order_cols, order_nmasks, cap
                )
                _, peer_end = _region_edges(peer_changed, cap)
                lo, hi = ps, jnp.minimum(peer_end, pe)
            else:
                lo, hi = ps, pe
        else:
            units, st, sn, et, en = frame_key
            if units == "rows":
                lo = {
                    "up": ps,
                    "p": jnp.maximum(idx - sn, ps),
                    "cur": idx,
                    "f": jnp.minimum(idx + sn, pe + 1),
                }[st]
                hi = {
                    "p": jnp.maximum(idx - en, ps - 1),
                    "cur": idx,
                    "f": jnp.minimum(idx + en, pe),
                    "uf": pe,
                }[et]
            else:  # range: peer-group granularity (offset ranges rejected
                # at plan time)
                peer_changed = part_changed | _changed_of(
                    order_cols, order_nmasks, cap
                )
                peer_start, peer_end = _region_edges(peer_changed, cap)
                lo = ps if st == "up" else peer_start
                hi = pe if et == "uf" else jnp.minimum(peer_end, pe)

        acc_t = jnp.dtype(arg_dtype)
        if fname in ("sum", "avg", "count"):
            if jnp.issubdtype(acc_t, jnp.floating) or fname == "avg":
                acc_t = jnp.dtype(jnp.float64)
            else:
                acc_t = jnp.dtype(jnp.int64)
            contrib = jnp.where(live, arg, jnp.zeros_like(arg)).astype(acc_t)
            from ballista_tpu.ops.aggregate import _prefix_sum_2d

            cs = _prefix_sum_2d(contrib[:, None])[:, 0]
            cnt_cs = running_count(live)

            hi_c = jnp.clip(hi, 0, cap - 1)
            lo_c = jnp.clip(lo, 0, cap - 1)
            lo_prev = jnp.clip(lo_c - 1, 0, cap - 1)
            nonempty = hi >= lo

            def seg(cs1d, zero):
                pre = jnp.where(lo_c > 0, cs1d[lo_prev], zero)
                return jnp.where(nonempty, cs1d[hi_c] - pre, zero)

            cnt = seg(cnt_cs, jnp.zeros((), jnp.int64))
            if fname == "count":
                vals = cnt
                nulls = None
            elif fname == "avg":
                s = seg(cs, jnp.zeros((), acc_t))
                vals = s / jnp.maximum(cnt, 1).astype(jnp.float64)
                nulls = cnt == 0
            else:
                vals = seg(cs, jnp.zeros((), acc_t))
                nulls = cnt == 0
        else:  # min / max — frames start at UNBOUNDED PRECEDING (plan-
            # validated), so the value at the frame's END row of the
            # segmented running scan IS the frame reduction
            from ballista_tpu.ops.aggregate import _max_ident, _min_ident

            ident = _max_ident(arg.dtype) if fname == "min" else _min_ident(
                arg.dtype
            )
            masked = jnp.where(live, arg, ident)
            run = _seg_running_minmax(masked, ps, fname == "min")
            hi_c = jnp.clip(hi, 0, cap - 1)
            vals = run[hi_c]
            cnt_cs = running_count(live)
            pre = jnp.where(
                ps > 0, cnt_cs[jnp.clip(ps - 1, 0, cap - 1)], 0
            )
            # empty frame (an end bound of N PRECEDING before the
            # partition start) or no live rows in it -> NULL
            nulls = (hi < ps) | ((cnt_cs[hi_c] - pre) == 0)
            vals = jnp.where(nulls, jnp.zeros_like(vals), vals)

        out_t = jnp.dtype(out_dtype)
        vals = vals.astype(out_t)
        out_vals = jnp.zeros(cap, out_t).at[perm].set(
            vals, unique_indices=True
        )
        out_nulls = (
            None
            if nulls is None
            else jnp.zeros(cap, bool).at[perm].set(
                nulls, unique_indices=True
            )
        )
        return out_vals, out_nulls

    return jax.jit(window_frame)


class WindowExec(ExecutionPlan):
    """Appends one column per window expression, a partition of its input
    at a time: a window needs every row of a ``PARTITION BY`` group in one
    place, and the planner puts them there (``PhysicalPlanner._whole_groups``:
    the hash exchange on the shared keys, or the gather into one
    partition). The output keeps the input's partitioning."""

    def __init__(self, input: ExecutionPlan, window_exprs, names) -> None:
        super().__init__()
        self.input = input
        self.window_exprs = list(window_exprs)
        self.names = list(names)
        ins = input.schema()
        self._schema = Schema(
            list(ins.fields)
            + [
                Field(n, w.data_type(ins), w.nullable(ins))
                for n, w in zip(self.names, self.window_exprs)
            ]
        )
        # resolve key columns now (planner guarantees column refs);
        # nulls_first defaults to the engine's Sort convention
        # (FIRST for DESC, LAST for ASC)
        self._keys: list[tuple[tuple[int, ...], tuple[SortKey, ...]]] = []
        self._args: list[int | None] = []  # arg column index; -1 = literal
        self._arg_lits: list = []
        for w in self.window_exprs:
            for e in list(w.partition_by) + [e for e, _, _ in w.order_by]:
                if not isinstance(e, L.Column):
                    raise PlanError(
                        "window PARTITION BY / ORDER BY must be columns "
                        "(project expressions first)"
                    )
            if w.arg is None:
                self._args.append(None)
                self._arg_lits.append(None)
            elif isinstance(w.arg, L.Column):
                ai = L.resolve_field_index(ins, w.arg.cname)
                if ins.fields[ai].dtype == DataType.STRING:
                    raise PlanError(
                        "window functions over STRING columns are not "
                        "supported yet"
                    )
                self._args.append(ai)
                self._arg_lits.append(None)
            elif isinstance(w.arg, L.Literal):
                if not isinstance(w.arg.value, (int, float, bool)):
                    raise PlanError(
                        "window function literal arguments must be numeric"
                    )
                self._args.append(-1)
                self._arg_lits.append(w.arg)
            else:
                raise PlanError(
                    "window function arguments must be columns "
                    "(project expressions first)"
                )
            fr = w.frame
            if fr is not None:
                if fr.units == "range" and (
                    fr.start_type in ("p", "f") or fr.end_type in ("p", "f")
                ):
                    raise PlanError(
                        "RANGE frames with numeric offsets are not "
                        "supported (use ROWS)"
                    )
                if w.fname in ("min", "max") and fr.start_type != "up":
                    raise PlanError(
                        "MIN/MAX window frames must start at UNBOUNDED "
                        "PRECEDING (no prefix trick for sliding frames)"
                    )
            self._keys.append(
                (
                    tuple(
                        L.resolve_field_index(ins, e.cname)
                        for e in w.partition_by
                    ),
                    tuple(
                        SortKey(
                            col=L.resolve_field_index(ins, e.cname),
                            ascending=asc,
                            nulls_first=(
                                nf if nf is not None else not asc
                            ),
                        )
                        for e, asc, nf in w.order_by
                    ),
                )
            )

    def schema(self) -> Schema:
        return self._schema

    def children(self) -> list[ExecutionPlan]:
        return [self.input]

    def output_partitioning(self):
        return UnknownPartitioning(self.input.output_partitioning().n)

    def describe(self) -> str:
        return "WindowExec: " + ", ".join(
            f"{n} = {w.name()}"
            for n, w in zip(self.names, self.window_exprs)
        )

    def execute(
        self, partition: int, ctx: TaskContext
    ) -> Iterator[DeviceBatch]:
        # the order of the rows is nothing to a sort: by capacity, so that
        # batches a shuffle read delivered in another order (two map outputs
        # fetched at once) concatenate through the program of the last time
        batches = sorted(
            self.input.execute(partition, ctx), key=lambda b: -b.capacity
        )
        if not batches:
            return
        b = concat_batches(batches) if len(batches) > 1 else batches[0]
        self._count_sorts(b)
        out_cols, out_nulls = self.append_window_columns(b)
        yield DeviceBatch(
            schema=self._schema,
            columns=tuple(out_cols),
            valid=b.valid,
            nulls=tuple(out_nulls),
            dictionaries=dict(b.dictionaries),
        )

    @staticmethod
    def _sort_keys(pk, ok) -> tuple:
        return tuple(SortKey(col=i, ascending=True) for i in pk) + ok

    def _count_sorts(self, b: DeviceBatch) -> None:
        """The sorts ``append_window_columns`` dispatches for ``b``, into
        the operator's metrics (the executor sums them into the
        ``holistic.*`` counters; docs/observability.md). The live rows stay
        a device scalar until the task's metrics are read."""
        distinct = dict.fromkeys(
            self._sort_keys(pk, ok) for pk, ok in self._keys
        )
        live = jnp.sum(b.valid, dtype=jnp.int64)
        for sk in distinct:
            self.metrics.add("rows_sorted", live)
            self.metrics.add(
                "sort_passes", argsort_count(b.columns, b.nulls, sk)
            )

    def append_window_columns(self, b: DeviceBatch):
        """Input batch -> (columns + appended window columns, null masks).
        Pure-jax given the batch (the finisher programs are jitted and
        inline when traced), so MeshWindowExec can run it per shard inside
        a ``shard_map`` after the partition-key exchange."""
        out_cols = list(b.columns)
        out_nulls = list(b.nulls)
        perm_cache: dict = {}  # shared sort for identical key sets
        for w, (pk, ok), argi, arg_lit, field in zip(
            self.window_exprs, self._keys, self._args, self._arg_lits,
            self._schema.fields[len(b.schema):],
        ):
            sk = self._sort_keys(pk, ok)
            perm = perm_cache.get(sk)
            if perm is None:
                with self.metrics.time("sort_time"):
                    perm = holistic_perm(
                        sort_passes(b.columns, b.nulls, b.valid, list(sk))
                    )
                perm_cache[sk] = perm

            # keys, argument and validity in sorted order: one dispatch
            ranking = w.fname in ("row_number", "rank", "dense_rank")
            want = list(pk) + [k.col for k in ok]
            if not ranking and argi not in (None, -1):
                want.append(argi)
            got, got_nulls, valid_sorted = holistic_take(
                [b.columns[i] for i in want], [b.nulls[i] for i in want],
                b.valid, perm,
            )
            pairs = list(zip(got, got_nulls))
            part_pairs = pairs[: len(pk)]
            order_pairs = pairs[len(pk): len(pk) + len(ok)]
            if w.fname in ("row_number", "rank", "dense_rank"):
                prog = _rank_program(
                    tuple(b.nulls[i] is not None for i in pk),
                    tuple(b.nulls[k.col] is not None for k in ok),
                    w.fname,
                    b.capacity,
                )
                with self.metrics.time("rank_time"):
                    vals = prog(
                        [c for c, _ in part_pairs],
                        [m for _, m in part_pairs],
                        [c for c, _ in order_pairs],
                        [m for _, m in order_pairs],
                        perm,
                    )
                out_cols.append(vals)
                out_nulls.append(None)
                continue

            if argi == -1:  # literal argument (COUNT(*) counts frame rows)
                import numpy as np

                v = arg_lit.value
                arg_col = jnp.full(
                    b.capacity, v,
                    jnp.asarray(np.asarray(v)).dtype,
                )
                arg_null = None
            else:
                arg_col, arg_null = pairs[-1]
            frame_key = (
                None
                if w.frame is None
                else (
                    w.frame.units, w.frame.start_type, w.frame.start_n,
                    w.frame.end_type, w.frame.end_n,
                )
            )
            prog = _agg_window_program(
                w.fname,
                frame_key,
                bool(ok),
                tuple(b.nulls[i] is not None for i in pk),
                tuple(b.nulls[k.col] is not None for k in ok),
                str(arg_col.dtype),
                arg_null is not None,
                str(jnp.dtype(field.dtype.to_np())),
                w.offset,
                b.capacity,
            )
            with self.metrics.time("rank_time"):
                vals, nulls = prog(
                    [c for c, _ in part_pairs],
                    [m for _, m in part_pairs],
                    [c for c, _ in order_pairs],
                    [m for _, m in order_pairs],
                    arg_col,
                    arg_null,
                    valid_sorted,
                    perm,
                )
            out_cols.append(vals)
            out_nulls.append(nulls)
        return out_cols, out_nulls
