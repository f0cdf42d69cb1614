"""Mesh stage programs: whole distributed stages as ONE jitted shard_map.

The reference executes a repartitioned aggregate / partitioned join as
three processes' worth of machinery — upstream tasks hash-partition to IPC
files (shuffle_writer.rs:142-292), the scheduler promotes the next stage
(query_stage_scheduler.rs:181-309), downstream tasks fetch over Flight
(shuffle_reader.rs:102-130). On-pod, the whole pipeline compiles into one
XLA program per mesh: local partial -> ``all_to_all`` over ICI -> local
final, with no host round-trip between stages.

Capacity/overflow discipline: every shape is static; bucket, group and
expansion overflows come back as SEPARATE per-device flags, checked
host-side after the step. Retryable overflows (bucket capacity, group
capacity, join-expansion output capacity) are retried here with grown
capacities — the mesh runner holds the inputs, so a retry is just a
re-dispatch of a differently-sized cached program. Non-retryable
conditions (hash-collision runs past the probe window) raise.

Join tier parity with the local kernels (ops/join.py): all three packing
modes (exact single-int key, exact2 two-int pack, hashed multi-key with
window-verified probes), m:n expansion joins for duplicate build keys, and
INNER-join residual filters — so q5/q18-class join shapes run PARTITIONED
on the mesh.
"""

from __future__ import annotations


import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

# Collective programs from CONCURRENT host threads (a multi-slot executor
# running two mesh stage-tasks at once) can interleave their per-device
# executions — device 0 enters program A's all_to_all while device 1 is in
# program B's, and the rendezvous deadlocks (observed on the virtual CPU
# mesh: "Expected 8 threads to join... not all arrived"). One program's
# collectives must fully complete before another dispatches, so every
# runner method holds this process-global lock through dispatch AND a
# completion barrier.
_COLLECTIVE_LOCK = threading.Lock()


def _stage_program(name: str, operator: str, f):
    """``f`` under the name its jitted program carries on the device
    trace's ``XLA Modules`` line, its operations scoped to the physical
    operator that owns the stage (docs/observability.md)."""

    def program(*args):
        with jax.named_scope(operator):
            return f(*args)

    program.__name__ = name
    return program

from ballista_tpu.columnar.batch import DeviceBatch, round_capacity
from ballista_tpu.datatypes import DataType, Field, Schema
from ballista_tpu.errors import CapacityError, ExecutionError
from ballista_tpu.ops.aggregate import AggOp, group_aggregate
from ballista_tpu.ops.join import (
    JoinSide,
    _build_finish,
    _choose_pack_mode,
    _pack_key,
    expand_join,
    probe_counts,
)
from ballista_tpu.ops.perm import multi_key_perm
from ballista_tpu.parallel.collective import (
    all_to_all_rows,
    bucket_rows_by_pid,
    exchange_by_key,
)
from ballista_tpu.parallel.mesh import SHARD_AXIS

MAX_MESH_RETRIES = 6


def _sum_dtype_np(dtype: DataType) -> DataType:
    if dtype in (DataType.BOOL,) or dtype.is_integer:
        return DataType.INT64
    return DataType.FLOAT64


class MeshStageRunner:
    """Compiles and runs mesh-wide stage programs over a 1-D device mesh.

    Inputs are mesh-sharded batches (see ``parallel.mesh.shard_batch``);
    outputs stay sharded — each device holds the rows whose hash routes to
    it, exactly the invariant a downstream mesh stage needs.
    """

    def __init__(self, mesh, axis: str = SHARD_AXIS) -> None:
        self.mesh = mesh
        self.axis = axis
        self.n_dev = int(mesh.devices.size)
        self._programs: dict = {}

    # -- helpers -------------------------------------------------------------
    def _leaf_specs(self, tree):
        return jax.tree_util.tree_map(lambda _: P(self.axis), tree)

    # -- repartitioned aggregate ---------------------------------------------
    def aggregate(
        self,
        batch: DeviceBatch,
        key_idxs: list[int],
        val_idxs: list[int],
        ops: list[AggOp],
        capacity: int,
        bucket_cap: int | None = None,
    ) -> DeviceBatch:
        """Partial agg per device -> all_to_all exchange of group states by
        key hash -> final merge agg per device. Output: sharded batch of
        (keys ++ aggregated values); each group lives on exactly one device.

        Group-capacity overflow is retried with the exact required capacity
        (the kernel computes the true group count even on overflow)."""
        for attempt in range(MAX_MESH_RETRIES):
            # states per device never exceed `capacity`, so a bucket of
            # `capacity` slots can always hold one device's worth
            bcap = bucket_cap or capacity
            prog = self._aggregate_program(
                batch, tuple(key_idxs), tuple(val_idxs), tuple(ops),
                capacity, bcap,
            )
            with _COLLECTIVE_LOCK:
                out_cols, out_nulls, out_valid, grp_ovf, need = prog(
                    batch.columns, batch.nulls, batch.valid
                )
                from ballista_tpu.ops.fetch import fetch_arrays

                # the fetch doubles as the completion barrier the lock needs
                grp_ovf, need = fetch_arrays(
                    [grp_ovf, need], site="mesh_agg.overflow"
                )
                jax.block_until_ready(out_valid)
            if not np.any(grp_ovf):
                break
            required = int(np.max(need))
            new_cap = round_capacity(required + 1)
            if new_cap <= capacity:
                new_cap = capacity * 2
            if attempt == MAX_MESH_RETRIES - 1:
                raise CapacityError(
                    "mesh aggregate exceeded group capacity after retries",
                    required=required,
                )
            capacity = new_cap
        in_schema = batch.schema
        fields = [in_schema.fields[i] for i in key_idxs]
        dicts = {
            k: v
            for k, v in batch.dictionaries.items()
            if any(in_schema.fields[i].name == k for i in key_idxs)
        }
        for i, op in zip(val_idxs, ops):
            f = in_schema.fields[i]
            if op == AggOp.COUNT:
                fields.append(Field(f"{f.name}#count", DataType.INT64, False))
            elif op == AggOp.SUM:
                fields.append(
                    Field(f"{f.name}#sum", _sum_dtype_np(f.dtype), True)
                )
            else:
                out_name = f"{f.name}#{op.value}"
                fields.append(Field(out_name, f.dtype, True))
                if f.dtype == DataType.STRING:
                    # MIN/MAX over a dictionary-coded column: the codes ride
                    # through; the dictionary follows under the renamed field
                    d = batch.dictionaries.get(f.name)
                    if d is not None:
                        dicts[out_name] = d
        return DeviceBatch(
            schema=Schema(fields),
            columns=tuple(out_cols),
            valid=out_valid,
            nulls=tuple(out_nulls),
            dictionaries=dicts,
        )

    def _aggregate_program(
        self, batch, key_idxs, val_idxs, ops, capacity, bucket_cap
    ):
        key = (
            "agg",
            str(batch.schema),
            batch.capacity,
            key_idxs,
            val_idxs,
            ops,
            capacity,
            bucket_cap,
            tuple(m is None for m in batch.nulls),
        )
        prog = self._programs.get(key)
        if prog is None:
            prog = self._compile_aggregate(
                batch, key_idxs, val_idxs, ops, capacity, bucket_cap
            )
            self._programs[key] = prog
        return prog

    def _compile_aggregate(
        self, batch, key_idxs, val_idxs, ops, capacity, bucket_cap
    ):
        axis, n_dev = self.axis, self.n_dev
        merge_ops = tuple(op.merge_op for op in ops)
        n_keys = len(key_idxs)

        def f(cols, nulls, valid):
            key_cols = [cols[i] for i in key_idxs]
            key_nulls = [nulls[i] for i in key_idxs]
            val_cols = [cols[i] for i in val_idxs]
            val_nulls = [nulls[i] for i in val_idxs]
            part = group_aggregate(
                key_cols, key_nulls, valid, val_cols, val_nulls,
                list(ops), capacity,
            )
            st_cols = tuple(part.keys) + tuple(part.values)
            st_nulls = tuple(part.key_nulls) + tuple(part.value_nulls)
            ex_cols, ex_nulls, ex_valid, b_ovf = exchange_by_key(
                st_cols, st_nulls, part.valid,
                tuple(range(n_keys)), axis, n_dev, bucket_cap,
            )
            fin = group_aggregate(
                list(ex_cols[:n_keys]),
                list(ex_nulls[:n_keys]),
                ex_valid,
                list(ex_cols[n_keys:]),
                list(ex_nulls[n_keys:]),
                list(merge_ops),
                capacity,
            )
            # bucket_cap == capacity makes bucket overflow impossible, but
            # keep the flag folded in as a backstop for explicit bucket_cap
            grp_ovf = (part.overflow | b_ovf | fin.overflow).reshape(1)
            need = jnp.maximum(
                part.n_groups.astype(jnp.int32), fin.n_groups.astype(jnp.int32)
            ).reshape(1)
            out_cols = tuple(fin.keys) + tuple(fin.values)
            # concrete (possibly all-false) masks so the output pytree has a
            # static structure for out_specs
            out_nulls = tuple(
                jnp.zeros(c.shape[0], dtype=bool) if m is None else m
                for c, m in zip(
                    out_cols, tuple(fin.key_nulls) + tuple(fin.value_nulls)
                )
            )
            return out_cols, out_nulls, fin.valid, grp_ovf, need

        in_specs = (
            self._leaf_specs(batch.columns),
            self._leaf_specs(batch.nulls),
            P(axis),
        )
        # outputs: all row-sharded (flags: one scalar per device)
        out_specs = (
            tuple(P(axis) for _ in range(n_keys + len(val_idxs))),
            tuple(P(axis) for _ in range(n_keys + len(val_idxs))),
            P(axis),
            P(axis),
            P(axis),
        )
        sm = shard_map(
            _stage_program("mesh_agg_stage", "MeshAggregateExec", f),
            mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(sm)

    # -- distributed TopK -----------------------------------------------------
    def topk(self, batch: DeviceBatch, keys, k: int) -> DeviceBatch:
        """ORDER BY ... LIMIT k as one mesh program: local sort + top-k on
        each shard, ``all_gather`` of the k-row candidates over ICI, final
        merge sort of the k*n_dev pool — every device computes the same
        replicated answer (SPMD), so the output is a single logical
        partition with no host hop. The shard-local top-k bounds the
        gather to k*n_dev rows regardless of input size (the mesh
        analogue of SortExec's fetch-sliced permutation)."""
        key_sig = tuple(
            (kk.col, kk.ascending, kk.nulls_first) for kk in keys
        )
        prog = self._topk_program(batch, key_sig, k)
        with _COLLECTIVE_LOCK:
            out_cols, out_nulls, out_valid = prog(
                batch.columns, batch.nulls, batch.valid
            )
            jax.block_until_ready(out_valid)
        return DeviceBatch(
            schema=batch.schema,
            columns=tuple(out_cols),
            valid=out_valid,
            nulls=tuple(out_nulls),
            dictionaries=dict(batch.dictionaries),
        )

    def _topk_program(self, batch, key_sig, k):
        key = (
            "topk", str(batch.schema), batch.capacity, key_sig, k,
            tuple(m is None for m in batch.nulls),
        )
        prog = self._programs.get(key)
        if prog is None:
            prog = self._compile_topk(batch, key_sig, k)
            self._programs[key] = prog
        return prog

    def _compile_topk(self, batch, key_sig, k):
        from ballista_tpu.ops.perm import take_batch
        from ballista_tpu.ops.sort import SortKey, sort_passes

        axis = self.axis
        keys = [
            SortKey(col=c, ascending=a, nulls_first=nf)
            for c, a, nf in key_sig
        ]

        def local_topk(cols, nulls, valid, kk):
            # same pass construction as single-device sort_perm — shared
            # so mesh TopK order cannot drift from SortExec order
            perm = multi_key_perm(sort_passes(cols, nulls, valid, keys))[:kk]
            return take_batch(list(cols), list(nulls), valid, perm)

        def f(cols, nulls, valid):
            shard_k = min(k, cols[0].shape[0])
            tcols, tnulls, tvalid = local_topk(cols, nulls, valid, shard_k)

            def ag(x):
                return jax.lax.all_gather(x, axis, tiled=True)

            gcols = tuple(ag(c) for c in tcols)
            gnulls = tuple(None if m is None else ag(m) for m in tnulls)
            gvalid = ag(tvalid)
            fk = min(k, gcols[0].shape[0])
            ocols, onulls, ovalid = local_topk(gcols, gnulls, gvalid, fk)
            out_nulls = tuple(
                jnp.zeros(c.shape[0], dtype=bool) if m is None else m
                for c, m in zip(ocols, onulls)
            )
            return tuple(ocols), out_nulls, ovalid

        in_specs = (
            self._leaf_specs(batch.columns),
            self._leaf_specs(batch.nulls),
            P(axis),
        )
        n = len(batch.columns)
        # replicated outputs: every device computed the identical answer
        out_specs = (
            tuple(P() for _ in range(n)),
            tuple(P() for _ in range(n)),
            P(),
        )
        sm = shard_map(
            _stage_program("mesh_topk_stage", "MeshSortExec", f),
            mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(sm)

    # -- full sort (sample sort / range exchange) -----------------------------

    SORT_SAMPLES = 64  # splitter samples per device

    def sort_full(self, batch: DeviceBatch, keys) -> DeviceBatch:
        """Total ORDER BY (no LIMIT) over the mesh: sample split points on
        the primary key -> range all_to_all exchange -> local multi-key
        sort per shard. Device d ends up holding the d-th key range,
        locally sorted, so the sharded batch read in index order IS the
        total order (ties on the primary key route to one device and are
        broken there by the remaining keys). The reference serializes this
        shape through a single post-gather sort task (planner.rs:104-132);
        the mesh version never funnels.

        Skew (few distinct primary keys) shows up as bucket overflow and
        retries with grown bucket capacity up to the skew-proof bound
        (per-shard rows, where overflow is impossible)."""
        key_sig = tuple(
            (kk.col, kk.ascending, kk.nulls_first) for kk in keys
        )
        per = max(1, batch.capacity // self.n_dev)
        bcap = round_capacity(max(1, (2 * per) // self.n_dev))
        for attempt in range(MAX_MESH_RETRIES):
            bcap = min(bcap, round_capacity(per))
            prog = self._sort_full_program(batch, key_sig, bcap)
            with _COLLECTIVE_LOCK:
                out_cols, out_nulls, out_valid, ovf = prog(
                    batch.columns, batch.nulls, batch.valid
                )
                from ballista_tpu.ops.fetch import fetch_arrays

                (ovf_h,) = fetch_arrays([ovf], site="mesh_sort.overflow")
                jax.block_until_ready(out_valid)
            if not np.any(ovf_h):
                break
            if bcap >= per or attempt == MAX_MESH_RETRIES - 1:
                raise CapacityError(
                    "mesh sort bucket overflow after retries",
                    required=per * self.n_dev,
                )
            bcap = round_capacity(bcap * 2)  # stay on the bucket ladder
        return DeviceBatch(
            schema=batch.schema,
            columns=tuple(out_cols),
            valid=out_valid,
            nulls=tuple(out_nulls),
            dictionaries=dict(batch.dictionaries),
        )

    def _sort_full_program(self, batch, key_sig, bcap):
        key = (
            "sortf", str(batch.schema), batch.capacity, key_sig, bcap,
            tuple(m is None for m in batch.nulls),
        )
        prog = self._programs.get(key)
        if prog is None:
            prog = self._compile_sort_full(batch, key_sig, bcap)
            self._programs[key] = prog
        return prog

    def _compile_sort_full(self, batch, key_sig, bcap):
        from ballista_tpu.ops.perm import take_batch
        from ballista_tpu.ops.sort import SortKey, sort_passes

        axis, n_dev = self.axis, self.n_dev
        keys = [
            SortKey(col=c, ascending=a, nulls_first=nf)
            for c, a, nf in key_sig
        ]
        k0 = keys[0]
        S = self.SORT_SAMPLES

        def routing_key(cols, nulls):
            """Primary sort key as a widened scalar whose ASCENDING order
            equals the key's sort order: DESC flips sign, null-masked rows
            pin to the end the key's null placement dictates."""
            r = cols[k0.col]
            nm = nulls[k0.col]
            if jnp.issubdtype(r.dtype, jnp.floating):
                r = r.astype(jnp.float64)
                hi = jnp.array(jnp.inf, r.dtype)
                # raw NaNs (not null-masked) sort last like jnp.sort
                r = jnp.where(jnp.isnan(r), hi, r)
            elif r.dtype == jnp.dtype(bool):
                r = r.astype(jnp.int64)
                hi = jnp.array(jnp.iinfo(jnp.int64).max, r.dtype)
            else:
                r = r.astype(jnp.int64)
                hi = jnp.array(jnp.iinfo(jnp.int64).max, r.dtype)
            lo = -hi
            if not k0.ascending:
                r = -r
            if nm is not None:
                r = jnp.where(nm, lo if k0.nulls_first else hi, r)
            return r, hi

        def f(cols, nulls, valid):
            per = valid.shape[0]
            r, hi = routing_key(cols, nulls)
            # dead rows route nowhere; use the sentinel so local sorted
            # samples see only live keys in the prefix
            r_live = jnp.where(valid, r, hi)
            rs = jnp.sort(r_live)
            nlive = jnp.sum(valid).astype(jnp.int32)
            pos = jnp.clip(
                (jnp.arange(S, dtype=jnp.int32) * nlive) // S, 0, per - 1
            )
            samp = jnp.where(nlive > 0, rs[pos], hi)
            gs = jnp.sort(jax.lax.all_gather(samp, axis, tiled=True))
            tot = S * n_dev
            spl_pos = (
                jnp.arange(1, n_dev, dtype=jnp.int32) * tot
            ) // n_dev
            splitters = gs[spl_pos]
            pid = jnp.searchsorted(splitters, r_live, side="left").astype(
                jnp.int32
            )
            pid = jnp.where(valid, pid, n_dev)
            bcols, bnulls, bvalid, ovf = bucket_rows_by_pid(
                cols, nulls, valid, pid, n_dev, bcap
            )
            ecols, enulls, evalid = all_to_all_rows(
                bcols, bnulls, bvalid, axis, n_dev, bcap
            )
            perm = multi_key_perm(
                sort_passes(list(ecols), list(enulls), evalid, keys)
            )
            ocols, onulls, ovalid = take_batch(
                list(ecols), list(enulls), evalid, perm
            )
            out_nulls = tuple(
                jnp.zeros(c.shape[0], dtype=bool) if m is None else m
                for c, m in zip(ocols, onulls)
            )
            return tuple(ocols), out_nulls, ovalid, ovf.reshape(1)

        in_specs = (
            self._leaf_specs(batch.columns),
            self._leaf_specs(batch.nulls),
            P(axis),
        )
        n = len(batch.columns)
        out_specs = (
            tuple(P(axis) for _ in range(n)),
            tuple(P(axis) for _ in range(n)),
            P(axis),
            P(axis),
        )
        sm = shard_map(
            _stage_program("mesh_sort_stage", "MeshSortExec", f),
            mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(sm)

    # -- partition-keyed windows ----------------------------------------------

    def window(self, batch: DeviceBatch, key_idxs: list[int], local_fn,
               n_out: int, fn_key=None):
        """Partition-keyed window functions over the mesh: hash-exchange
        rows by PARTITION BY key so each partition lands whole on one
        device, then run ``local_fn`` — the single-device window program —
        per shard inside the same compiled program. The reference punts on
        distributed windows entirely (planner.rs:163-169 funnels through a
        coalesce); this keeps K-way parallelism.

        ``local_fn(cols, nulls, valid) -> (out_cols, out_nulls)`` must be
        traceable and return the INPUT columns plus ``n_out`` appended
        window columns (null mask per appended column or None)."""
        per = max(1, batch.capacity // self.n_dev)
        bcap = round_capacity(max(1, (2 * per) // self.n_dev))
        for attempt in range(MAX_MESH_RETRIES):
            bcap = min(bcap, round_capacity(per))
            prog = self._window_program(
                batch, tuple(key_idxs), local_fn, n_out, bcap, fn_key
            )
            with _COLLECTIVE_LOCK:
                out_cols, out_nulls, out_valid, ovf = prog(
                    batch.columns, batch.nulls, batch.valid
                )
                from ballista_tpu.ops.fetch import fetch_arrays

                (ovf_h,) = fetch_arrays([ovf], site="mesh_window.overflow")
                jax.block_until_ready(out_valid)
            if not np.any(ovf_h):
                break
            if bcap >= per or attempt == MAX_MESH_RETRIES - 1:
                raise CapacityError(
                    "mesh window bucket overflow after retries",
                    required=per * self.n_dev,
                )
            bcap *= 2
        return out_cols, out_nulls, out_valid

    def _window_program(self, batch, key_idxs, local_fn, n_out, bcap,
                        fn_key=None):
        key = (
            "window", str(batch.schema), batch.capacity, key_idxs,
            fn_key if fn_key is not None else id(local_fn), n_out, bcap,
            tuple(m is None for m in batch.nulls),
        )
        prog = self._programs.get(key)
        if prog is None:
            prog = self._compile_window(
                batch, key_idxs, local_fn, n_out, bcap
            )
            self._programs[key] = prog
        return prog

    def _compile_window(self, batch, key_idxs, local_fn, n_out, bcap):
        axis, n_dev = self.axis, self.n_dev

        def f(cols, nulls, valid):
            ecols, enulls, evalid, ovf = exchange_by_key(
                cols, nulls, valid, key_idxs, axis, n_dev, bcap
            )
            out_cols, out_nulls = local_fn(
                list(ecols), list(enulls), evalid
            )
            out_nulls = tuple(
                jnp.zeros(c.shape[0], dtype=bool) if m is None else m
                for c, m in zip(out_cols, out_nulls)
            )
            return tuple(out_cols), out_nulls, evalid, ovf.reshape(1)

        in_specs = (
            self._leaf_specs(batch.columns),
            self._leaf_specs(batch.nulls),
            P(axis),
        )
        n = len(batch.columns) + n_out
        out_specs = (
            tuple(P(axis) for _ in range(n)),
            tuple(P(axis) for _ in range(n)),
            P(axis),
            P(axis),
        )
        sm = shard_map(
            _stage_program("mesh_window_stage", "MeshWindowExec", f),
            mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(sm)

    # -- partitioned join -----------------------------------------------------
    def join(
        self,
        left: DeviceBatch,
        right: DeviceBatch,
        left_keys: list[int],
        right_keys: list[int],
        join_type: JoinSide = JoinSide.INNER,
        bucket_cap: int | None = None,
        filter_fn=None,
        out_cap: int | None = None,
    ) -> DeviceBatch:
        """PARTITIONED-mode join (ref HashJoinExecNode PartitionMode
        PARTITIONED, ballista.proto:474-487): exchange BOTH sides by join
        key over ICI, then build+probe locally per device.

        Key packing follows the local tier (ops/join.py): exact single-int,
        exact2 two-int, or hashed with window-verified probes. Duplicate
        build keys run the m:n expansion path; the expansion output
        capacity and the exchange bucket capacity grow on overflow and the
        program re-dispatches (the inputs are already on device).

        ``filter_fn``: optional traceable residual filter
        ``f(joined_batch) -> bool[rows]`` applied inside the program
        (INNER joins only — the caller enforces that restriction).
        """
        # String keys join by dictionary code. The compiled program bakes no
        # dictionary knowledge, so the shared-dictionary contract must be
        # re-validated on EVERY call (a program-cache hit would otherwise
        # skip the trace-time check and join mismatched codes).
        for li, ri in zip(left_keys, right_keys):
            lf = left.schema.fields[li]
            rf = right.schema.fields[ri]
            if DataType.STRING in (lf.dtype, rf.dtype):
                ld = left.dictionaries.get(lf.name)
                rd = right.dictionaries.get(rf.name)
                if ld is None or rd is None or ld.values != rd.values:
                    raise ExecutionError(
                        f"mesh join key {lf.name!r}/{rf.name!r} requires a "
                        "shared dictionary; unify dictionaries before "
                        "sharding"
                    )
        # pack mode decided host-side on the build (right) batch — static
        # for the compiled program; probe packs with the same mode
        mode = _choose_pack_mode(right, list(right_keys))
        bcap = bucket_cap or max(
            left.capacity // self.n_dev, right.capacity // self.n_dev, 1
        )
        # post-exchange local probe length is n_dev * bucket_cap; a unique
        # build emits at most one row per probe row
        ocap = out_cap or self.n_dev * bcap

        for attempt in range(MAX_MESH_RETRIES):
            prog = self._join_program(
                left, right, tuple(left_keys), tuple(right_keys),
                join_type, bcap, mode, ocap, filter_fn,
            )
            with _COLLECTIVE_LOCK:
                cols, nulls, valid, bucket_ovf, run_ovf, exp_ovf, totals = (
                    prog(
                        left.columns, left.nulls, left.valid,
                        right.columns, right.nulls, right.valid,
                    )
                )
                from ballista_tpu.ops.fetch import fetch_arrays

                # fetch doubles as the completion barrier the lock needs
                bucket_ovf, run_ovf, exp_ovf, totals = fetch_arrays(
                    [bucket_ovf, run_ovf, exp_ovf, totals],
                    site="mesh_join.overflow",
                )
                jax.block_until_ready(valid)
            if np.any(run_ovf):
                raise ExecutionError(
                    "mesh join build side has a packed-hash collision run "
                    "longer than the probe window; use integer join keys "
                    "or reduce build size"
                )
            if np.any(bucket_ovf):
                # grown capacities snap to the bucket ladder (like the
                # exec/base.py retry path) so mesh retries land on shared
                # compiled-program signatures under non-pow2 ladders too
                bcap = round_capacity(bcap * 2)
                ocap = max(ocap, round_capacity(self.n_dev * bcap))
                continue
            if np.any(exp_ovf):
                required = int(np.max(totals))
                ocap = round_capacity(max(required + 1, ocap * 2))
                continue
            break
        else:
            raise CapacityError(
                "mesh join exceeded static capacities after retries",
                required=int(np.max(totals)),
            )
        if join_type in (JoinSide.SEMI, JoinSide.ANTI):
            out_schema = left.schema
        elif join_type == JoinSide.LEFT:
            out_schema = left.schema.join(
                Schema([Field(f.name, f.dtype, True) for f in right.schema])
            )
        else:
            out_schema = left.schema.join(right.schema)
        dicts = dict(left.dictionaries)
        if join_type not in (JoinSide.SEMI, JoinSide.ANTI):
            dicts.update(right.dictionaries)
        return DeviceBatch(
            schema=out_schema,
            columns=tuple(cols),
            valid=valid,
            nulls=tuple(nulls),
            dictionaries=dicts,
        )

    def _join_program(
        self, left, right, left_keys, right_keys, join_type, bucket_cap,
        mode, out_cap, filter_fn,
    ):
        key = (
            "join",
            str(left.schema), left.capacity,
            str(right.schema), right.capacity,
            left_keys, right_keys, join_type, bucket_cap, mode, out_cap,
            id(filter_fn) if filter_fn is not None else None,
            tuple(m is None for m in left.nulls),
            tuple(m is None for m in right.nulls),
        )
        prog = self._programs.get(key)
        if prog is None:
            prog = self._compile_join(
                left, right, left_keys, right_keys, join_type, bucket_cap,
                mode, out_cap, filter_fn,
            )
            self._programs[key] = prog
        return prog

    def _compile_join(
        self, left, right, left_keys, right_keys, join_type, bucket_cap,
        mode, out_cap, filter_fn,
    ):
        axis, n_dev = self.axis, self.n_dev
        l_schema, r_schema = left.schema, right.schema
        l_dicts = dict(left.dictionaries)
        r_dicts = dict(right.dictionaries)
        semi_anti = join_type in (JoinSide.SEMI, JoinSide.ANTI)

        def f(lcols, lnulls, lvalid, rcols, rnulls, rvalid):
            lc, ln, lv, l_ovf = exchange_by_key(
                lcols, lnulls, lvalid, left_keys, axis, n_dev, bucket_cap
            )
            rc, rn, rv, r_ovf = exchange_by_key(
                rcols, rnulls, rvalid, right_keys, axis, n_dev, bucket_cap
            )
            # build the right side locally under the static pack mode
            dead = ~rv
            for i in right_keys:
                if rn[i] is not None:
                    dead = dead | rn[i]
            packed = _pack_key([rc[i] for i in right_keys], mode)
            passes = [(dead, False), (packed, False)]
            if mode == "hash":
                # tie-break on actual keys: duplicate keys land adjacent
                passes.extend((rc[i], False) for i in right_keys)
            perm = multi_key_perm(passes)
            rbatch = DeviceBatch(
                schema=r_schema,
                columns=rc,
                valid=rv,
                nulls=rn,
                dictionaries=r_dicts,
            )
            bt = dataclasses.replace(
                _build_finish(perm, dead, rbatch, right_keys, mode),
                batch=rbatch,
            )
            lbatch = DeviceBatch(
                schema=l_schema,
                columns=lc,
                valid=lv,
                nulls=ln,
                dictionaries=l_dicts,
            )
            first, count, live = probe_counts(bt, lbatch, list(left_keys))
            bucket_ovf = (l_ovf | r_ovf).reshape(1)
            run_ovf = bt.run_overflow.reshape(1)
            if semi_anti:
                m = count > 0
                keep = m if join_type == JoinSide.SEMI else ~m
                out = lbatch.with_valid(lbatch.valid & keep)
                zero = jnp.zeros(1, dtype=jnp.int32)
                out_nulls = tuple(
                    jnp.zeros(c.shape[0], dtype=bool) if nm is None else nm
                    for c, nm in zip(out.columns, out.nulls)
                )
                return (
                    out.columns, out_nulls, out.valid,
                    bucket_ovf, run_ovf,
                    jnp.zeros(1, dtype=bool), zero,
                )
            if join_type == JoinSide.LEFT:
                eff = jnp.where(lbatch.valid, jnp.maximum(count, 1), 0)
                ekind = JoinSide.LEFT
            else:
                eff = count
                ekind = JoinSide.INNER
            total = jnp.sum(eff).astype(jnp.int32).reshape(1)
            exp_ovf = (total > out_cap).reshape(1)
            batch, i, k, real = expand_join(
                bt, lbatch, first, count, eff, out_cap, ekind
            )
            if filter_fn is not None:
                passes_f = filter_fn(batch) & real
                batch = batch.with_valid(batch.valid & passes_f)
            out_nulls = tuple(
                jnp.zeros(c.shape[0], dtype=bool) if m is None else m
                for c, m in zip(batch.columns, batch.nulls)
            )
            return (
                batch.columns, out_nulls, batch.valid,
                bucket_ovf, run_ovf, exp_ovf, total,
            )

        in_specs = (
            self._leaf_specs(left.columns),
            self._leaf_specs(left.nulls),
            P(axis),
            self._leaf_specs(right.columns),
            self._leaf_specs(right.nulls),
            P(axis),
        )
        if semi_anti:
            n_out = len(l_schema)
        else:
            n_out = len(l_schema) + len(r_schema)
        out_specs = (
            tuple(P(axis) for _ in range(n_out)),
            tuple(P(axis) for _ in range(n_out)),
            P(axis),
            P(axis),
            P(axis),
            P(axis),
            P(axis),
        )
        sm = shard_map(
            _stage_program("mesh_join_stage", "MeshJoinExec", f),
            mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(sm)
