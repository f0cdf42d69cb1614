"""Adaptive capacity shrink: re-bucket sparse batches to a small capacity.

Filters and selective joins in this engine only clear validity bits, so a
highly selective operator (TPC-H q18: a HAVING that keeps ~60 of 1.5M
groups) leaves a batch whose capacity is orders of magnitude larger than
its live row count — and every downstream sort pass, gather, and scatter
still pays the FULL capacity. This helper compacts live rows to the front
and slices the batch down to a learned power-of-two capacity, so the rest
of the plan runs at the data's true scale.

The learned capacity rides the cross-query plan cache exactly like join
build strategies and expansion capacities (exec/joins.py): the first run
at a site pays one host sync to count live rows and decides (ratio test —
shrinking costs one compaction, only worth it when the capacity drops by
>= 64x); later runs reuse the cached capacity speculatively, validated by
a deferred device flag so a grown input triggers invalidate-and-retry via
SpeculationMiss. Keys this run itself synced stay non-speculative (see
TaskContext.run_state) so multi-batch sites converge.

The reference has no analogue — DataFusion batches are dynamically sized,
so selectivity shrinks them for free; this is the static-shape engine's
equivalent of that behavior.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ballista_tpu.columnar.batch import DeviceBatch, round_capacity

# Below this capacity a shrink cannot pay for its own compaction.
SHRINK_MIN_CAP = 4096
# Shrink only when the new capacity is at most old/RATIO. The compaction
# pass costs ~a bool argsort of the OLD capacity plus a new-capacity
# gather (~40ms at 8.4M on a v5e) with no knowledge of how much
# downstream work it saves. With the round-4 kernel work the downstream
# ops this pays into (probe gathers, build sorts, boundary gathers) all
# scale with capacity, so a modest bar wins: at RATIO=4 TPC-H q5 drops
# 1.12s -> 0.77s (the filtered-orders build and post-join probes run at
# 1/4 capacity) while the worst case — a selective filter feeding a
# one-op tail, q6 — pays ~35ms. The old bar of 64 left both on the table.
SHRINK_RATIO = 4
# Learned capacity = round_capacity(HEADROOM * live): room for modest
# growth before the speculation flag fires.
SHRINK_HEADROOM = 2


@functools.lru_cache(maxsize=None)
def _shrink_program(
    sig: tuple, nulls_sig: tuple, old_cap: int, new_cap: int
):
    """Compact live rows to the front and slice to ``new_cap`` — one jitted
    program. The gather runs over the SLICED order (new_cap indices), so
    its cost scales with the small output, not the old capacity; only the
    bool argsort pass touches the full batch."""
    from ballista_tpu.ops.perm import argsort_i32, take_many_split

    def shrink_compact(cols, nulls, valid):
        order = argsort_i32((~valid).astype(jnp.int32))[:new_cap]
        out_cols, out_nulls = take_many_split(
            list(cols), list(nulls), order
        )
        n_live = jnp.sum(valid.astype(jnp.int32))
        out_valid = jnp.arange(new_cap, dtype=jnp.int32) < n_live
        overflow = n_live > new_cap
        return tuple(out_cols), tuple(out_nulls), out_valid, overflow

    return jax.jit(shrink_compact)


def _run_shrink(batch: DeviceBatch, new_cap: int):
    sig = tuple(str(c.dtype) for c in batch.columns)
    nulls_sig = tuple(m is not None for m in batch.nulls)
    prog = _shrink_program(sig, nulls_sig, batch.capacity, new_cap)
    cols, nulls, valid, overflow = prog(
        tuple(batch.columns), tuple(batch.nulls), batch.valid
    )
    return (
        DeviceBatch(
            schema=batch.schema,
            columns=cols,
            valid=valid,
            nulls=nulls,
            dictionaries=dict(batch.dictionaries),
        ),
        overflow,
    )


def maybe_shrink(
    batch: DeviceBatch, ctx, site_display: str, partition: int
) -> DeviceBatch:
    """Shrink ``batch`` when this plan site is known (or now measured) to
    be highly selective. Safe no-op without a plan cache."""
    if ctx is None or ctx.plan_cache is None:
        return batch
    cap = batch.capacity
    if cap <= SHRINK_MIN_CAP:
        return batch
    # NO job_id in the key (unlike join strategy flags): a structural
    # collision across jobs merely fires the validation flag and re-learns,
    # while job scoping would cost every distributed query a blocking
    # first-sight sync per site (executors share one plan cache)
    key = ("shrink", site_display, partition, cap)
    cache = ctx.plan_cache
    synced = ctx.run_state.setdefault("synced_caps", set())
    cached = cache.get(key)
    if cached is not None and key not in synced:
        if cached == 0:  # learned: not selective enough to shrink
            return batch
        out, overflow = _run_shrink(batch, cached)
        ctx.defer_speculation(
            overflow,
            "cached shrink capacity went stale (live rows grew)",
            [key],
        )
        return out
    if cached == 0:
        # STICKY don't-shrink: a mixed-selectivity multi-batch site must
        # not oscillate (a later sparse batch re-learning a small capacity
        # would make the next run speculatively shrink the dense batch,
        # fire the overflow flag, and pay a full SpeculationMiss re-run on
        # every warm query)
        synced.add(key)
        return batch
    # first sight (this run): ONE host sync decides, then the decision is
    # cached across queries
    from ballista_tpu.ops.fetch import fetch_arrays

    n = int(fetch_arrays([batch.count_valid()], site="shrink.count")[0])
    new_cap = round_capacity(max(SHRINK_HEADROOM * n, SHRINK_MIN_CAP))
    if new_cap > cap // SHRINK_RATIO:
        cache[key] = 0
        synced.add(key)
        return batch
    cache[key] = max(new_cap, cache.get(key) or 0)
    synced.add(key)
    out, _ = _run_shrink(batch, new_cap)  # count known: cannot overflow
    return out
