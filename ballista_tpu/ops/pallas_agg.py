"""Pallas TPU kernel: one-hot matmul grouped reduction.

The dense aggregation path (ops/aggregate.py ``_stacked_reduce``) reduces
per-row contributions into a small number of group slots. In plain XLA the
options are a scatter-add (serialized random access, ~840ms for 8.4M rows
x 4 f64 columns on a v5e) or a chunked one-hot matmul (the materialized
one-hot round-trips HBM and f64 dots are software-emulated: ~225ms). This
kernel keeps the one-hot entirely in VMEM — each grid step builds a
(P, B) f32 one-hot for its row block and feeds the MXU directly — and runs
the same reduction in ~2ms (measured, 8.4M rows, P=26, 8 value columns):
HBM traffic collapses to the operands themselves.

Numerics: f64 value columns are split into exact f32 (hi, lo) pairs
host-side (48-bit significand coverage); products against the 0/1 one-hot
are exact on the MXU at HIGHEST precision, so the only error source is
f32 accumulation. What decides it is the LENGTH OF ONE DOT, the block's
row count: on a v5e, 2M rows into 12 slots came out 1.8e-6 off an f64
reference with 32768-row blocks, 1.3e-7 with 8192, 2.4e-8 with 2048 and
4e-9 with 512, while the number of blocks accumulated into one f32
partial (1, 8, 16 or 64) moved nothing (chip run of PR 21, PERF.md). So
blocks are capped at ``_MAX_BLOCK_ROWS`` = 2048 rows (1.16 ms against
0.93 ms for those 2M rows), partials cover ``_PARTIAL_ROWS`` rows and are
summed in f64. ~2e-8 is still above what small-data unit tests assert
(rtol=1e-9), which is why callers gate this path to large batches.

Counts (0/1 contributions) are exact: per-block partials stay below 2^24
(f32's exact-integer range) and the cross-block sum runs in f64.

The reference engine has no analogue — DataFusion accumulates per-group in
a row-oriented hash table (the workload this replaces is the accumulate
loop behind ballista.proto:275-623 HashAggregateExecNode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ballista_tpu.compilecache import metrics

# Rows accumulated into one f32 partial before the f64 cross-partial sum.
# It bounds the partial outputs ((n / _PARTIAL_ROWS, P, R) f32), not the
# error: see the module note.
_PARTIAL_ROWS = 32768

# Rows of one grid step, i.e. the length of one f32 dot: the error bound.
_MAX_BLOCK_ROWS = 2048

# VMEM budget for the (P, B) one-hot: B*P*4 bytes <= ~6MB.
_ONEHOT_VMEM_BYTES = 6 << 20


def _block_rows(P: int) -> int:
    b = _ONEHOT_VMEM_BYTES // (4 * max(P, 1))
    return max(512, min(_MAX_BLOCK_ROWS, (b // 512) * 512))


def available() -> bool:
    """The kernel is the TPU's path and only the TPU's. No trial compile
    guards it: a shape Mosaic refuses raises where the query lowers it,
    it does not become a silent switch to the XLA one-hot path."""
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=None)
def _program(n: int, R: int, P: int):
    """(rid (1, n) i32, matT (R, n) f32) -> (P, R) f64 group sums.

    Rows with rid outside [0, P) contribute nothing (the one-hot matches
    no slot) — callers encode dropped rows as rid == P.
    """
    from jax.experimental import pallas as pl

    B = min(_block_rows(P), n)
    nb = -(-n // B)
    sup = max(1, _PARTIAL_ROWS // B)  # grid steps per f32 partial
    nb2 = -(-nb // sup)

    def kernel(rid_ref, mat_ref, out_ref):
        g = pl.program_id(0)

        @pl.when(g % sup == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        oh = (
            jax.lax.broadcasted_iota(jnp.int32, (P, B), 0)
            == rid_ref[0, :][None, :]
        ).astype(jnp.float32)
        # out (P, R) = oh (P, B) . matT (R, B) contracted over B
        out_ref[...] += jax.lax.dot_general(
            oh,
            mat_ref[...],
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )[None]

    def agg_onehot_sums(rid2, matT):
        # Mosaic rejects 64-bit index types; trace the call in x32 mode
        # (operands are i32/f32 by construction).
        with jax.enable_x64(False):
            call = pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct((nb2, P, R), jnp.float32),
                grid=(nb,),
                in_specs=[
                    pl.BlockSpec((1, B), lambda g: (0, g)),
                    pl.BlockSpec((R, B), lambda g: (0, g)),
                ],
                out_specs=pl.BlockSpec(
                    (1, P, R), lambda g: (g // sup, 0, 0)
                ),
                # the kernel's own name on the device trace
                name="agg_onehot_kernel",
            )
            pad = nb * B - n
            if pad:
                rid2 = jnp.pad(rid2, ((0, 0), (0, pad)), constant_values=P)
                matT = jnp.pad(matT, ((0, 0), (0, pad)))
            partials = call(rid2, matT)
        return partials.astype(jnp.float64).sum(axis=0)

    return jax.jit(agg_onehot_sums)


def onehot_sums(rid: jnp.ndarray, rows: list[jnp.ndarray], P: int):
    """Sum each f32 row-vector of ``rows`` into ``P`` slots keyed by
    ``rid`` (i32[n]; values outside [0, P) are dropped). Returns
    (P, len(rows)) f64. Traceable under jit."""
    # runs at trace time: one count per program the kernel was staged into
    # (chip_smoke.py reads it to show q1 took this path, not the XLA one)
    metrics.add("pallas_onehot_traces")
    matT = jnp.stack([r.astype(jnp.float32) for r in rows], axis=0)
    rid2 = rid.astype(jnp.int32).reshape(1, -1)
    return _program(rid2.shape[1], len(rows), P)(rid2, matT)


def split_hi_lo(col: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact f64 -> (hi, lo) f32 pair (hi = f32(x), lo = f32(x - hi));
    hi + lo reproduces the input to 48 significand bits."""
    hi = col.astype(jnp.float32)
    lo = (col - hi.astype(jnp.float64)).astype(jnp.float32)
    return hi, lo
