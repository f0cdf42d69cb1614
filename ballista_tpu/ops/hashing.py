"""64-bit column hashing for shuffles and hash partitioning.

The reference hash-partitions RecordBatches row-wise with DataFusion's
``BatchPartitioner`` (ref ballista/rust/core/src/execution_plans/
shuffle_writer.rs:209-256). Here the row hash is computed on device for a
whole batch at once: a splitmix64 finalizer per column, combined across
columns — branch-free and vectorizable on the VPU.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# numpy scalars, not jnp: a jnp constant at import initialises a backend, so
# every process importing the engine (scheduler, client, CLI) would claim
# the chip that belongs to the executor.
_C1 = np.uint64(0x9E3779B97F4A7C15)
_C2 = np.uint64(0xBF58476D1CE4E5B9)
_C3 = np.uint64(0x94D049BB133111EB)


def _splitmix64(x: jnp.ndarray) -> jnp.ndarray:
    x = x + _C1
    x = (x ^ (x >> np.uint64(30))) * _C2
    x = (x ^ (x >> np.uint64(27))) * _C3
    return x ^ (x >> np.uint64(31))


def _to_u64(col: jnp.ndarray) -> jnp.ndarray:
    """Reinterpret any column as uint64 lanes.

    Floats hash by bit pattern of their float32 value: +0.0 is added first to
    canonicalize -0.0 (SQL-equal values must hash equal), and the f64->f32
    narrowing keeps equal inputs equal (collisions are fine — join probes
    verify actual columns). A 64-bit float bitcast is deliberately avoided:
    TPU's x64-rewrite pass does not implement f64 bitcast-convert.
    """
    if jnp.issubdtype(col.dtype, jnp.floating):
        canon = col.astype(jnp.float32) + jnp.float32(0.0)
        return canon.view(jnp.uint32).astype(jnp.uint64)
    return col.astype(jnp.uint64)


def hash_columns(cols: list[jnp.ndarray]) -> jnp.ndarray:
    """Row-wise combined hash of one or more columns -> uint64[n]."""
    h = jnp.zeros(cols[0].shape, dtype=jnp.uint64)
    for c in cols:
        h = _splitmix64(h ^ _splitmix64(_to_u64(c)))
    return h
