"""Device mesh construction and batch sharding.

The mesh is 1-D over the shuffle axis: stage partitions map to mesh slots
exactly like the reference maps stage partitions to executor task slots
(ballista/rust/scheduler/src/state/task_scheduler.rs:53-211) — except here
"executors" are chips on ICI and placement is XLA's job.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ballista_tpu.columnar.batch import DeviceBatch, round_capacity
from ballista_tpu.errors import ExecutionError
from ballista_tpu.obs import trace as obs_trace
from ballista_tpu.ops.fetch import read_array

SHARD_AXIS = "shards"


def make_mesh(n_devices: int | None = None, axis: str = SHARD_AXIS) -> Mesh:
    """1-D mesh over the first ``n_devices`` local devices."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ExecutionError(
                f"need {n_devices} devices, have {len(devs)} "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "JAX_PLATFORMS=cpu for a virtual CPU mesh)"
            )
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def row_sharding(mesh: Mesh, axis: str = SHARD_AXIS) -> NamedSharding:
    return NamedSharding(mesh, P(axis))


def shard_batch(
    mesh: Mesh,
    batch: DeviceBatch,
    axis: str = SHARD_AXIS,
    local_capacity: int | None = None,
) -> DeviceBatch:
    """Distribute a host-visible batch across the mesh's row axis.

    Output arrays have global length ``n_dev * local_capacity`` with rows
    round-robin-packed into per-device blocks (block d = rows for device d);
    masked slots pad each block.
    """
    n_dev = mesh.devices.size
    valid_h = read_array(batch.valid, "mesh.shard")
    n = int(np.sum(valid_h))
    per_dev = -(-n // n_dev)  # ceil
    cap = local_capacity or round_capacity(max(per_dev, 1))
    if per_dev > cap:
        raise ExecutionError(
            f"local capacity {cap} < {per_dev} rows per device"
        )
    live = np.flatnonzero(valid_h)
    sh = row_sharding(mesh, axis)

    def place(col, fill=0):
        col = read_array(col, "mesh.shard")
        out = np.full((n_dev * cap,) + col.shape[1:], fill, dtype=col.dtype)
        for d in range(n_dev):
            rows = live[d::n_dev]
            out[d * cap : d * cap + len(rows)] = col[rows]
        with obs_trace.phase("task.h2d", nbytes=out.nbytes):
            return jax.device_put(out, sh)

    valid = np.zeros(n_dev * cap, dtype=bool)
    for d in range(n_dev):
        valid[d * cap : d * cap + len(live[d::n_dev])] = True
    return DeviceBatch(
        schema=batch.schema,
        columns=tuple(place(c) for c in batch.columns),
        valid=jax.device_put(valid, sh),
        nulls=tuple(
            None if m is None else place(m, fill=True) for m in batch.nulls
        ),
        dictionaries=dict(batch.dictionaries),
    )


def is_row_sharded(batch: DeviceBatch, mesh: Mesh, axis: str = SHARD_AXIS) -> bool:
    """True when the batch's arrays are already sharded over this mesh's
    row axis (the invariant mesh stage outputs maintain) — lets a chain of
    mesh operators compose without host round-trips."""
    want = NamedSharding(mesh, P(axis))
    try:
        return all(
            getattr(c, "sharding", None) is not None
            and c.sharding.is_equivalent_to(want, c.ndim)
            for c in batch.columns + (batch.valid,)
        )
    except Exception:
        return False


def unshard_batch(batch: DeviceBatch) -> DeviceBatch:
    """Gather a mesh-sharded batch back to one addressable batch (host
    gather — the client collect path, not a hot path)."""

    def gather(x):
        return jnp.asarray(read_array(x, "mesh.unshard"))

    return DeviceBatch(
        schema=batch.schema,
        columns=tuple(gather(c) for c in batch.columns),
        valid=gather(batch.valid),
        nulls=tuple(None if m is None else gather(m) for m in batch.nulls),
        dictionaries=dict(batch.dictionaries),
    )


