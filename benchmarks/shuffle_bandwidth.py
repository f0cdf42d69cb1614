#!/usr/bin/env python
"""Shuffle-bandwidth micro-benchmark (the BASELINE.md north-star metric
names "shuffle GB/s over ICI").

Two tiers are measured, matching the engine's two shuffle paths:

1. **Mesh collective shuffle**: one jitted ``shard_map`` ``all_to_all``
   over the available device mesh — the on-pod path SQL stages use
   (parallel/stage.py). On real multi-chip hardware this rides ICI; under
   ``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4``
   it validates the same program on the virtual mesh (numbers then
   characterize host memcpy, not ICI — the harness labels which).
2. **Local device hash partition**: partition-id hashing + stacked
   gather into bucket order on one chip — the file/Flight shuffle's
   device-side cost (executor/shuffle.py).

Usage: python benchmarks/shuffle_bandwidth.py [--mb 256] [--parts 8]
Prints conbench-style JSON records like benchmarks/micro.py.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _amortized(fn, *args, reps=6):
    """Dispatch N times, fetch one scalar once — amortises the blocking
    host round trip out of the measurement."""
    import numpy as np

    out = fn(*args)
    np.asarray(out.reshape(-1)[:1])

    def run_k(k):
        t0 = time.time()
        for _ in range(k):
            out = fn(*args)
        np.asarray(out.reshape(-1)[:1])
        return time.time() - t0

    t1 = min(run_k(1) for _ in range(2))
    tn = min(run_k(reps) for _ in range(2))
    return max((tn - t1) / (reps - 1), 1e-9)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--mb", type=int, default=256,
                   help="payload megabytes per measurement")
    p.add_argument("--parts", type=int, default=8)
    p.add_argument("-o", "--output")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import ballista_tpu  # noqa: F401 — enables x64

    records = []
    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())

    # -- tier 1: mesh all_to_all ------------------------------------------
    if n_dev >= 2:
        from jax import shard_map
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()), ("x",))
        per_dev = (args.mb << 20) // (n_dev * 4)
        rows = per_dev - (per_dev % n_dev)
        x = jax.device_put(
            jnp.ones((n_dev * rows,), jnp.float32),
            NamedSharding(mesh, P("x")),
        )

        @jax.jit
        def a2a(x):
            def f(xs):  # xs: (rows,) local shard
                blocks = xs.reshape(n_dev, rows // n_dev)
                return jax.lax.all_to_all(
                    blocks, "x", split_axis=0, concat_axis=0, tiled=False
                ).reshape(-1)

            return shard_map(
                f, mesh=mesh, in_specs=P("x"), out_specs=P("x")
            )(x)

        dt = _amortized(a2a, x)
        moved = n_dev * rows * 4  # every element crosses the interconnect
        records.append(
            {
                "name": "shuffle_all_to_all",
                "tags": {
                    "platform": platform,
                    "devices": n_dev,
                    "interconnect": "ici" if platform == "tpu" else "host",
                },
                "seconds": round(dt, 6),
                "gb_per_s": round(moved / dt / 1e9, 3),
                "bytes": moved,
            }
        )
    else:
        records.append(
            {
                "name": "shuffle_all_to_all",
                "tags": {"platform": platform, "devices": n_dev},
                "skipped": "needs >= 2 devices (run under a virtual "
                "CPU mesh or a TPU pod slice)",
            }
        )

    # -- tier 2: single-device hash partition ------------------------------
    from ballista_tpu.ops.hashing import hash_columns
    from ballista_tpu.ops.perm import stable_argsort

    rows = (args.mb << 20) // 8
    r = np.random.default_rng(0)
    keys = jnp.asarray(r.integers(0, 1 << 30, rows).astype(np.int64))
    payload = jnp.asarray(r.integers(0, 1 << 30, rows).astype(np.int64))
    parts = args.parts

    @jax.jit
    def hash_partition(keys, payload):
        pid = (hash_columns([keys]).view(jnp.int64) % parts).astype(
            jnp.int32
        )
        order = stable_argsort(pid)
        return payload[order]

    dt = _amortized(hash_partition, keys, payload)
    moved = rows * 8 * 2  # key read + payload move (bucket-ordered write)
    records.append(
        {
            "name": "shuffle_hash_partition_local",
            "tags": {"platform": platform, "partitions": parts},
            "seconds": round(dt, 6),
            "gb_per_s": round(moved / dt / 1e9, 3),
            "bytes": moved,
        }
    )

    out = "\n".join(json.dumps(rec) for rec in records)
    print(out)
    if args.output:
        Path(args.output).write_text(out + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
