#!/usr/bin/env python
"""Kernel micro-benchmarks with conbench-style JSON output.

ref conbench/{benchmarks.py,_criterion.py} — the reference publishes
criterion micro-bench results (per-benchmark name + timing stats) to a
conbench server. Here the engine's kernel primitives are timed directly
(sort, grouped aggregate, join build/probe, hash partition, compaction)
and the same record shape is written to stdout / --output, ready for a
conbench POST or plain regression diffing.

Timing note: dispatch is asynchronous, so each sample times `run -> tiny
fetch` and subtracts the measured round-trip baseline.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    p = argparse.ArgumentParser(description="kernel micro-benchmarks")
    p.add_argument("--rows", type=int, default=1 << 20)
    p.add_argument("--samples", type=int, default=5)
    p.add_argument("-o", "--output", help="write JSON records here")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import ballista_tpu  # noqa: F401 — enables x64
    from ballista_tpu.ops.aggregate import AggOp, group_aggregate
    from ballista_tpu.ops.compact import compact
    from ballista_tpu.ops.join import JoinSide, build_side, probe_side
    from ballista_tpu.ops.partition import partition_ids
    from ballista_tpu.ops.perm import stable_argsort
    from ballista_tpu.columnar.batch import DeviceBatch
    from ballista_tpu.datatypes import DataType, Field, Schema

    n = args.rows
    r = np.random.default_rng(0)
    keys = jnp.asarray(r.integers(0, n // 4, n).astype(np.int64))
    vals = jnp.asarray(r.uniform(0, 100, n))
    valid = jnp.ones(n, dtype=bool)
    schema = Schema([Field("k", DataType.INT64), Field("v", DataType.FLOAT64)])
    batch = DeviceBatch(
        schema=schema, columns=(keys, vals), valid=valid,
        nulls=(None, None), dictionaries={},
    )
    dim_n = max(n // 16, 8)
    dim = DeviceBatch(
        schema=schema,
        columns=(
            jnp.asarray(np.arange(dim_n, dtype=np.int64)),
            jnp.asarray(r.uniform(0, 1, dim_n)),
        ),
        valid=jnp.ones(dim_n, dtype=bool),
        nulls=(None, None),
        dictionaries={},
    )

    trivial = jax.jit(lambda: jnp.zeros(()))
    np.asarray(trivial())
    t0 = time.time()
    np.asarray(trivial())
    rtt = time.time() - t0

    bt = build_side(dim, [0])

    cases = {
        "stable_argsort_i64": lambda: stable_argsort(keys),
        "group_aggregate_sum_count": lambda: group_aggregate(
            [keys], [None], valid, [vals, vals], [None, None],
            [AggOp.SUM, AggOp.COUNT], 1 << 18,
        ).n_groups,
        "join_build": lambda: build_side(dim, [0]).n,
        "join_probe": lambda: probe_side(bt, batch, [0], JoinSide.INNER).valid,
        "hash_partition_ids_8": lambda: partition_ids(batch, [0], 8),
        "compact": lambda: compact(batch).valid,
    }

    records = []
    for name, fn in cases.items():
        fn()  # compile
        samples = []
        for _ in range(args.samples):
            t0 = time.time()
            out = fn()
            leaf = jax.tree_util.tree_leaves(out)[0]
            np.asarray(leaf.reshape(-1)[:1] if leaf.ndim else leaf)
            samples.append(max(time.time() - t0 - rtt, 0.0))
        rec = {
            "run_name": "ballista-tpu-micro",
            "benchmark_name": name,
            "unit": "s",
            "rows": n,
            "stats": {
                "mean": statistics.mean(samples),
                "min": min(samples),
                "max": max(samples),
                "iterations": len(samples),
            },
        }
        records.append(rec)
        print(
            f"{name}: min {rec['stats']['min'] * 1000:.2f} ms "
            f"mean {rec['stats']['mean'] * 1000:.2f} ms over {n} rows"
        )
    if args.output:
        Path(args.output).write_text(json.dumps(records, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
