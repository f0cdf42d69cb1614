#!/usr/bin/env python
"""h2oai db-benchmark (groupby + join) adaptation.

ref benchmarks/db-benchmark/{groupby-datafusion.py,join-datafusion.py} —
the standard G1 groupby questions and the join benchmark, run over the
engine with synthetic data matching the h2o generator's shape (no egress:
the official x.csv inputs aren't downloadable here; pass --data to use a
real G1 file). Questions the engine doesn't support yet are skipped with
a note, mirroring how the reference comments out unsupported questions.

This script runs the questions through the local ``TpuContext`` and has
produced no tracked number. The tracked measurement of the group-by
questions is the benchmark's cell ``h2o-g1-1e7-mem.groupby`` (BENCHMARK.json,
``perf/queries/g1q{3,5,2,7}.sql``: questions 3, 5, 2 and 7 of the SQL below
on the served path at 1e7 rows, each answer held to a plain reference;
PERF.md §4), and of the advanced questions 6 and 8 the cell
``h2o-g1-1e7-adv-mem.advanced`` (``perf/queries/g1q{6,8}.sql``: the exact
median and the sample deviation by two keys, the two largest per key with
their row numbers).

Usage: python benchmarks/db_benchmark.py [--n 1e6] [--k 100] [--iterations 2]
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

GROUPBY_QUERIES = {
    # ref groupby-datafusion.py:73-226 — all ten G1 questions run,
    # including q6's approx_percentile_cont (exact sort-based percentile,
    # exec/percentile.py)
    "q1": "SELECT id1, SUM(v1) AS v1 FROM x GROUP BY id1",
    "q2": "SELECT id1, id2, SUM(v1) AS v1 FROM x GROUP BY id1, id2",
    "q3": "SELECT id3, SUM(v1) AS v1, AVG(v3) AS v3 FROM x GROUP BY id3",
    "q4": "SELECT id4, AVG(v1) AS v1, AVG(v2) AS v2, AVG(v3) AS v3 "
          "FROM x GROUP BY id4",
    "q5": "SELECT id6, SUM(v1) AS v1, SUM(v2) AS v2, SUM(v3) AS v3 "
          "FROM x GROUP BY id6",
    "q6": "SELECT id4, id5, approx_percentile_cont(v3, 0.5) AS median_v3, "
          "stddev(v3) AS stddev_v3 FROM x GROUP BY id4, id5",
    "q7": "SELECT id3, MAX(v1) - MIN(v2) AS range_v1_v2 FROM x GROUP BY id3",
    "q8": "SELECT id6, v3 from (SELECT id6, v3, row_number() OVER "
          "(PARTITION BY id6 ORDER BY v3 DESC) AS row FROM x) t "
          "WHERE row <= 2",
    "q9": "SELECT id2, id4, corr(v1, v2) as corr FROM x GROUP BY id2, id4",
    "q10": "SELECT id1, id2, id3, id4, id5, id6, SUM(v3) as v3, "
           "COUNT(*) AS cnt FROM x GROUP BY id1, id2, id3, id4, id5, id6",
}

JOIN_QUERY = (
    "SELECT x.id1, x.v1, small.v2 FROM x JOIN small ON x.id1 = small.id1"
)


def gen_g1(n: int, k: int):
    """Synthetic G1 table with the h2o generator's column shape."""
    import numpy as np
    import pyarrow as pa

    r = np.random.default_rng(1)
    return pa.table(
        {
            "id1": pa.array([f"id{v:03d}" for v in r.integers(1, k + 1, n)]),
            "id2": pa.array([f"id{v:03d}" for v in r.integers(1, k + 1, n)]),
            "id3": pa.array(
                [f"id{v:010d}" for v in r.integers(1, max(n // k, 1) + 1, n)]
            ),
            "id4": pa.array(r.integers(1, k + 1, n).astype("int64")),
            "id5": pa.array(r.integers(1, k + 1, n).astype("int64")),
            "id6": pa.array(
                r.integers(1, max(n // k, 1) + 1, n).astype("int64")
            ),
            "v1": pa.array(r.integers(1, 6, n).astype("int64")),
            "v2": pa.array(r.integers(1, 16, n).astype("int64")),
            "v3": pa.array(np.round(r.uniform(0, 100, n), 6)),
        }
    )


def main() -> int:
    p = argparse.ArgumentParser(description="h2oai db-benchmark")
    p.add_argument("--n", type=float, default=1e6, help="rows")
    p.add_argument("--k", type=int, default=100, help="group cardinality")
    p.add_argument("--iterations", type=int, default=2)
    p.add_argument("--data", help="real G1 x.csv (default: synthetic)")
    args = p.parse_args()

    import numpy as np
    import pyarrow as pa

    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.exec.context import TpuContext

    ctx = TpuContext(
        BallistaConfig().with_setting("ballista.shuffle.partitions", "1")
    )
    n = int(args.n)
    if args.data:
        ctx.register_csv("x", args.data)
    else:
        t0 = time.time()
        ctx.register_table("x", gen_g1(n, args.k))
        print(f"generated {n} rows in {time.time() - t0:.2f}s")

    for name, sql in GROUPBY_QUERIES.items():
        for i in range(args.iterations):
            t0 = time.time()
            res = ctx.sql(sql).collect()
            print(
                f"groupby {name} run {i + 1}: {(time.time() - t0) * 1000:.0f} "
                f"ms ({res.num_rows} groups)"
            )

    # join benchmark (ref join-datafusion.py): x joined to a small dim
    r = np.random.default_rng(2)
    small = pa.table(
        {
            "id1": pa.array([f"id{v:03d}" for v in range(1, args.k + 1)]),
            "v2": pa.array(r.uniform(0, 100, args.k)),
        }
    )
    ctx.register_table("small", small)
    for i in range(args.iterations):
        t0 = time.time()
        res = ctx.sql(JOIN_QUERY).collect()
        print(
            f"join small run {i + 1}: {(time.time() - t0) * 1000:.0f} ms "
            f"({res.num_rows} rows)"
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
