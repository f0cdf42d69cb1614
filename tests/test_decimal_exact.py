"""Exact decimal summation (VERDICT r4 item 4).

TPC-H money columns are decimal(15,2); float SUM's reduction order varies
across batch sizes, tiers, and backends, so checksums could never be
compared exactly. The engine detects decimal-valued f64 SUM inputs and
accumulates them as integral f64 at a learned scale
(exec/aggregate._dec_scaled_sums) — sums become order-independent and
BIT-EXACT. These tests assert exact equality (==, no rtol):

- across different batch sizes (different reduction orders) in-process;
- across processes: the in-proc run (the default backend) vs a subprocess
  forced to jax-cpu on the virtual mesh.

ref: Decimal128 end-to-end in the reference's expression vocabulary
(datafusion.proto:411-420); BASELINE.md "identical result checksums".
"""

import json
import pathlib
import subprocess
import sys

import numpy as np
import pyarrow as pa

from ballista_tpu.config import BallistaConfig
from ballista_tpu.exec.context import TpuContext
from tests.conftest import CPU_MESH_ENV


def _money_table(n=50_000, seed=5):
    rng = np.random.default_rng(seed)
    return pa.table(
        {
            "g": pa.array(rng.integers(0, 7, n).astype(np.int64)),
            # decimal(_,2) money values, exactly representable intent
            "price": pa.array(
                np.round(rng.uniform(1, 10_000, n), 2)
            ),
            "disc": pa.array(np.round(rng.uniform(0, 0.1, n), 2)),
            "qty": pa.array(
                np.round(rng.integers(1, 51, n).astype(np.float64), 2)
            ),
        }
    )


SQL = (
    "SELECT g, SUM(price) AS sp, SUM(price * (1 - disc)) AS srev, "
    "SUM(qty) AS sq, AVG(price) AS ap, COUNT(*) AS c "
    "FROM t GROUP BY g ORDER BY g"
)


def _run(batch_rows: int) -> dict:
    ctx = TpuContext(
        BallistaConfig()
        .with_setting("ballista.shuffle.partitions", "1")
        .with_setting("ballista.tpu.batch_rows", str(batch_rows))
    )
    ctx.register_table("t", _money_table())
    # warm-up runs: run 1 learns the partial-pass scales, run 2 learns the
    # merge-pass scales off now-exact partials, run 3 is fully exact
    ctx.sql(SQL).collect()
    ctx.sql(SQL).collect()
    return ctx.sql(SQL).collect().to_pandas().to_dict("list")


def test_money_sums_independent_of_batch_size():
    a = _run(4096)
    b = _run(50_000)
    c = _run(7177)  # odd size: different boundary splits entirely
    for col in ("sp", "srev", "sq", "ap"):
        assert a[col] == b[col] == c[col], (
            col, a[col], b[col], c[col]
        )
    # sanity vs the float oracle (values must still be RIGHT, not just
    # consistent)
    df = _money_table().to_pandas()
    df["rev"] = df.price * (1 - df.disc)
    want = df.groupby("g").agg(
        sp=("price", "sum"), srev=("rev", "sum"), sq=("qty", "sum")
    )
    np.testing.assert_allclose(a["sp"], want.sp.values, rtol=1e-12)
    np.testing.assert_allclose(a["srev"], want.srev.values, rtol=1e-9)
    np.testing.assert_allclose(a["sq"], want.sq.values, rtol=1e-12)


CHILD = """
import json, sys
sys.path.insert(0, {root!r})
sys.path.insert(0, {root!r} + "/tests")
from test_decimal_exact import _run
print("RESULT " + json.dumps(_run(8192)))
"""


def test_money_sums_exact_across_backends():
    """Identical result checksums CPU vs TPU (BASELINE.md north star).

    The scaled-int sums are exact integers on both backends; the final
    divide-back to value units is the ONE step the TPU's emulated f64
    divides within 1-2ulp of IEEE (measured), so equality is asserted in
    the decimal domain — every aggregate re-scaled to its decimal
    precision must be the EXACT same integer (==, no tolerance). That is
    the checksum semantic: TPC-H answers compare at column scale."""
    here = _run(4096)  # in-proc: the default backend
    root = str(pathlib.Path(__file__).resolve().parent.parent)
    proc = subprocess.run(
        [sys.executable, "-c", CHILD.format(root=root)],
        env=dict(CPU_MESH_ENV),  # forces jax-cpu
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout
    there = json.loads(line[0][len("RESULT "):])
    assert here["c"] == there["c"]

    def cents(vals, scale):
        return [int(round(v * 10 ** scale)) for v in vals]

    for col, scale in (("sp", 2), ("srev", 4), ("sq", 2), ("ap", 6)):
        assert cents(here[col], scale) == cents(there[col], scale), (
            col, here[col], there[col]
        )
