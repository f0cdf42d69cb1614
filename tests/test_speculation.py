"""Plan-shape speculation: warm queries reuse cached join build-strategy
flags and expansion capacities without blocking host syncs; a STALE cache
entry must be caught by the deferred validation flag and transparently
retried — never silently wrong.

The cache exists because every blocking sync stalls the host (cost not
measured on the attached chip); see ballista_tpu/ops/fetch.py and
exec/base.py defer_speculation.
"""

import subprocess
import sys

from tests.conftest import CPU_MESH_ENV

SCRIPT = r"""
import numpy as np
import pyarrow as pa

from ballista_tpu.config import BallistaConfig
from ballista_tpu.exec.context import TpuContext

ctx = TpuContext(
    BallistaConfig().with_setting("ballista.shuffle.partitions", "1")
)

n = 4000
r = np.random.default_rng(9)
fact = pa.table({
    "k": pa.array(r.integers(0, 50, n)),
    "v": pa.array(r.uniform(0, 100, n)),
})
dim_unique = pa.table({
    "id": pa.array(np.arange(50, dtype=np.int64)),
    "w": pa.array(r.uniform(0, 1, 50)),
})
ctx.register_table("fact", fact)
ctx.register_table("dim", dim_unique)

sql = "select sum(v * w) as s from fact join dim on k = id"

def oracle(d):
    m = fact.to_pandas().merge(d.to_pandas(), left_on="k", right_on="id")
    return float((m.v * m.w).sum())

# run 1: cold — syncs the build flags, caches (unique)
r1 = ctx.sql(sql).collect().to_pandas().s[0]
np.testing.assert_allclose(r1, oracle(dim_unique), rtol=1e-9)
key = [k for k in ctx._plan_cache if k[0] == "join_flags"]
assert key, ctx._plan_cache
# (dups, overflow, contiguous, lo, hi): ids 0..49 are a contiguous PK range
assert ctx._plan_cache[key[0]][:3] == (False, False, True)

# run 2: warm — same data, cached strategy, still correct
r2 = ctx.sql(sql).collect().to_pandas().s[0]
np.testing.assert_allclose(r2, r1, rtol=1e-12)

# now swap the dim table's DATA in place (bypassing register_table, which
# would clear the cache) so the cached "unique build" entry is stale:
# every id appears twice -> the unique-probe speculation must MISS and
# the retry must produce the correct (duplicated-join) result
dim_dup = pa.table({
    "id": pa.array(np.repeat(np.arange(50), 2).astype(np.int64)),
    "w": pa.array(r.uniform(0, 1, 100)),
})
reg = ctx.tables["dim"]
reg.kw["table"] = dim_dup
reg.kw["device_cache"] = {}

r3 = ctx.sql(sql).collect().to_pandas().s[0]
np.testing.assert_allclose(r3, oracle(dim_dup), rtol=1e-9)
# the stale entry was replaced by the fresh (dups) decision
assert ctx._plan_cache[key[0]][0] is True or ctx._plan_cache[key[0]][0] == True

# register_table clears the speculation cache entirely
ctx.register_table("dim", dim_unique)
assert not ctx._plan_cache
r4 = ctx.sql(sql).collect().to_pandas().s[0]
np.testing.assert_allclose(r4, r1, rtol=1e-9)
print("SPECULATION-OK")
"""


def test_speculation_miss_retries_correctly():
    # single-device CPU: the speculation cache lives on the local operator
    # tier (a multi-device env would route joins through the mesh tier)
    env = {
        k: v for k, v in CPU_MESH_ENV.items() if k != "XLA_FLAGS"
    }
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        env=env,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    assert "SPECULATION-OK" in proc.stdout
