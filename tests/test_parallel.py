"""Multi-device mesh tier tests (virtual 4-device CPU mesh, subprocess).

Covers the ICI shuffle exchange (bucket + all_to_all), the repartitioned
aggregate (partial -> exchange -> final merge), the PARTITIONED join, and
the driver's dryrun entry. Mirrors what the reference pins with its
distributed-plan tests (scheduler/src/planner.rs:328-471) — except the
exchange here is collectives inside one program, not files + Flight.
"""

import subprocess
import sys

from tests.conftest import CPU_MESH_ENV

COMMON = r"""
import numpy as np
import pyarrow as pa
import jax

from ballista_tpu.columnar.arrow_interop import batch_from_arrow, batch_to_arrow
from ballista_tpu.ops.aggregate import AggOp
from ballista_tpu.ops.join import JoinSide
from ballista_tpu.parallel import (
    MeshStageRunner, make_mesh, shard_batch, unshard_batch,
)

N_DEV = 4
assert len(jax.devices()) == N_DEV, jax.devices()
mesh = make_mesh(N_DEV)
runner = MeshStageRunner(mesh)
rng = np.random.default_rng(13)
"""


def run_script(body: str):
    proc = subprocess.run(
        [sys.executable, "-c", COMMON + body],
        env=CPU_MESH_ENV,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    return proc.stdout


def test_exchange_routes_every_row_once():
    out = run_script(r"""
from jax import shard_map
from jax.sharding import PartitionSpec as P
from ballista_tpu.parallel.collective import exchange_by_key
from ballista_tpu.parallel.mesh import SHARD_AXIS

n = 4000
t = pa.table({"k": pa.array(rng.integers(0, 101, n)),
              "v": pa.array(np.arange(n, dtype=np.int64))})
sb = shard_batch(mesh, batch_from_arrow(t))
cap_local = sb.capacity // N_DEV

def f(cols, valid):
    c, _, v, ovf = exchange_by_key(
        cols, (None, None), valid, (0,), SHARD_AXIS, N_DEV, cap_local
    )
    return c, v, ovf.reshape(1)

sm = jax.jit(shard_map(
    f, mesh=mesh,
    in_specs=((P(SHARD_AXIS), P(SHARD_AXIS)), P(SHARD_AXIS)),
    out_specs=((P(SHARD_AXIS), P(SHARD_AXIS)), P(SHARD_AXIS), P(SHARD_AXIS)),
    check_vma=False,
))
(k2, v2), valid2, ovf = sm(sb.columns, sb.valid)
assert not np.any(np.asarray(ovf))
k2, v2, valid2 = map(np.asarray, (k2, v2, valid2))
# every original row appears exactly once after the exchange
got = sorted(v2[valid2].tolist())
assert got == list(range(n)), (len(got), n)
# routing invariant: rows on device d are exactly those with hash(k)%N_DEV==d
from ballista_tpu.ops.hashing import hash_columns
import jax.numpy as jnp
pid = np.asarray(hash_columns([jnp.asarray(k2)]) % jnp.uint64(N_DEV)).astype(int)
glob_cap = len(valid2)
dev = np.arange(glob_cap) // (glob_cap // N_DEV)
assert np.all(pid[valid2] == dev[valid2])
print("EXCHANGE-OK")
""")
    assert "EXCHANGE-OK" in out


def test_mesh_repartitioned_aggregate():
    out = run_script(r"""
n = 6000
t = pa.table({"k": pa.array(rng.integers(0, 53, n)),
              "v": pa.array(rng.uniform(0, 10, n)),
              "w": pa.array(rng.integers(1, 5, n))})
sb = shard_batch(mesh, batch_from_arrow(t))
res = runner.aggregate(sb, [0], [1, 2, 1], [AggOp.SUM, AggOp.MAX, AggOp.COUNT],
                       capacity=128)
out = batch_to_arrow(unshard_batch(res)).to_pandas()
out = out.sort_values(out.columns[0]).reset_index(drop=True)
df = t.to_pandas()
want = df.groupby("k").agg(s=("v", "sum"), m=("w", "max"), c=("v", "count")).reset_index()
np.testing.assert_array_equal(out.iloc[:, 0], want.k)
np.testing.assert_allclose(out.iloc[:, 1], want.s, rtol=1e-9)
np.testing.assert_array_equal(out.iloc[:, 2], want.m)
np.testing.assert_array_equal(out.iloc[:, 3], want.c)
print("MESH-AGG-OK")
""")
    assert "MESH-AGG-OK" in out


def test_mesh_partitioned_join():
    out = run_script(r"""
n, nd = 4000, 29
fact = pa.table({"k": pa.array(rng.integers(0, nd + 10, n)),  # some misses
                 "v": pa.array(rng.uniform(0, 1, n))})
dim = pa.table({"k2": pa.array(np.arange(nd, dtype=np.int64)),
                "name": pa.array([f"g{i}" for i in range(nd)])})
sf = shard_batch(mesh, batch_from_arrow(fact))
sd = shard_batch(mesh, batch_from_arrow(dim))
fdf, ddf = fact.to_pandas(), dim.to_pandas()

inner = batch_to_arrow(unshard_batch(
    runner.join(sf, sd, [0], [0], JoinSide.INNER))).to_pandas()
want = fdf.merge(ddf, left_on="k", right_on="k2")
assert len(inner) == len(want)
np.testing.assert_allclose(sorted(inner.v), sorted(want.v), rtol=1e-12)

semi = batch_to_arrow(unshard_batch(
    runner.join(sf, sd, [0], [0], JoinSide.SEMI))).to_pandas()
assert len(semi) == (fdf.k < nd).sum()

anti = batch_to_arrow(unshard_batch(
    runner.join(sf, sd, [0], [0], JoinSide.ANTI))).to_pandas()
assert len(anti) == (fdf.k >= nd).sum()

left = batch_to_arrow(unshard_batch(
    runner.join(sf, sd, [0], [0], JoinSide.LEFT))).to_pandas()
assert len(left) == len(fdf)
assert left.name.isna().sum() == (fdf.k >= nd).sum()
print("MESH-JOIN-OK")
""")
    assert "MESH-JOIN-OK" in out


def test_graft_entry_dryrun():
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import jax\n"
            "import __graft_entry__ as g\n"
            "fn, args = g.entry()\n"
            "jax.jit(fn)(*args)\n"
            "g.dryrun_multichip(4)\n"
            "print('DRYRUN-OK')\n",
        ],
        env=CPU_MESH_ENV,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    assert "DRYRUN-OK" in proc.stdout
