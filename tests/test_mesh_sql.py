"""SQL queries executing through the ICI mesh tier (VERDICT r2 Next#2).

Each test launches a subprocess with a 4-device virtual CPU mesh and runs
``ctx.sql(...)`` — asserting both that the physical plan routes through the
mesh operators (MeshAggregateExec / MeshJoinExec) and that results match a
pandas oracle. This is the integration the round-2 verdict flagged: the
collective tier must be reachable from a SQL query, not a standalone
library.
"""

import subprocess
import sys

from tests.conftest import CPU_MESH_ENV

COMMON = r"""
import numpy as np
import pyarrow as pa
import jax

from ballista_tpu.config import BallistaConfig
from ballista_tpu.exec.context import TpuContext

assert len(jax.devices()) == 4, jax.devices()
ctx = TpuContext()
assert ctx.mesh_runtime() is not None, "mesh tier should be active"
rng = np.random.default_rng(11)


def physical_display(sql):
    return ctx.create_physical_plan(ctx.sql_to_logical(sql)).display()
"""


def run_script(body: str):
    proc = subprocess.run(
        [sys.executable, "-c", COMMON + body],
        env=CPU_MESH_ENV,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    return proc.stdout


def test_sql_groupby_runs_on_mesh():
    out = run_script(r"""
n = 20000
t = pa.table({"k": pa.array(rng.integers(0, 500, n)),
              "v": pa.array(rng.uniform(0, 10, n)),
              "w": pa.array(rng.integers(1, 9, n))})
ctx.register_table("t", t)
sql = "SELECT k, SUM(v) AS s, AVG(v) AS a, MAX(w) AS m, COUNT(*) AS c FROM t GROUP BY k ORDER BY k"
assert "MeshAggregateExec" in physical_display(sql), physical_display(sql)
got = ctx.sql(sql).collect().to_pandas()
df = t.to_pandas()
want = df.groupby("k").agg(s=("v", "sum"), a=("v", "mean"), m=("w", "max"),
                           c=("v", "count")).reset_index()
assert len(got) == len(want)
np.testing.assert_array_equal(got.k, want.k)
np.testing.assert_allclose(got.s, want.s, rtol=1e-9)
np.testing.assert_allclose(got.a, want.a, rtol=1e-9)
np.testing.assert_array_equal(got.m, want.m)
np.testing.assert_array_equal(got.c, want.c)
print("MESH-SQL-AGG-OK")
""")
    assert "MESH-SQL-AGG-OK" in out


def test_sql_join_groupby_runs_on_mesh():
    out = run_script(r"""
n, nd = 30000, 400
fact = pa.table({"fk": pa.array(rng.integers(0, nd + 50, n)),  # some misses
                 "v": pa.array(rng.uniform(0, 10, n))})
dim = pa.table({"id": pa.array(np.arange(nd, dtype=np.int64)),
                "grp": pa.array((np.arange(nd) % 23).astype(np.int64))})
ctx.register_table("fact", fact)
ctx.register_table("dim", dim)
sql = ("SELECT grp, SUM(v) AS s, COUNT(*) AS c FROM fact "
       "JOIN dim ON fk = id GROUP BY grp ORDER BY grp")
disp = physical_display(sql)
assert "MeshJoinExec" in disp and "MeshAggregateExec" in disp, disp
got = ctx.sql(sql).collect().to_pandas()
df = fact.to_pandas().merge(dim.to_pandas(), left_on="fk", right_on="id")
want = df.groupby("grp").agg(s=("v", "sum"), c=("v", "count")).reset_index()
assert len(got) == len(want)
np.testing.assert_array_equal(got.grp, want.grp)
np.testing.assert_allclose(got.s, want.s, rtol=1e-9)
np.testing.assert_array_equal(got.c, want.c)
print("MESH-SQL-JOIN-OK")
""")
    assert "MESH-SQL-JOIN-OK" in out


def test_sql_expansion_join_on_mesh():
    # duplicate keys on BOTH sides: the m:n expansion path (q18-class)
    out = run_script(r"""
n_l, n_r = 5000, 3000
left = pa.table({"k": pa.array(rng.integers(0, 200, n_l)),
                 "a": pa.array(rng.uniform(0, 1, n_l))})
right = pa.table({"k2": pa.array(rng.integers(0, 200, n_r)),
                  "b": pa.array(rng.uniform(0, 1, n_r))})
ctx.register_table("l", left)
ctx.register_table("r", right)
sql = "SELECT SUM(a + b) AS s, COUNT(*) AS c FROM l JOIN r ON k = k2"
disp = physical_display(sql)
assert "MeshJoinExec" in disp, disp
got = ctx.sql(sql).collect().to_pandas()
df = left.to_pandas().merge(right.to_pandas(), left_on="k", right_on="k2")
assert int(got.c[0]) == len(df)
np.testing.assert_allclose(got.s[0], (df.a + df.b).sum(), rtol=1e-9)
print("MESH-SQL-EXPAND-OK")
""")
    assert "MESH-SQL-EXPAND-OK" in out


def test_sql_semi_anti_left_on_mesh():
    out = run_script(r"""
n, nd = 8000, 97
fact = pa.table({"fk": pa.array(rng.integers(0, nd * 2, n)),
                 "v": pa.array(rng.uniform(0, 1, n))})
dim = pa.table({"id": pa.array(np.arange(nd, dtype=np.int64)),
                "name": pa.array([f"n{i}" for i in range(nd)])})
ctx.register_table("fact", fact)
ctx.register_table("dim", dim)
fdf, ddf = fact.to_pandas(), dim.to_pandas()

semi = ctx.sql(
    "SELECT COUNT(*) AS c FROM fact WHERE fk IN (SELECT id FROM dim)"
).collect().to_pandas()
assert int(semi.c[0]) == int((fdf.fk < nd).sum())

anti = ctx.sql(
    "SELECT COUNT(*) AS c FROM fact WHERE fk NOT IN (SELECT id FROM dim)"
).collect().to_pandas()
assert int(anti.c[0]) == int((fdf.fk >= nd).sum())

left = ctx.sql(
    "SELECT COUNT(*) AS c, COUNT(name) AS cn FROM fact "
    "LEFT JOIN dim ON fk = id"
).collect().to_pandas()
assert int(left.c[0]) == n
assert int(left.cn[0]) == int((fdf.fk < nd).sum())
print("MESH-SQL-SEMIANTI-OK")
""")
    assert "MESH-SQL-SEMIANTI-OK" in out


def test_sql_string_key_groupby_on_mesh():
    # dictionary-coded group keys survive the exchange
    out = run_script(r"""
n = 9000
cats = [f"cat{i}" for i in range(37)]
t = pa.table({"c": pa.array([cats[i % 37] for i in rng.integers(0, 37, n)]),
              "v": pa.array(rng.uniform(0, 5, n))})
ctx.register_table("t", t)
got = ctx.sql(
    "SELECT c, SUM(v) AS s FROM t GROUP BY c ORDER BY c"
).collect().to_pandas()
want = t.to_pandas().groupby("c").agg(s=("v", "sum")).reset_index().sort_values("c").reset_index(drop=True)
np.testing.assert_array_equal(got.c, want.c)
np.testing.assert_allclose(got.s, want.s, rtol=1e-9)
print("MESH-SQL-STR-OK")
""")
    assert "MESH-SQL-STR-OK" in out


def test_sql_order_by_limit_runs_as_mesh_topk():
    out = run_script(r"""
n = 40000
t = pa.table({"k": pa.array(rng.integers(0, 1000, n)),
              "v": pa.array(rng.uniform(0, 100, n)),
              "d": pa.array(rng.integers(0, 3650, n).astype(np.int32))})
ctx.register_table("t", t)
sql = ("SELECT k, SUM(v) AS s FROM t GROUP BY k "
       "ORDER BY s DESC, k ASC LIMIT 7")
disp = physical_display(sql)
assert "MeshSortExec" in disp, disp
assert "CoalescePartitionsExec" not in disp, disp
got = ctx.sql(sql).collect().to_pandas()
df = t.to_pandas()
want = (df.groupby("k").v.sum().reset_index(name="s")
          .sort_values(["s", "k"], ascending=[False, True]).head(7))
np.testing.assert_array_equal(got.k.values, want.k.values)
np.testing.assert_allclose(got.s.values, want.s.values, rtol=1e-9)

# skip + fetch through the same path
sql2 = "SELECT k, v FROM t ORDER BY v DESC LIMIT 5 OFFSET 3"
disp2 = physical_display(sql2)
assert "MeshSortExec" in disp2, disp2
got2 = ctx.sql(sql2).collect().to_pandas()
want2 = df.sort_values("v", ascending=False).iloc[3:8]
np.testing.assert_allclose(got2.v.values, want2.v.values, rtol=1e-12)
print("MESH-TOPK-OK")
""")
    assert "MESH-TOPK-OK" in out


def test_sql_full_order_by_runs_as_mesh_sample_sort():
    # VERDICT r4 weak#6: no-LIMIT ORDER BY used to funnel through
    # CoalescePartitions to one device; now a sample sort (splitters ->
    # range all_to_all -> local sort) keeps it on the mesh.
    out = run_script(r"""
import pandas as pd
n = 5000
t = pa.table({"k": rng.integers(0, 40, n),
              "g": rng.integers(0, 7, n),
              "v": np.round(rng.uniform(-100, 100, n), 2)})
ctx.register_table("t", t)
sql = "SELECT k, g, v FROM t ORDER BY v DESC, k ASC, g ASC"
disp = physical_display(sql)
assert "MeshSortExec(ici-sample-sort)" in disp, disp
assert "CoalescePartitionsExec" not in disp, disp
res = ctx.sql(sql).collect().to_pandas().reset_index(drop=True)
exp = (t.to_pandas()
        .sort_values(["v", "k", "g"], ascending=[False, True, True])
        .reset_index(drop=True)[["k", "g", "v"]])
pd.testing.assert_frame_equal(res, exp)
print("MESH-SAMPLE-SORT-OK")
""")
    assert "MESH-SAMPLE-SORT-OK" in out


def test_sql_ranking_window_runs_on_mesh():
    out = run_script(r"""
import pandas as pd
n = 5000
t = pa.table({"k": rng.integers(0, 40, n),
              "g": rng.integers(0, 7, n),
              "v": np.round(rng.uniform(-100, 100, n), 2)})
ctx.register_table("t", t)
sql = ("SELECT k, g, v, "
       "row_number() OVER (PARTITION BY g ORDER BY v DESC) AS rn, "
       "rank() OVER (PARTITION BY g ORDER BY v DESC) AS rk FROM t")
disp = physical_display(sql)
assert "MeshWindowExec" in disp, disp
res = (ctx.sql(sql).collect().to_pandas()
       .sort_values(["g", "v", "k", "rn"]).reset_index(drop=True))
df = t.to_pandas()
df["rn"] = df.groupby("g")["v"].rank(
    method="first", ascending=False).astype("int64")
df["rk"] = df.groupby("g")["v"].rank(
    method="min", ascending=False).astype("int64")
exp = (df.sort_values(["g", "v", "k", "rn"]).reset_index(drop=True)
         [["k", "g", "v", "rn", "rk"]])
# rank is deterministic; row_number's order within peer ties is not —
# compare it as a multiset
pd.testing.assert_frame_equal(res[["k", "g", "v", "rk"]],
                              exp[["k", "g", "v", "rk"]])
assert sorted(res["rn"]) == sorted(exp["rn"])
print("MESH-WINDOW-RANK-OK")
""")
    assert "MESH-WINDOW-RANK-OK" in out


def test_sql_frame_window_runs_on_mesh():
    out = run_script(r"""
import pandas as pd
n = 5000
t = pa.table({"k": rng.integers(0, 40, n),
              "g": rng.integers(0, 7, n),
              "v": np.round(rng.uniform(-100, 100, n), 2)})
ctx.register_table("t", t)
sql = ("SELECT k, g, v, SUM(v) OVER (PARTITION BY g ORDER BY v "
       "ROWS BETWEEN UNBOUNDED PRECEDING AND CURRENT ROW) AS cs FROM t")
disp = physical_display(sql)
assert "MeshWindowExec" in disp, disp
res = (ctx.sql(sql).collect().to_pandas()
       .sort_values(["g", "v", "k"]).reset_index(drop=True))
df2 = t.to_pandas().sort_values(["g", "v"], kind="stable")
df2["cs"] = df2.groupby("g")["v"].cumsum()
exp = (df2.sort_values(["g", "v", "k"]).reset_index(drop=True)
          [["k", "g", "v", "cs"]])
# cumsum order within v-ties is arbitrary; the running sum at each peer
# group's END row is deterministic — compare those
m = res.groupby(["g", "v"])["cs"].max().reset_index()
me = exp.groupby(["g", "v"])["cs"].max().reset_index()
pd.testing.assert_frame_equal(m, me, check_exact=False, rtol=1e-9)
print("MESH-WINDOW-FRAME-OK")
""")
    assert "MESH-WINDOW-FRAME-OK" in out


def test_sql_window_without_partition_falls_back_local():
    out = run_script(r"""
n = 400
t = pa.table({"v": np.round(rng.uniform(-10, 10, n), 2)})
ctx.register_table("t", t)
sql = "SELECT v, row_number() OVER (ORDER BY v) AS rn FROM t"
disp = physical_display(sql)
assert "MeshWindowExec" not in disp, disp
assert "WindowExec" in disp, disp
got = ctx.sql(sql).collect().to_pandas().sort_values("rn")
assert (got.v.values == np.sort(t.to_pandas().v.values)).all()
print("MESH-WINDOW-FALLBACK-OK")
""")
    assert "MESH-WINDOW-FALLBACK-OK" in out
