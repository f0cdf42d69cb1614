"""Adaptive aggregate-capacity retry (VERDICT r2 Weak#1 regression).

The round-2 bench failed at its own default scale because q18's
``GROUP BY l_orderkey`` produced more groups than the fixed
``ballista.tpu.agg_capacity``. The engine now reports the exact required
group count on overflow (the sort-based kernel computes the true count
regardless of capacity) and the execution driver retries with a grown
capacity instead of failing.
"""

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.errors import CapacityError
from ballista_tpu.exec.context import TpuContext


def _ctx_small_cap(cap: int) -> TpuContext:
    cfg = BallistaConfig().with_setting("ballista.tpu.agg_capacity", str(cap))
    return TpuContext(cfg)


def test_group_count_exceeding_capacity_retries_and_succeeds():
    n, n_groups = 20_000, 3_000  # groups >> capacity of 256
    rng = np.random.default_rng(3)
    keys = rng.integers(0, n_groups, n)
    vals = rng.uniform(0, 10, n)
    t = pa.table({"k": pa.array(keys), "v": pa.array(vals)})
    ctx = _ctx_small_cap(256)
    ctx.register_table("t", t)
    out = (
        ctx.sql("SELECT k, SUM(v) AS s, COUNT(*) AS c FROM t GROUP BY k")
        .collect()
        .to_pandas()
        .sort_values("k")
        .reset_index(drop=True)
    )
    want = (
        pa.table({"k": pa.array(keys), "v": pa.array(vals)})
        .to_pandas()
        .groupby("k")
        .agg(s=("v", "sum"), c=("v", "count"))
        .reset_index()
    )
    assert len(out) == len(want)
    np.testing.assert_array_equal(out.k.to_numpy(), want.k.to_numpy())
    np.testing.assert_allclose(out.s.to_numpy(), want.s.to_numpy(), rtol=1e-9)
    np.testing.assert_array_equal(out.c.to_numpy(), want.c.to_numpy())


def test_capacity_error_carries_required_count():
    from ballista_tpu.ops.aggregate import AggOp, group_aggregate
    import jax.numpy as jnp

    n = 1024
    keys = jnp.arange(n, dtype=jnp.int64)  # 1024 distinct groups
    vals = jnp.ones(n)
    res = group_aggregate(
        [keys], [None], jnp.ones(n, dtype=bool), [vals], [None],
        [AggOp.SUM], capacity=16,
    )
    with pytest.raises(CapacityError) as ei:
        res.check_overflow()
    assert ei.value.required == n


def test_scalar_aggregate_unaffected():
    t = pa.table({"v": pa.array(np.arange(100.0))})
    ctx = _ctx_small_cap(16)
    ctx.register_table("t", t)
    out = ctx.sql("SELECT SUM(v) AS s FROM t").collect().to_pandas()
    assert out.s[0] == pytest.approx(4950.0)


def test_overflow_is_raised_before_the_fold_not_after_it():
    """Ten batches of two keys (no single int key, so the partial folds every
    four states): the first four passes overflow 256 slots, and the attempt
    ends at the first fold, with those four passes and no fold behind it.
    The retry and the warm run take the same passes."""
    from ballista_tpu.compilecache import metrics

    def moved(before):
        now = metrics.snapshot()
        return {k: now[k] - before.get(k, 0) for k in (
            "agg.capacity_retries", "agg.sort_passes",
            "phase.task.d2h.count:agg.overflow") if k in now}

    n = 40_000
    rng = np.random.default_rng(5)
    a, b = rng.integers(0, 60, n), rng.integers(0, 50, n)
    t = pa.table({"a": pa.array(a), "b": pa.array(b),
                  "v": pa.array(rng.integers(1, 6, n))})
    cfg = (BallistaConfig()
           .with_setting("ballista.tpu.agg_capacity", "256")
           .with_setting("ballista.tpu.batch_rows", "4096"))
    ctx = TpuContext(cfg)
    ctx.register_table("t", t)
    sql = "SELECT a, b, SUM(v) AS s FROM t GROUP BY a, b"
    before = metrics.snapshot()
    out = ctx.sql(sql).collect().to_pandas()
    first = moved(before)
    before = metrics.snapshot()
    again = ctx.sql(sql).collect().to_pandas()
    warm = moved(before)
    want = (t.to_pandas().groupby(["a", "b"]).agg(s=("v", "sum"))
            .reset_index())
    for got in (out, again):
        got = got.sort_values(["a", "b"]).reset_index(drop=True)
        assert got.equals(want.astype(got.dtypes.to_dict()))
    assert first["agg.capacity_retries"] == 1
    assert warm["agg.capacity_retries"] == 0
    assert first["agg.sort_passes"] == warm["agg.sort_passes"] + 4
    # a read before every fold that follows sort-path passes
    assert first["phase.task.d2h.count:agg.overflow"] == (
        warm["phase.task.d2h.count:agg.overflow"] + 1)
