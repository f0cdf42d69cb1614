"""Kernel tests: golden comparisons against pandas/pyarrow (the oracle role
DuckDB/DataFusion play in the reference's test strategy, SURVEY.md §4)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from ballista_tpu.columnar import batch_from_arrow, batch_to_arrow
from ballista_tpu.ops import (
    AggOp,
    JoinSide,
    build_side,
    compact,
    group_aggregate,
    hash_columns,
    partition_ids,
    probe_side,
    scalar_aggregate,
    sort_batch,
)
from ballista_tpu.ops.sort import SortKey

import jax.numpy as jnp


def _batch(table):
    return batch_from_arrow(table)


def test_hash_columns_deterministic_and_spread():
    a = jnp.arange(10_000, dtype=jnp.int64)
    h1 = hash_columns([a])
    h2 = hash_columns([a])
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    # distinct inputs -> distinct hashes (no collisions on a small range)
    assert len(np.unique(np.asarray(h1))) == 10_000
    # multi-column differs from single-column
    h3 = hash_columns([a, a])
    assert not np.array_equal(np.asarray(h1), np.asarray(h3))


def test_compact_moves_live_rows_front(sample_table):
    b = _batch(sample_table)
    mask = np.asarray(b.column("grp")) == 2
    b2 = b.with_valid(b.valid & jnp.asarray(mask))
    c = compact(b2)
    n = c.num_rows()
    assert n == int(mask[:1000].sum())
    v = np.asarray(c.valid)
    assert v[:n].all() and not v[n:].any()
    got = np.sort(np.asarray(c.column("id"))[:n])
    expect = np.sort(np.arange(1000)[np.asarray(b.column("grp"))[:1000] == 2])
    np.testing.assert_array_equal(got, expect)


def test_sort_multi_key(sample_table):
    b = _batch(sample_table)
    s = sort_batch(
        b,
        [
            SortKey(b.schema.index_of("grp"), ascending=True),
            SortKey(b.schema.index_of("price"), ascending=False),
        ],
    )
    out = batch_to_arrow(s).to_pandas()
    expect = (
        sample_table.to_pandas()
        .sort_values(["grp", "price"], ascending=[True, False])
        .reset_index(drop=True)
    )
    pd.testing.assert_frame_equal(out.reset_index(drop=True), expect)


def test_sort_desc_string_and_int_min():
    t = pa.table(
        {
            "s": pa.array(["b", "a", "c", "a"]),
            "x": pa.array([5, np.iinfo(np.int64).min, 0, 7], type=pa.int64()),
        }
    )
    b = _batch(t)
    s = sort_batch(b, [SortKey(0, ascending=False), SortKey(1, ascending=True)])
    out = batch_to_arrow(s).to_pandas()
    expect = (
        t.to_pandas()
        .sort_values(["s", "x"], ascending=[False, True])
        .reset_index(drop=True)
    )
    pd.testing.assert_frame_equal(out.reset_index(drop=True), expect)


@pytest.mark.parametrize("descending", [False, True])
def test_an_int64_key_sorts_as_two_int32_keys(descending, monkeypatch):
    """``ops/perm.py split_wide_ints`` (the TPU's path, steered here): the
    halves of an int64 key, sorted as two stable passes, give the order and
    the ties of the one-pass sort, extremes and negatives included."""
    import jax

    from ballista_tpu.ops import perm as P

    rng = np.random.default_rng(36)
    lim = np.iinfo(np.int64)
    vals = np.concatenate([
        rng.integers(lim.min, lim.max, 3000, dtype=np.int64),
        rng.integers(-5, 5, 500),  # ties, around zero
        rng.integers(2**32 - 3, 2**32 + 3, 200),  # across the halves
        [lim.min, lim.max, 0, -1, 2**31, -(2**31), 2**32, -(2**32)] * 4,
    ])
    rng.shuffle(vals)
    col = jnp.asarray(np.pad(vals, (0, 4096 - len(vals))))
    flag = jnp.asarray(np.arange(4096) >= len(vals))  # padding last
    passes = [(flag, False), (col, descending)]
    assert P.split_wide_ints(passes) is passes  # the CPU keeps one pass
    with monkeypatch.context() as on_the_chip:
        on_the_chip.setattr(jax, "default_backend", lambda: "tpu")
        split = P.split_wide_ints(passes)
    assert [str(c.dtype) for c, _ in split] == ["bool", "int32", "int32"]
    got = np.asarray(P.multi_key_perm(split))
    want = np.asarray(P.multi_key_perm(passes))
    assert np.array_equal(got, want)
    key = -vals.astype(object) if descending else vals.astype(object)
    order = sorted(range(len(vals)), key=lambda i: (key[i], i))
    assert got[: len(vals)].tolist() == order


@pytest.mark.parametrize("keys", [1, 2, 3])
def test_the_first_pass_of_a_sort_takes_no_gather(keys, monkeypatch):
    """A sort of k passes is k argsorts and 2(k - 1) gathers: the first pass
    sorts the rows where they lie."""
    from ballista_tpu.ops import perm as P

    taken = []
    take = P.take
    monkeypatch.setattr(P, "take", lambda c, p: taken.append(1) or take(c, p))
    rng = np.random.default_rng(keys)
    cols = [rng.integers(0, 4, 2048).astype(np.int32) for _ in range(keys)]
    perm = np.asarray(P.multi_key_perm([(jnp.asarray(c), i == 1)
                                        for i, c in enumerate(cols)]))
    assert len(taken) == 2 * (keys - 1)
    by = [(-c if i == 1 else c) for i, c in enumerate(cols)]
    want = sorted(range(2048), key=lambda r: tuple(b[r] for b in by) + (r,))
    assert perm.tolist() == want


def test_group_aggregate_matches_pandas(sample_table):
    b = _batch(sample_table)
    schema = b.schema
    res = group_aggregate(
        key_cols=[b.column("grp"), b.column("flag")],
        key_nulls=[None, None],
        valid=b.valid,
        val_cols=[b.column("price"), b.column("qty"), b.column("qty")],
        val_nulls=[None, None, None],
        ops=[AggOp.SUM, AggOp.COUNT, AggOp.MAX],
        capacity=64,
    )
    res.check_overflow()
    n = int(res.n_groups)
    df = pd.DataFrame(
        {
            "grp": np.asarray(res.keys[0])[:n],
            "flag": np.asarray(res.keys[1])[:n],
            "sum_price": np.asarray(res.values[0])[:n],
            "cnt": np.asarray(res.values[1])[:n],
            "max_qty": np.asarray(res.values[2])[:n],
        }
    ).sort_values(["grp", "flag"]).reset_index(drop=True)
    pdf = sample_table.to_pandas()
    d = b.dictionaries["flag"]
    pdf["flag"] = pdf["flag"].map({v: i for i, v in enumerate(d.values)})
    expect = (
        pdf.groupby(["grp", "flag"], as_index=False)
        .agg(sum_price=("price", "sum"), cnt=("qty", "count"), max_qty=("qty", "max"))
        .sort_values(["grp", "flag"])
        .reset_index(drop=True)
    )
    np.testing.assert_array_equal(df["grp"], expect["grp"])
    np.testing.assert_array_equal(df["flag"], expect["flag"])
    np.testing.assert_allclose(df["sum_price"], expect["sum_price"], rtol=1e-12)
    np.testing.assert_array_equal(df["cnt"], expect["cnt"])
    np.testing.assert_array_equal(df["max_qty"], expect["max_qty"])


def test_group_aggregate_null_keys_and_values():
    t = pa.table(
        {
            "k": pa.array([1, 1, None, None, 2], type=pa.int64()),
            "v": pa.array([10.0, None, 5.0, 7.0, None]),
        }
    )
    b = _batch(t)
    res = group_aggregate(
        [b.column("k")],
        [b.null_mask("k")],
        b.valid,
        [b.column("v"), b.column("v")],
        [b.null_mask("v"), b.null_mask("v")],
        [AggOp.SUM, AggOp.COUNT],
        capacity=8,
    )
    n = int(res.n_groups)
    assert n == 3  # 1, 2, NULL
    rows = {}
    knull = np.asarray(res.key_nulls[0])[:n]
    for i in range(n):
        key = None if knull[i] else int(np.asarray(res.keys[0])[i])
        s = float(np.asarray(res.values[0])[i])
        snull = bool(np.asarray(res.value_nulls[0])[i])
        c = int(np.asarray(res.values[1])[i])
        rows[key] = (None if snull else s, c)
    assert rows[1] == (10.0, 1)
    assert rows[2] == (None, 0)  # SUM of all-null -> NULL, COUNT -> 0
    assert rows[None] == (12.0, 2)


def test_group_aggregate_overflow_detection():
    t = pa.table({"k": pa.array(np.arange(100), type=pa.int64())})
    b = _batch(t)
    res = group_aggregate(
        [b.column("k")], [None], b.valid,
        [b.column("k")], [None], [AggOp.SUM], capacity=16,
    )
    with pytest.raises(Exception, match="capacity"):
        res.check_overflow()


def test_scalar_aggregate():
    t = pa.table({"v": pa.array([1.0, 2.0, None, 4.0])})
    b = _batch(t)
    outs, nulls = scalar_aggregate(
        b.valid,
        [b.column("v")] * 4,
        [b.null_mask("v")] * 4,
        [AggOp.SUM, AggOp.COUNT, AggOp.MIN, AggOp.MAX],
    )
    assert float(outs[0]) == 7.0
    assert int(outs[1]) == 3
    assert float(outs[2]) == 1.0
    assert float(outs[3]) == 4.0


def test_join_inner_left_semi_anti():
    build_t = pa.table(
        {
            "bk": pa.array([10, 20, 30], type=pa.int64()),
            "bname": pa.array(["ten", "twenty", "thirty"]),
        }
    )
    probe_t = pa.table(
        {
            "pk": pa.array([20, 99, 10, 20, None], type=pa.int64()),
            "pval": pa.array([1.0, 2.0, 3.0, 4.0, 5.0]),
        }
    )
    bb = _batch(build_t)
    pb = _batch(probe_t)
    bt = build_side(bb, [0])
    bt.check_unique()

    inner = probe_side(bt, pb, [0], JoinSide.INNER)
    df = batch_to_arrow(inner).to_pandas().sort_values("pval")
    assert list(df["pk"]) == [20, 10, 20]
    assert list(df["bname"]) == ["twenty", "ten", "twenty"]

    left = probe_side(bt, pb, [0], JoinSide.LEFT)
    df = batch_to_arrow(left).to_pandas().sort_values("pval")
    assert list(df["pk"].fillna(-1)) == [20, 99, 10, 20, -1]
    assert list(df["bname"].fillna("-")) == ["twenty", "-", "ten", "twenty", "-"]

    semi = probe_side(bt, pb, [0], JoinSide.SEMI)
    assert sorted(batch_to_arrow(semi).to_pandas()["pval"]) == [1.0, 3.0, 4.0]

    anti = probe_side(bt, pb, [0], JoinSide.ANTI)
    assert sorted(batch_to_arrow(anti).to_pandas()["pval"]) == [2.0, 5.0]


def test_join_multi_key_and_dup_detection():
    build_t = pa.table(
        {
            "a": pa.array([1, 1, 2], type=pa.int32()),
            "b": pa.array([1, 2, 1], type=pa.int32()),
            "payload": pa.array([100, 200, 300], type=pa.int64()),
        }
    )
    probe_t = pa.table(
        {
            "a": pa.array([1, 1, 2, 2], type=pa.int32()),
            "b": pa.array([2, 3, 1, 2], type=pa.int32()),
        }
    )
    bt = build_side(_batch(build_t), [0, 1])
    bt.check_unique()
    out = probe_side(bt, _batch(probe_t), [0, 1], JoinSide.INNER)
    df = batch_to_arrow(out).to_pandas()
    assert sorted(df["payload"]) == [200, 300]

    dup_t = pa.table({"k": pa.array([5, 5], type=pa.int64())})
    btd = build_side(_batch(dup_t), [0])
    with pytest.raises(Exception, match="duplicate"):
        btd.check_unique()


def test_partition_ids_balanced(sample_table):
    b = _batch(sample_table)
    pids = np.asarray(partition_ids(b, [b.schema.index_of("id")], 8))
    live = pids[:1000]
    assert live.min() >= 0 and live.max() < 8
    counts = np.bincount(live, minlength=8)
    assert counts.min() > 60  # roughly balanced
    assert (pids[1000:] == 8).all()  # drop bucket for padding


def test_join_null_build_key_never_matches_zero():
    build_t = pa.table(
        {"bk": pa.array([None, 20], type=pa.int64()), "p": pa.array([1, 2], type=pa.int64())}
    )
    probe_t = pa.table({"pk": pa.array([0, 20], type=pa.int64())})
    bt = build_side(_batch(build_t), [0])
    out = probe_side(bt, _batch(probe_t), [0], JoinSide.INNER)
    df = batch_to_arrow(out).to_pandas()
    assert list(df["p"]) == [2]  # key 0 must NOT match the NULL build row


def test_join_mixed_width_keys_no_truncation():
    build_t = pa.table({"bk": pa.array([5], type=pa.int32()), "p": pa.array([9], type=pa.int64())})
    probe_t = pa.table({"pk": pa.array([5 - 2**32, 5], type=pa.int64())})
    bt = build_side(_batch(build_t), [0])
    out = probe_side(bt, _batch(probe_t), [0], JoinSide.INNER)
    df = batch_to_arrow(out).to_pandas()
    assert list(df["pk"]) == [5]


def test_join_string_key_dictionary_mismatch_raises():
    from ballista_tpu.errors import ExecutionError

    build_t = pa.table({"s": pa.array(["a", "b"]), "p": pa.array([1, 2], type=pa.int64())})
    probe_t = pa.table({"s2": pa.array(["b", "c"])})
    bt = build_side(_batch(build_t), [0])
    with pytest.raises(ExecutionError, match="dictionary"):
        probe_side(bt, _batch(probe_t), [0], JoinSide.INNER)


def test_hash_negative_zero_canonical():
    h = hash_columns([jnp.array([0.0, -0.0], dtype=jnp.float64)])
    assert int(np.asarray(h)[0]) == int(np.asarray(h)[1])


def test_group_sum_int32_widens():
    """SUM over int32 must accumulate in int64 (SQL widening), not wrap."""
    import jax.numpy as jnp

    keys = jnp.zeros(4, dtype=jnp.int32)
    vals = jnp.full(4, 2**30, dtype=jnp.int32)
    valid = jnp.ones(4, dtype=bool)
    res = group_aggregate([keys], [None], valid, [vals], [None], [AggOp.SUM], 8)
    assert res.values[0].dtype == jnp.int64
    assert int(res.values[0][0]) == 4 * 2**30


def test_group_by_nan_is_one_group():
    """SQL groups all NaN keys together (pandas/DataFusion behavior)."""
    import jax.numpy as jnp

    keys = jnp.asarray([float("nan"), float("nan"), 1.0, float("nan")])
    vals = jnp.ones(4, dtype=jnp.int64)
    valid = jnp.ones(4, dtype=bool)
    res = group_aggregate([keys], [None], valid, [vals], [None], [AggOp.SUM], 8)
    assert int(res.n_groups) == 2


def test_build_side_float_collision_not_duplicate():
    """Distinct f64 keys that collide in the packed (f32-narrowed) hash must
    not be reported as duplicate build keys."""
    import numpy as np

    from ballista_tpu.columnar.batch import DeviceBatch
    from ballista_tpu.datatypes import DataType, Field, Schema

    schema = Schema([Field("k", DataType.FLOAT64), Field("v", DataType.INT64)])
    b = DeviceBatch.from_host(
        schema,
        [np.asarray([1.0, 1.0 + 1e-12]), np.asarray([10, 20], dtype=np.int64)],
        num_rows=2,
    )
    bt = build_side(b, [0])
    bt.check_unique()  # must not raise


def test_probe_finds_match_past_hash_collision():
    """Distinct f64 build keys that collide in the f32-narrowed packed hash:
    the window scan must still find the true match (and ANTI must drop it)."""
    import numpy as np

    from ballista_tpu.columnar.batch import DeviceBatch
    from ballista_tpu.datatypes import DataType, Field, Schema

    schema = Schema([Field("k", DataType.FLOAT64), Field("v", DataType.INT64)])
    b = DeviceBatch.from_host(
        schema,
        [np.asarray([1.0, 1.0 + 1e-12]), np.asarray([10, 20], dtype=np.int64)],
        num_rows=2,
    )
    bt = build_side(b, [0])
    bt.check_unique()
    pschema = Schema([Field("pk", DataType.FLOAT64)])
    p = DeviceBatch.from_host(
        pschema, [np.asarray([1.0 + 1e-12, 1.0, 2.0])], num_rows=3
    )
    out = probe_side(bt, p, [0], JoinSide.INNER)
    live = np.asarray(out.valid)
    vcol = np.asarray(out.column("v"))[live]
    kcol = np.asarray(out.column("pk"))[live]
    assert sorted(vcol.tolist()) == [10, 20]
    assert set(kcol.tolist()) == {1.0, 1.0 + 1e-12}
    anti = probe_side(bt, p, [0], JoinSide.ANTI)
    alive = np.asarray(anti.valid)
    akeys = np.asarray(anti.column("pk"))[alive]
    assert akeys.tolist() == [2.0]


def test_bool_min_max_sum():
    import jax.numpy as jnp

    keys = jnp.asarray([0, 0, 1, 1], dtype=jnp.int32)
    vals = jnp.asarray([True, False, True, True])
    valid = jnp.ones(4, dtype=bool)
    res = group_aggregate(
        [keys], [None], valid,
        [vals, vals, vals], [None, None, None],
        [AggOp.MIN, AggOp.MAX, AggOp.SUM], 8,
    )
    assert bool(res.values[0][0]) is False and bool(res.values[0][1]) is True
    assert bool(res.values[1][0]) is True and bool(res.values[1][1]) is True
    assert int(res.values[2][0]) == 1 and int(res.values[2][1]) == 2


@pytest.mark.parametrize("n,slots", [(1000, 12), (70_000, 12), (5000, 300)])
def test_pallas_onehot_sums_interpreted(monkeypatch, n, slots):
    """ops/pallas_agg.py, run by the Pallas interpreter (the kernel itself
    needs a TPU; tests/test_tpu_compile.py compiles it for one): a ragged
    tail, several grid steps per f32 partial and several partials, rows
    keyed outside [0, slots) dropped. Small integers sum exactly in f32;
    f64 columns go through the (hi, lo) split callers use."""
    from jax.experimental import pallas as pl

    from ballista_tpu.ops import pallas_agg

    real = pl.pallas_call
    monkeypatch.setattr(
        pl, "pallas_call", lambda *a, **kw: real(*a, interpret=True, **kw)
    )
    pallas_agg._program.cache_clear()
    r = np.random.default_rng(n)
    rid = r.integers(-1, slots + 1, n).astype(np.int32)
    ints = r.integers(0, 100, n).astype(np.float64)
    money = np.round(r.uniform(900, 105_000, n), 2)
    hi, lo = pallas_agg.split_hi_lo(jnp.asarray(money))
    got = np.asarray(
        pallas_agg.onehot_sums(
            jnp.asarray(rid), [jnp.asarray(ints), hi, lo], slots
        )
    )
    pallas_agg._program.cache_clear()  # no interpreted program outlives this
    keep = (rid >= 0) & (rid < slots)
    want_i = np.bincount(rid[keep], weights=ints[keep], minlength=slots)
    want_m = np.bincount(rid[keep], weights=money[keep], minlength=slots)
    np.testing.assert_array_equal(got[:, 0], want_i)
    np.testing.assert_allclose(got[:, 1] + got[:, 2], want_m, rtol=1e-6)


def test_dense_aggregate_does_not_fall_back_from_the_kernel(monkeypatch):
    """Where the kernel is the path (a TPU backend, >= 1<<20 rows, few
    slots), a kernel the compiler refuses fails the aggregate: there is no
    silent switch to the XLA one-hot path (PR 21)."""
    from ballista_tpu.ops import aggregate as A
    from ballista_tpu.ops import pallas_agg

    def refused(n, R, P):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(pallas_agg, "available", lambda: True)
    monkeypatch.setattr(pallas_agg, "_program", refused)
    n = A._PALLAS_MIN_ROWS
    codes = jnp.zeros(n, jnp.int32)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        A._dense_agg(
            [codes], [None], (3,), jnp.ones(n, bool),
            [jnp.ones(n, jnp.float64)], [None], (AggOp.SUM,),
        )
