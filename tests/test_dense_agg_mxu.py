"""The dense aggregate's factorized one-hot (``ops/aggregate.py
_factored_sums``) against numpy's int64 ``add.at`` (the scatter-add it
replaced, wraparound included), bit for bit, and a slot's occupancy from its
count against a scatter-set of every valid row.

Every dense pass past 2,048 slots reduces its counts and integer sums on the
MXU; its float64 sums and MIN/MAX keep their scatter, and are checked here
beside them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ballista_tpu.ops import aggregate as A
from ballista_tpu.ops.aggregate import AggOp

# slots -> vocab sizes of the keys: 2,049 (one key of 2,048 values, the
# first past the one-hot kernels), g1q2's 10,201, and 50,001 = 224 x 224
# padded to 50,176 slots, as g1q3's and g1q7's merge passes in the cell
VOCABS = {2049: (2048,), 10201: (100, 100), 50001: (50000,)}
OPS = (AggOp.SUM, AggOp.SUM, AggOp.SUM, AggOp.SUM, AggOp.COUNT, AggOp.MAX)
# the factorized reduction's columns for OPS and _inputs' NULL masks: the
# row count and four live masks, then 8 + 4 + 1 limbs (int64, int32, bool)
K = 5 + 13
# rows as a share of one chunk (``_factored_layout``'s rule for K): within
# one chunk (not a power of two), exactly one, and across two boundaries with
# the last one padded
ROWS = {"part": lambda c: c // 3 + 1, "one": lambda c: c,
        "across": lambda c: 2 * c + c // 2 + 1}


def _rows(slots, share):
    _, _, chunk = A._factored_layout(slots, 1 << 20, K)
    n = ROWS[share](chunk)
    assert A._factored_layout(slots, n, K)[2] == min(n, chunk)
    return n


def _inputs(vocab, n, null_keys, seed):
    rng = np.random.default_rng(seed)
    codes = [rng.integers(0, v, n).astype(np.int32) for v in vocab]
    key_nulls = [rng.random(n) < 0.05 if null_keys else None for _ in vocab]
    valid = rng.random(n) < 0.9
    vals = [
        # the whole range: sums wrap, negatives carry through every limb
        rng.integers(np.iinfo(np.int64).min, np.iinfo(np.int64).max, n,
                     dtype=np.int64, endpoint=True),
        rng.integers(np.iinfo(np.int32).min, np.iinfo(np.int32).max, n,
                     dtype=np.int32, endpoint=True),
        rng.random(n) < 0.5,
        rng.standard_normal(n),
    ]
    vals += [vals[0], vals[1]]  # COUNT(int64), MAX(int32)
    val_nulls = [rng.random(n) < 0.1, None, rng.random(n) < 0.1, None,
                 rng.random(n) < 0.2, rng.random(n) < 0.1]
    return codes, key_nulls, valid, vals, val_nulls


def _slots(vocab, codes, key_nulls, valid):
    seg = np.zeros(len(valid), dtype=np.int64)
    for code, nm, v in zip(codes, key_nulls, vocab):
        c = code if nm is None else np.where(nm, v, code)
        seg = seg * (v + 1) + c
    return np.where(valid, seg, -1)


@pytest.mark.parametrize("null_keys", [False, True], ids=["keys", "null_keys"])
@pytest.mark.parametrize("share", sorted(ROWS))
@pytest.mark.parametrize("slots", sorted(VOCABS))
def test_factored_sums_equal_numpy_bit_for_bit(slots, share, null_keys,
                                              monkeypatch):
    vocab = VOCABS[slots]
    assert A.dense_slots(vocab) == slots and A.dense_factored(slots)
    n = _rows(slots, share)
    inputs = _inputs(vocab, n, null_keys, seed=slots + n)
    layouts = []
    layout = A._factored_layout
    monkeypatch.setattr(A, "_factored_layout",
                        lambda *a: layouts.append(a) or layout(*a))
    got = jax.tree.map(np.asarray, jax.jit(
        lambda c, k, v, x, xn: A._dense_agg(c, k, vocab, v, x, xn, OPS)
    )(*inputs))
    assert layouts == [(slots, n, K)]  # the chunks _rows worked out

    codes, key_nulls, valid, vals, val_nulls = inputs
    seg = _slots(vocab, codes, key_nulls, valid)
    lives = [(seg >= 0) & (True if vn is None else ~vn) for vn in val_nulls]
    nonnull = [np.bincount(seg[l], minlength=slots) for l in lives]
    # int64 sums wrap as np.add.at's do
    for i in (0, 1, 2):
        ref = np.zeros(slots, dtype=np.int64)
        np.add.at(ref, seg[lives[i]], vals[i][lives[i]].astype(np.int64))
        np.testing.assert_array_equal(got.values[i], ref)
    ref = np.zeros(slots)
    np.add.at(ref, seg[lives[3]], vals[3][lives[3]])
    np.testing.assert_allclose(got.values[3], ref, rtol=1e-12, atol=1e-12)
    np.testing.assert_array_equal(got.values[4], nonnull[4])
    ref = np.full(slots, np.iinfo(np.int32).min, dtype=np.int32)
    np.maximum.at(ref, seg[lives[5]], vals[5][lives[5]])
    has = nonnull[5] > 0
    np.testing.assert_array_equal(got.values[5][has], ref[has])
    for i in (0, 1, 2, 3, 5):
        np.testing.assert_array_equal(got.value_nulls[i], nonnull[i] == 0)
    occupied = np.bincount(seg[seg >= 0], minlength=slots) > 0
    np.testing.assert_array_equal(got.valid, occupied)
    assert int(got.n_groups) == occupied.sum()


@pytest.mark.parametrize("src", ["int64", "int32", "bool"])
def test_limbs_recombine_to_the_value(src):
    """``_byte_limbs``: every limb exact in bfloat16, and their shifted sum
    the value itself, the extremes of the source type included."""
    rng = np.random.default_rng(7)
    if src == "bool":
        x = np.array([0, 1, 1, 0])
    else:
        info = np.iinfo(src)
        x = np.concatenate([
            [info.min, info.max, -1, 0, 1],
            rng.integers(info.min, info.max, 1000, dtype=src, endpoint=True),
        ]).astype(np.int64)
    limbs = A._byte_limbs(jnp.asarray(x, jnp.int64), src)
    shifts = A._limb_shifts(src)
    assert len(limbs) == len(shifts) == {"int64": 8, "int32": 4, "bool": 1}[src]
    total = np.zeros_like(x)
    for limb, s in zip(limbs, shifts):
        limb = np.asarray(limb)
        assert limb.min() >= -128 and limb.max() <= 255
        np.testing.assert_array_equal(
            np.asarray(jnp.asarray(limb).astype(jnp.bfloat16)
                       .astype(jnp.int64)), limb)
        total = total + (limb << s)
    np.testing.assert_array_equal(total, x)


@pytest.mark.parametrize("path", ["onehot", "factored", "factored_sparse"])
def test_occupancy_is_the_count_of_rows_a_slot(path):
    """``_dense_agg``'s ``valid`` and ``n_groups`` from the count of rows a
    slot, on each path a dense pass can take off the chip (the Pallas
    kernel's is the TPU's alone): what a scatter-set of every valid row
    gives. Rows whose every value is NULL still occupy their slot. The
    sparse case has far fewer rows than slots, a shape the int64 scatter
    took until PR 37's review."""
    vocab = {"onehot": (3, 2), "factored": (100, 100),
             "factored_sparse": (50000,)}[path]
    slots = A.dense_slots(vocab)
    n = 100 if path == "factored_sparse" else 1000
    rng = np.random.default_rng(3)
    codes = [rng.integers(0, v, n).astype(np.int32) for v in vocab]
    key_nulls = [rng.random(n) < 0.1 for _ in vocab]
    valid = rng.random(n) < 0.5
    vals = [rng.standard_normal(n), rng.integers(0, 9, n)]
    val_nulls = [np.ones(n, dtype=bool), rng.random(n) < 0.5]

    res = jax.jit(
        lambda c, k, v, x, xn: A._dense_agg(
            c, k, vocab, v, x, xn, (AggOp.SUM, AggOp.COUNT))
    )(codes, key_nulls, valid, vals, val_nulls)
    seg = _slots(vocab, codes, key_nulls, valid)
    occupied = np.zeros(slots, dtype=bool)
    occupied[seg[seg >= 0]] = True
    np.testing.assert_array_equal(np.asarray(res.valid), occupied)
    assert int(res.n_groups) == occupied.sum()
    # the all-NULL column is NULL in every slot, occupied or not
    assert np.asarray(res.value_nulls[0]).all()


def test_no_values_still_occupy_their_slots():
    """A dense pass with no aggregate (``SELECT DISTINCT``-like) still
    counts its rows a slot."""
    vocab = (100, 100)
    rng = np.random.default_rng(5)
    n = 30000
    codes = [rng.integers(0, v, n).astype(np.int32) for v in vocab]
    valid = rng.random(n) < 0.3
    assert A.dense_factored(A.dense_slots(vocab))
    res = A.dense_group_aggregate(codes, [None, None], list(vocab), valid,
                                  [], [], [])
    seg = _slots(vocab, codes, [None, None], valid)
    np.testing.assert_array_equal(
        np.asarray(res.valid),
        np.bincount(seg[seg >= 0], minlength=A.dense_slots(vocab)) > 0)
