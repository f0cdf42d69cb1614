"""Direct-address (LUT) join probe: exact int keys over a bounded domain
probe through a scattered ``(first, count)`` table instead of a binary
search (ops/join.py attach_lut / probe_side / probe_counts; same
HashJoinExecNode wire shape, ballista.proto:474-487 — the table is an
execution detail like the contiguous range probe).

The sparse-domain case is the regression that motivated these tests: the
build's dead-tail sentinel keys must not alias table slots after the TPU
x64 narrow (they once truncated arbitrarily, silently dropping matches in
the upper half of the domain — TPC-H q18 returned 44 of 74 rows).
"""

import numpy as np

import jax.numpy as jnp

from ballista_tpu.columnar.batch import DeviceBatch, round_capacity
from ballista_tpu.datatypes import DataType, Field, Schema
from ballista_tpu.ops.join import (
    JoinSide,
    attach_lut,
    build_side,
    probe_counts,
    probe_side,
)


def _batch(keys: np.ndarray, cap: int) -> DeviceBatch:
    n = len(keys)
    cols = [jnp.asarray(np.concatenate([keys, np.zeros(cap - n, keys.dtype)]))]
    valid = jnp.asarray(
        np.concatenate([np.ones(n, bool), np.zeros(cap - n, bool)])
    )
    schema = Schema([Field("k", DataType.INT64, False)])
    return DeviceBatch(
        schema=schema, columns=tuple(cols), valid=valid, nulls=(None,),
        dictionaries={},
    )


def test_lut_matches_searchsorted_on_sparse_domain():
    rng = np.random.default_rng(5)
    # sparse build keys spread over a wide domain, small capacity: the
    # dead tail dominates the build and its sentinel handling matters
    bkeys = np.sort(rng.choice(500_000, 60, replace=False)).astype(np.int64)
    bt = build_side(_batch(bkeys, 4096), [0])
    pkeys = rng.integers(0, 500_000, 20_000).astype(np.int64)
    pkeys[:500] = rng.choice(bkeys, 500)
    probe = _batch(pkeys, 32768)

    ref = np.asarray(probe_side(bt, probe, [0], JoinSide.SEMI).valid)
    _, c_ref, _ = probe_counts(bt, probe, [0])

    attach_lut(bt, round_capacity(int(bkeys.max() - bkeys.min() + 1)))
    got = np.asarray(probe_side(bt, probe, [0], JoinSide.SEMI).valid)
    first, c_lut, _ = probe_counts(bt, probe, [0])

    assert np.array_equal(ref, got)
    assert np.array_equal(np.asarray(c_ref), np.asarray(c_lut))
    # matched probes point at the right build row (keys agree)
    f = np.asarray(first)
    cnt = np.asarray(c_lut)
    # the build's rows stay in arrival order: sorted position p is row perm[p]
    skeys = np.asarray(bt.batch.columns[0])[np.asarray(bt.perm)]
    m = cnt > 0
    assert np.array_equal(
        skeys[f[m]], np.asarray(probe.columns[0])[m]
    )


def test_lut_duplicate_build_run_counts():
    rng = np.random.default_rng(7)
    # duplicated build keys: count must equal each key's run length
    base = np.sort(rng.choice(10_000, 50, replace=False)).astype(np.int64)
    reps = rng.integers(1, 5, 50)
    bkeys = np.repeat(base, reps)
    bt = build_side(_batch(bkeys, 1024), [0])
    pkeys = np.concatenate([base, base + 1]).astype(np.int64)
    probe = _batch(pkeys, 256)

    attach_lut(bt, round_capacity(int(bkeys.max() - bkeys.min() + 1)))
    first, count, _ = probe_counts(bt, probe, [0])
    count = np.asarray(count)[: len(pkeys)]
    # base+1 may collide with another base key; compute run lengths exactly
    from collections import Counter

    runs = Counter(bkeys.tolist())
    want = np.array([runs.get(int(k), 0) for k in pkeys])
    assert np.array_equal(count, want)
    # first indices point at the start of each run in the sorted build
    f = np.asarray(first)[: len(pkeys)]
    # the build's rows stay in arrival order: sorted position p is row perm[p]
    skeys = np.asarray(bt.batch.columns[0])[np.asarray(bt.perm)]
    for i, k in enumerate(pkeys):
        if want[i]:
            assert skeys[f[i]] == k
            assert f[i] == 0 or skeys[f[i] - 1] != k


def test_lut_probe_out_of_domain_keys_never_match():
    bkeys = (np.arange(100, dtype=np.int64) * 3) + 1000
    bt = build_side(_batch(bkeys, 256), [0])
    attach_lut(bt, round_capacity(int(bkeys.max() - bkeys.min() + 1)))
    pkeys = np.array([0, 999, 1001, 1000, 1297, 1298, 10**12], np.int64)
    probe = _batch(pkeys, 64)
    _, count, _ = probe_counts(bt, probe, [0])
    assert np.asarray(count)[:7].tolist() == [0, 0, 0, 1, 1, 0, 0]


def test_exact2_contiguous_first_key_probe():
    """Two-int-key join with a unique contiguous FIRST key (supplier shape:
    (l_suppkey, c_nationkey) = (s_suppkey, s_nationkey)): the build flags
    contiguity on key0 and the probe direct-indexes + verifies the second
    key — results must match the searchsorted path for every join kind."""
    import jax.numpy as _jnp

    rng = np.random.default_rng(0)
    ns = 200
    sk = np.arange(1, ns + 1).astype(np.int64)
    natk = rng.integers(0, 25, ns).astype(np.int64)
    cols = [sk, natk]
    cap = 256
    arrs = tuple(
        _jnp.asarray(np.concatenate([v, np.zeros(cap - ns, v.dtype)]))
        for v in cols
    )
    valid = _jnp.asarray(
        np.concatenate([np.ones(ns, bool), np.zeros(cap - ns, bool)])
    )
    schema = Schema(
        [Field("k0", DataType.INT64, False), Field("k1", DataType.INT64, False)]
    )
    b = DeviceBatch(
        schema=schema, columns=arrs, valid=valid, nulls=(None, None),
        dictionaries={},
    )
    bt = build_side(b, [0, 1])
    assert bt.mode == "exact2"
    assert bt.flags()[2], "key0 contiguity not detected"

    n = 5000
    pcap = 8192
    pk0 = rng.integers(1, ns + 1, n).astype(np.int64)
    pk1 = rng.integers(0, 25, n).astype(np.int64)
    parrs = tuple(
        _jnp.asarray(np.concatenate([v, np.zeros(pcap - n, v.dtype)]))
        for v in (pk0, pk1)
    )
    pvalid = _jnp.asarray(
        np.concatenate([np.ones(n, bool), np.zeros(pcap - n, bool)])
    )
    p = DeviceBatch(
        schema=schema, columns=parrs, valid=pvalid, nulls=(None, None),
        dictionaries={},
    )
    for kind in (JoinSide.INNER, JoinSide.SEMI, JoinSide.ANTI, JoinSide.LEFT):
        ref = probe_side(bt, p, [0, 1], kind, contiguous=False)
        got = probe_side(bt, p, [0, 1], kind, contiguous=True)
        assert np.array_equal(
            np.asarray(ref.valid), np.asarray(got.valid)
        ), kind
        for ci, (cr, cg) in enumerate(zip(ref.columns, got.columns)):
            keep = np.asarray(ref.valid)
            if ref.nulls[ci] is not None:
                keep = keep & ~np.asarray(ref.nulls[ci])
            assert np.array_equal(
                np.asarray(cr)[keep], np.asarray(cg)[keep]
            ), (kind, ci)


def test_dict_keyed_build_lut_cache_never_poisons(monkeypatch):
    """Exec-level regression (found by the AQE build-side flip): a
    dictionary-keyed build's code domain GROWS every time a probe batch
    unifies new strings into its dictionary, so the cross-run
    ``join_lut`` plan-cache entry re-poisoned itself — learn the first
    build's range, outgrow it on the next unification, SpeculationMiss,
    invalidate, relearn — until the retry bound failed the task.
    Dict-keyed builds must take the fresh-flags path (no cache) and the
    join must complete correctly with a many-batch probe stream."""
    import pyarrow as pa

    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.exec.context import TpuContext
    from ballista_tpu.exec.joins import HashJoinExec

    monkeypatch.setattr(HashJoinExec, "_LUT_MIN_PROBE", 1)
    n_dim = 400

    def strings(lo: int, hi: int, reps: int):
        return pa.array(
            [f"s{i}" for _ in range(reps) for i in range(lo, hi)]
        )

    # three probe sources with DISJOINT string domains: each scan batch
    # carries its OWN dictionary (one registered table's dictionary is
    # table-wide, which hides the growth — shuffle files from separate
    # map tasks, the distributed shape, do not), so every union arm
    # unifies NEW entries into the build dictionary. The first arm's
    # learned domain (~1200 codes, rounded to the 2048 capacity floor)
    # is outgrown by the later arms (cumulative ~20k codes).
    facts = {
        "fact1": (0, 800),
        "fact2": (800, 5000),
        "fact3": (5000, 20000),
    }
    dim = pa.table(
        {
            "skey": pa.array([f"s{i}" for i in range(n_dim)]),
            "attr": pa.array([i % 7 for i in range(n_dim)]),
        }
    )
    union = " UNION ALL ".join(
        f"SELECT skey, v FROM {t}" for t in facts
    )
    # fact side first: the BUILD is the small dict-keyed dim, the probe
    # the multi-dictionary union stream — the poisoning shape
    sql = (
        "SELECT count(*) AS c, sum(f.v) AS s "
        f"FROM ({union}) f JOIN dim d ON f.skey = d.skey"
    )

    ctx = TpuContext(BallistaConfig())
    fact_tables = {
        t: pa.table(
            {
                "skey": strings(lo, hi, 2),
                "v": pa.array(
                    [float(i % 97) for i in range(2 * (hi - lo))]
                ),
            }
        )
        for t, (lo, hi) in facts.items()
    }
    for t, tab in fact_tables.items():
        ctx.register_table(t, tab)
    ctx.register_table("dim", dim)
    # twice through the SAME context: the second run hits whatever the
    # first left in the shared plan cache
    first = ctx.sql(sql).collect().to_pydict()
    second = ctx.sql(sql).collect().to_pydict()
    assert first == second
    # only fact1's first n_dim distinct keys match the dim, twice each
    f1 = fact_tables["fact1"].to_pydict()
    exp_s = sum(
        v for k, v in zip(f1["skey"], f1["v"]) if int(k[1:]) < n_dim
    )
    assert first["c"] == [2 * n_dim]
    assert abs(first["s"][0] - exp_s) < 1e-6 * max(1.0, exp_s)
