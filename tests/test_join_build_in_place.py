"""The join build leaves its rows where they lie (``ops/join.py``): the
finisher sorts and gathers the keys only, and every reader of a build row
(the probe's payload gather, the expansion) gathers at ``perm[sorted
position]``. Held here to a numpy reference over the same data, in every
packing mode, join kind and probe path, with dead rows and null keys in the
build and a nullable and a dictionary-encoded payload; to the finisher's
lowered program, whose gathers must not grow with the payload; and to the
counters the operator keeps of it (``join.build_gather_bytes``,
``join.builds_in_place``) on the served path."""

import numpy as np
import pyarrow as pa
import pytest

import jax.numpy as jnp

from ballista_tpu.columnar.batch import DeviceBatch, Dictionary, round_capacity
from ballista_tpu.datatypes import DataType, Field, Schema
from ballista_tpu.ops.join import (
    JoinSide,
    _build_finish_jit,
    attach_lut,
    build_side,
    expand_join,
    probe_counts,
    probe_side,
)

CAP = 256
ROWS = 200
WORDS = ("ash", "birch", "cedar", "elm", "fir", "oak", "yew")
# the build's key dtypes per packing mode: two int keys in the exact2
# ranges pack exactly; a negative first key leaves them to the hash
KEY_TYPES = {
    "exact": [DataType.INT64],
    "exact2": [DataType.INT64, DataType.INT32],
    "hash": [DataType.INT64, DataType.INT64],
}


def _keys(mode, n, rng, *, dups, contiguous):
    """``n`` key tuples as columns: unique or three rows a key; the first
    key exactly ``[lo, lo + n)`` where ``contiguous``."""
    distinct = n // 3 if dups else n
    if contiguous:
        first = 1_000 + np.arange(distinct)
    elif mode == "hash":
        first = rng.choice(np.arange(-5_000, 5_000), distinct, replace=False)
    else:
        first = rng.choice(50_000, distinct, replace=False)
    cols = [first.astype(np.int64)]
    if mode != "exact":
        cols.append(rng.integers(0, 1 << 20, distinct))
    pick = rng.permutation(np.resize(np.arange(distinct), n))
    return [c[pick] for c in cols]


def _build_data(mode, dups, contiguous, seed):
    """The build batch as host arrays: live rows, then dead rows and null
    keys scattered among them and a dead tail; the dead and null-key rows
    carry keys of their own, which must match nothing."""
    rng = np.random.default_rng(seed)
    valid = np.arange(CAP) < ROWS
    valid[rng.choice(ROWS, 20, replace=False)] = False
    key_null = np.zeros(CAP, bool)
    key_null[rng.choice(ROWS, 15, replace=False)] = True
    live = valid & ~key_null
    keys = [np.zeros(CAP, np.int64) for _ in KEY_TYPES[mode]]
    for c, v in zip(keys, _keys(mode, int(live.sum()), rng, dups=dups,
                                contiguous=contiguous)):
        c[live] = v
    for c in keys:  # the rows that must not match: keys no live row has
        c[~live] = 90_000 + np.arange(int((~live).sum()))
    return {
        "keys": [c.astype(t.to_np()) for c, t in zip(keys, KEY_TYPES[mode])],
        "valid": valid, "key_null": key_null,
        "v": rng.normal(size=CAP), "v_null": rng.random(CAP) < 0.15,
        "s": rng.integers(0, len(WORDS), CAP).astype(np.int32),
        "w": rng.integers(-100, 100, CAP).astype(np.int32),
    }


def _build_batch(d) -> DeviceBatch:
    nk = len(d["keys"])
    fields = [Field(f"bk{j}", DataType.INT64 if c.dtype == np.int64
                    else DataType.INT32, True)
              for j, c in enumerate(d["keys"])]
    fields += [Field("v", DataType.FLOAT64, True),
               Field("s", DataType.STRING, False),
               Field("w", DataType.INT32, False)]
    nulls = [jnp.asarray(d["key_null"])] + [None] * (nk - 1)
    nulls += [jnp.asarray(d["v_null"]), None, None]
    return DeviceBatch(
        schema=Schema(fields),
        columns=tuple(jnp.asarray(c) for c in d["keys"])
        + (jnp.asarray(d["v"]), jnp.asarray(d["s"]), jnp.asarray(d["w"])),
        valid=jnp.asarray(d["valid"]), nulls=tuple(nulls),
        dictionaries={"s": Dictionary(WORDS)},
    )


def _probe_data(d, seed):
    """Probe keys: live build keys, the dead rows' keys, misses, and for
    two keys a live first key beside a wrong second; null keys and dead
    rows besides."""
    rng = np.random.default_rng(seed + 1)
    live = d["valid"] & ~d["key_null"]
    rows = rng.choice(CAP, CAP)
    keys = [c[rows].astype(np.int64) for c in d["keys"]]
    miss = rng.random(CAP) < 0.15
    keys[0][miss] = 70_000 + np.arange(int(miss.sum()))
    if len(keys) > 1:
        wrong = rng.random(CAP) < 0.1
        keys[1][wrong] = (1 << 21) + np.arange(int(wrong.sum()))
    valid = rng.random(CAP) > 0.05
    key_null = rng.random(CAP) < 0.05
    assert live[rows].any() and (~live[rows]).any()
    return {"keys": keys, "valid": valid, "key_null": key_null}


def _probe_batch(p) -> DeviceBatch:
    fields = [Field("pid", DataType.INT64, False)]
    fields += [Field(f"pk{j}", DataType.INT64, True)
               for j in range(len(p["keys"]))]
    nulls = (None, jnp.asarray(p["key_null"])) + (None,) * (len(p["keys"]) - 1)
    return DeviceBatch(
        schema=Schema(fields),
        columns=(jnp.arange(CAP, dtype=jnp.int64),)
        + tuple(jnp.asarray(c) for c in p["keys"]),
        valid=jnp.asarray(p["valid"]), nulls=nulls, dictionaries={},
    )


def _reference(d, p, kind):
    """numpy: the rows the join must give, as sorted tuples of the probe
    row and the build row's payload (``None`` for a NULL)."""
    live = d["valid"] & ~d["key_null"]
    index: dict = {}
    for r in np.flatnonzero(live):
        index.setdefault(tuple(int(c[r]) for c in d["keys"]), []).append(r)

    def payload(r):
        v = None if d["v_null"][r] else float(d["v"][r])
        return (v, WORDS[d["s"][r]], int(d["w"][r]))

    out = []
    for i in np.flatnonzero(p["valid"]):
        hits = [] if p["key_null"][i] else index.get(
            tuple(int(c[i]) for c in p["keys"]), [])
        if kind in (JoinSide.SEMI, JoinSide.ANTI):
            if bool(hits) == (kind == JoinSide.SEMI):
                out.append((int(i),))
            continue
        out += [(int(i),) + payload(r) for r in hits]
        if not hits and kind == JoinSide.LEFT:
            out.append((int(i), None, None, None))
    return sorted(out, key=repr)


def _rows(batch: DeviceBatch, kind):
    """The valid rows of a join's output in ``_reference``'s form."""
    valid = np.asarray(batch.valid)
    cols = [np.asarray(c) for c in batch.columns]
    if kind in (JoinSide.SEMI, JoinSide.ANTI):
        return sorted(((int(i),) for i in cols[0][valid]), key=repr)
    names = batch.schema.names
    at = {n: names.index(n) for n in ("v", "s", "w")}
    nulls = {n: (np.zeros(len(valid), bool) if batch.nulls[j] is None
                 else np.asarray(batch.nulls[j])) for n, j in at.items()}
    words = batch.dictionaries["s"].values
    out = []
    for r in np.flatnonzero(valid):
        v = None if nulls["v"][r] else float(cols[at["v"]][r])
        s = None if nulls["s"][r] else words[cols[at["s"]][r]]
        w = None if nulls["w"][r] else int(cols[at["w"]][r])
        out.append((int(cols[0][r]), v, s, w))
    return sorted(out, key=repr)


def _join(bt, pb, nk, kind, reader, path, layout):
    """One join the way ``exec/joins.py`` runs it: ``probe_side`` for a
    unique build, ``probe_counts`` + ``expand_join`` for the m:n path; the
    payload where it arrived, or gathered into sorted order once as the
    operator does for a probe batch larger than the build."""
    pkeys = list(range(1, 1 + nk))
    if path == "lut":
        _, _, _, lo, hi = bt.flags()
        attach_lut(bt, round_capacity(hi - lo + 1))
    if layout == "sorted":
        sorted_bt, gathered = bt.in_sorted_order()
        assert sorted_bt.perm is None and gathered > 0
        assert bt.in_sorted_order()[1] == 0  # made once a build
        bt = sorted_bt
    if reader == "probe":
        return probe_side(bt, pb, pkeys, kind,
                          contiguous=path == "contiguous")
    first, count, _ = probe_counts(bt, pb, pkeys)
    if kind in (JoinSide.SEMI, JoinSide.ANTI):
        m = count > 0
        return pb.with_valid(pb.valid & (m if kind == JoinSide.SEMI else ~m))
    eff = (jnp.where(pb.valid, jnp.maximum(count, 1), 0)
           if kind == JoinSide.LEFT else count)
    out_cap = round_capacity(max(int(jnp.sum(eff)), 1))
    return expand_join(bt, pb, first, count, eff, out_cap, kind)[0]


def _cases():
    paths = {"exact": ["search", "lut", "contiguous"],
             "exact2": ["search", "contiguous"], "hash": ["search"]}
    for mode, ps in paths.items():
        for kind in JoinSide:
            for layout in ("in_place", "sorted"):
                for path in ps:
                    yield mode, kind, "unique", "probe", path, layout
                for dup in ("unique", "dup"):
                    for path in [p for p in ps if p != "contiguous"]:
                        yield mode, kind, dup, "expand", path, layout


@pytest.mark.parametrize("mode,kind,build,reader,path,layout", list(_cases()))
def test_a_join_over_a_build_in_place_is_the_references(
        mode, kind, build, reader, path, layout):
    seed = 4_300_000_000 + len(mode) * 100 + len(path) * 10 + len(build)
    d = _build_data(mode, build == "dup", path == "contiguous", seed)
    p = _probe_data(d, seed)
    batch = _build_batch(d)
    bt = build_side(batch, list(range(len(d["keys"]))))
    assert bt.mode == mode
    dups, overflow, contiguous = bt.flags()[:3]
    assert (dups, overflow) == (build == "dup", False)
    assert contiguous == (path == "contiguous")
    # the build batch is the one that came in: nothing of it was copied
    assert bt.batch is batch
    out = _join(bt, _probe_batch(p), len(d["keys"]), kind, reader, path,
                layout)
    assert _rows(out, kind) == _reference(d, p, kind)


# -- the finisher's program ------------------------------------------------------


def _finisher_gathers(mode: str, payload: int, with_nulls: bool) -> int:
    """Gather ops of ``_build_finish`` lowered for int64 keys (one in exact
    mode, two otherwise) and ``payload`` columns of mixed dtypes."""
    nk = 1 if mode == "exact" else 2
    types = [DataType.INT64, DataType.FLOAT64, DataType.INT32,
             DataType.BOOL, DataType.STRING]
    fields = [Field(f"k{j}", DataType.INT64, False) for j in range(nk)]
    fields += [Field(f"p{j}", types[j % len(types)], True)
               for j in range(payload)]
    cols = tuple(jnp.zeros(CAP, f.dtype.to_np()) for f in fields)
    nulls = (None,) * nk + tuple(
        jnp.zeros(CAP, bool) if with_nulls else None for _ in range(payload))
    batch = DeviceBatch(
        schema=Schema(fields), columns=cols, valid=jnp.ones(CAP, bool),
        nulls=nulls, dictionaries={f.name: Dictionary(("a",)) for f in fields
                                   if f.dtype == DataType.STRING},
    )
    text = _build_finish_jit.lower(
        jnp.arange(CAP, dtype=jnp.int32), jnp.zeros(CAP, bool), batch,
        key_idxs=tuple(range(nk)), mode=mode,
    ).as_text()
    return text.count('"stablehlo.gather"(')


@pytest.mark.parametrize("mode", ["exact", "exact2", "hash"])
def test_the_finisher_gathers_no_payload(mode):
    """One gather in exact mode (the packed key widens the key column); at most
    the key columns and one more otherwise; the same with five payload
    columns, null masks or none, as with none."""
    bare = _finisher_gathers(mode, 0, False)
    assert bare == 1 if mode == "exact" else 1 <= bare <= 3
    for with_nulls in (False, True):
        assert _finisher_gathers(mode, 5, with_nulls) == bare


# -- the counters ----------------------------------------------------------------


def _served_join(fact_rows: int, dim_rows: int):
    """A served inner join of a ``fact_rows`` table to a ``dim_rows`` one
    on an int64 key, then a query that joins nothing: the join's answer,
    how the ``join.*`` counters moved in each, and the (capacity, mode) of
    every build the operator made."""
    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.compilecache import metrics
    from ballista_tpu.exec import joins

    rng = np.random.default_rng(4_300_000_001)
    fact = pa.table({"k": rng.integers(0, dim_rows, fact_rows),
                     "x": rng.normal(size=fact_rows)})
    dim = pa.table({"k": rng.permutation(dim_rows).astype(np.int64),
                    "y": rng.integers(0, 9, dim_rows)})
    built: list = []
    real = joins.build_side

    def spy(batch, key_idxs):
        bt = real(batch, key_idxs)
        built.append((batch.capacity, bt.mode))
        return bt

    joins.build_side = spy
    ctx = BallistaContext.standalone(concurrent_tasks=2)
    try:
        ctx.register_table("fact", fact)
        ctx.register_table("dim", dim)

        def moved(sql):
            before = metrics.snapshot()
            answer = ctx.sql(sql).collect()
            after = metrics.snapshot()
            return answer, {k: after[k] - before.get(k, 0) for k in after
                            if k.startswith("join.")}

        answer, joined = moved(
            "select count(*) as n, sum(dim.y) as s from fact "
            "join dim on fact.k = dim.k")
        _, alone = moved("select count(*) as n from fact where x > 0")
    finally:
        joins.build_side = real
        ctx.close()
    y = dim["y"].to_numpy()[np.argsort(dim["k"].to_numpy())]
    assert answer.column("n").to_pylist() == [fact_rows]
    assert answer.column("s").to_pylist() == [
        int(y[fact["k"].to_numpy()].sum())]
    assert alone["join.build_gather_bytes"] == 0
    assert alone["join.builds_in_place"] == 0
    assert built and {mode for _, mode in built} == {"exact"}
    assert joined["join.builds"] == len(built)
    return joined, built


def test_the_counters_of_a_served_join_are_its_builds():
    """A served inner join on an int64 key at 5e4 rows a side: each build
    gathered its capacity x 8 bytes through the permutation (exact mode:
    the int64 key column alone), and every build left its payload in place; a
    query that joins nothing moves neither."""
    joined, built = _served_join(50_000, 50_000)
    assert joined["join.builds_in_place"] == joined["join.builds"]
    assert joined["join.build_gather_bytes"] == sum(
        cap * 8 for cap, _ in built)


def test_a_build_smaller_than_its_probe_batches_is_gathered_once():
    """A 1,000-row build probed by batches of 5e4 rows: its payload is
    gathered into sorted order once, so the build is not in place and its
    payload's bytes count beside its keys'; the answer is the same."""
    joined, built = _served_join(50_000, 1_000)
    assert joined["join.builds_in_place"] < joined["join.builds"]
    assert joined["join.build_gather_bytes"] > sum(
        cap * 8 for cap, _ in built)


# -- the operator's choice of layout ---------------------------------------------


@pytest.fixture
def operator():
    """A hash join operator over two small registered tables; the tests
    hand it build tables and probe batches of their own."""
    from ballista_tpu.exec.context import TpuContext
    from ballista_tpu.exec.joins import HashJoinExec
    from ballista_tpu.expr import logical as L
    from ballista_tpu.plan import logical as P

    ctx = TpuContext()
    ctx.register_table("a", pa.table({"k": np.arange(4, dtype=np.int64)}))
    ctx.register_table("b", pa.table({"k": np.arange(4, dtype=np.int64)}))
    return HashJoinExec(ctx.scan("a", None, 1), ctx.scan("b", None, 1),
                        [(L.col("k"), L.col("k"))], P.JoinType.INNER)


def _table(mode, dups, seed, cap=CAP):
    """``_build_data`` and its build table, the batch padded with dead rows
    to ``cap`` slots."""
    d = _build_data(mode, dups, False, seed)
    d = {k: [np.pad(c, (0, cap - CAP)) for c in v] if k == "keys"
         else np.pad(v, (0, cap - CAP)) for k, v in d.items()}
    return d, build_side(_build_batch(d), list(range(len(d["keys"]))))


def test_the_sorted_payload_is_a_join_program():
    """The gather into sorted order runs as ``jit_join_sorted_rows``, a
    name the join's device-time readers match (``perf/layers/_join.py``),
    and gives the payload in the order of the sorted keys."""
    import importlib.util
    import pathlib

    from ballista_tpu.ops.join import _sorted_rows_program

    d, bt = _table("exact", False, 4_300_000_101)
    b = bt.batch
    prog = _sorted_rows_program(tuple(str(c.dtype) for c in b.columns),
                                tuple(m is not None for m in b.nulls))
    text = prog.lower(tuple(b.columns), tuple(b.nulls), b.valid,
                      bt.perm).as_text()
    assert "@jit_join_sorted_rows" in text
    path = (pathlib.Path(__file__).resolve().parent.parent / "perf"
            / "layers" / "_join.py")
    spec = importlib.util.spec_from_file_location("_join_reader", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert reader.JOIN.match("jit_join_sorted_rows")
    sorted_bt, gathered = bt.in_sorted_order()
    n = int(bt.n)
    assert gathered > 0 and sorted_bt.sorted_batch is None
    np.testing.assert_array_equal(
        np.asarray(sorted_bt.batch.columns[0])[:n],
        np.asarray(bt.keys)[:n])
    assert bool(np.asarray(sorted_bt.batch.valid)[:n].all())


def test_task_slots_sharing_a_table_gather_it_once(operator):
    """Eight threads that read a cached build by batches larger than it
    gather its payload once between them: one count of its bytes, one build
    out of place, and the cache slot then holds the sorted table in place
    of the arrival batch. A batch no larger than the build reads it in
    place."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    _, bt = _table("exact", False, 4_300_000_102)
    slot = ("bt_right",)
    operator._build_cache[slot] = (bt.batch, bt)
    assert operator._rows_for(bt, CAP) is bt
    start = threading.Barrier(8)

    def read(_):
        start.wait()
        return operator._rows_for(bt, 4 * CAP)

    with ThreadPoolExecutor(8) as pool:
        got = list(pool.map(read, range(8)))
    assert all(t.perm is None and t.batch is bt.sorted_batch for t in got)
    c = operator.metrics.counters
    assert c["builds_in_place"] == -1
    assert c["build_gather_bytes"] == sum(
        a.nbytes for a in (*bt.sorted_batch.columns, bt.sorted_batch.valid,
                           *bt.sorted_batch.nulls) if a is not None)
    cached_batch, cached = operator._build_cache[slot]
    assert cached.perm is None and cached_batch is bt.sorted_batch


@pytest.mark.parametrize("cap", [CAP, 2048])
def test_an_expansion_decides_its_layout_by_its_output(operator, cap):
    """The expansion gathers the build at its output capacity (2,048 slots
    here, the least capacity there is), so that is the size it decides the
    layout by, whatever the probe batch's: an output larger than the build
    gathers the build's payload into sorted order, one no larger leaves it
    in place. A probe batch of 256 rows reads both builds. The answer is
    the reference's."""
    seed = 4_300_000_103 + cap
    d, bt = _table("exact", True, seed, cap)
    p = _probe_data(d, seed)
    out = operator._expand_with_filter(bt, _probe_batch(p), [1],
                                       JoinSide.INNER)
    assert out.capacity == 2048
    assert _rows(out, JoinSide.INNER) == _reference(d, p, JoinSide.INNER)
    outgrown = cap < out.capacity
    assert (bt.sorted_batch is not None) == outgrown
    assert operator.metrics.counters.get("builds_in_place", 0) == -outgrown
