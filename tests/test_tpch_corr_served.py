"""TPC-H's correlated scalar subquery templates (q17, q20: the templates of
the cell ``tpch-sf1-corr-mem.correlated``) on the served path, against each
template's own plain reference, at a size the CPU runs.

SF 0.05 under a seed whose every parameter set below keeps lines (q17) and
suppliers (q20). Each template at clause 2.4's validation parameters and at
two draws, and once more on two executors, where the decorrelating
aggregate's partial and final halves are cut across a shuffle. Then two
crafted tables: q17 with lines at an exact decimal tie (``5 * qty * count =
sum``), which SQL's ``<`` drops, and q20 with pairs that have no line in the
year (their subquery is NULL: the pair is dropped) or exactly twice the
available quantity shipped, and a correlated subquery whose outer keys
repeat, miss the subquery's rows or are NULL. The decorrelating aggregate
groups only the keys the outer query can join (the semi-join reduction by
the outer's key domain): the counters ``subquery.*`` read the rows and
groups of the reduced aggregates, and nothing for a plan without one."""

import pathlib
import sys

import numpy as np
import pyarrow as pa
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
PERF = ROOT / "perf"
for p in (str(ROOT), str(PERF)):
    if p not in sys.path:
        sys.path.insert(0, p)

import datagen  # noqa: E402
import traffic  # noqa: E402
import verify  # noqa: E402

from ballista_tpu.compilecache import metrics  # noqa: E402

SF = 0.05
SEED = 4_000_000_017
TEMPLATES = ("q17", "q20")
NEEDED = ("part", "lineitem", "partsupp", "supplier", "nation")


@pytest.fixture(scope="module")
def templates():
    return traffic.load_templates(TEMPLATES)


@pytest.fixture(scope="module")
def data(templates):
    tables = {n: t for n, t in datagen.gen_all(SF, SEED).items()
              if n in NEEDED}
    return tables, verify.frames(tables, templates)


def parameters(templates) -> dict:
    """(template, which) -> parameters: clause 2.4's validation values and
    the two draws of the cell's pool."""
    pool = traffic.pool(traffic.load("correlated"), templates)
    out = {}
    for name, mod in templates.items():
        out[name, "validation"] = mod.VALIDATION
        out[name, "draw0"], out[name, "draw1"] = pool[name]
    return out


def standalone(tables, config=None, **kw):
    from ballista_tpu.client.context import BallistaContext

    ctx = BallistaContext.standalone(config, **kw)
    for name, table in tables.items():
        ctx.register_table(name, table)
    return ctx


@pytest.fixture(scope="module")
def one_executor(data):
    ctx = standalone(data[0], concurrent_tasks=4)
    yield ctx
    ctx.close()


@pytest.fixture(scope="module")
def two_executors(data):
    from ballista_tpu.config import BallistaConfig

    config = BallistaConfig({"ballista.shuffle.partitions": "2"})
    ctx = standalone(data[0], config, concurrent_tasks=2, n_executors=2)
    yield ctx
    ctx.close()


def served(ctx, sql):
    """The answer, and how far each ``subquery.*`` counter moved."""
    before = metrics.snapshot()
    answer = ctx.sql(sql).collect()
    moved = {k: v - before[k] for k, v in metrics.snapshot().items()
             if k.startswith("subquery.")}
    return answer, moved


def expected_work(tables, name, p):
    """The decorrelating aggregate's input rows and groups once reduced to
    the outer query's parts, by numpy: q17's lines of the brand's parts in
    the container, by part; q20's lines of the year of the parts whose name
    starts with the colour, by part and supplier."""
    li, parts = tables["lineitem"], tables["part"]
    part = li["l_partkey"].to_numpy()
    keys = parts["p_partkey"].to_numpy()
    if name == "q17":
        kept = keys[(parts["p_brand"].to_numpy(zero_copy_only=False)
                     == p["brand"])
                    & (parts["p_container"].to_numpy(zero_copy_only=False)
                       == p["container"])]
        lines = np.isin(part, kept)
        return int(lines.sum()), len(np.unique(part[lines]))
    names = parts["p_name"].to_numpy(zero_copy_only=False)
    kept = keys[np.char.startswith(names.astype(str), p["color"])]
    ship = li["l_shipdate"].cast(pa.int32()).to_numpy()
    lo = datagen.days(int(p["date"][:4]), 1, 1)
    hi = datagen.days(int(p["date"][:4]) + 1, 1, 1)
    lines = (ship >= lo) & (ship < hi) & np.isin(part, kept)
    pairs = np.unique(np.stack([part[lines],
                                li["l_suppkey"].to_numpy()[lines]]), axis=1)
    return int(lines.sum()), pairs.shape[1]


CASES = ([(t, which, "one_executor") for t in TEMPLATES
          for which in ("validation", "draw0", "draw1")]
         + [(t, "validation", "two_executors") for t in TEMPLATES])


@pytest.mark.parametrize("name,which,cluster", CASES)
def test_the_served_path_gives_the_reference_answer(
        request, templates, data, name, which, cluster):
    ctx = request.getfixturevalue(cluster)
    mod, p = templates[name], parameters(templates)[name, which]
    reference = mod.reference(data[1], p)
    answer, moved = served(ctx, mod.SQL.format(**p))
    verdict = verify.judge([(name, 0, answer)], {name: mod},
                           {(name, 0): reference}, 0)
    assert verdict["correct"], (verdict["numbers"], verdict["first_mismatch"])
    assert verdict["numbers"]["mismatched"] == {"value": 0, "limit": 0}
    if name == "q17":
        assert len(mod.small_lines(data[1], p)) > 5
        assert verdict["numbers"]["relerr_q17"]["value"] < 1e-12
    else:
        assert len(reference) > 3 and mod.LIMITS == {}
    # the decorrelating aggregate saw every row of the outer query's parts
    # under it once, and handed the join one row a group, however many
    # tasks it ran in: every task of its partial ran over a reduced input
    rows, groups = expected_work(data[0], name, p)
    assert 0 < rows < data[0]["lineitem"].num_rows / 20
    assert moved["subquery.agg_rows"] == rows
    assert moved["subquery.agg_groups"] == groups
    assert moved["subquery.agg_self_seconds"] > 0
    assert moved["subquery.agg_reduced"] > 0


def test_no_correlated_scalar_subquery_counts_nothing(one_executor, data):
    """A grouped aggregate, a join and an uncorrelated subquery: none of
    them decorrelates a scalar subquery."""
    sql = """select l_partkey, count(*) as n from lineitem, part
             where l_partkey = p_partkey and l_quantity > (
                 select avg(l_quantity) from lineitem)
             group by l_partkey"""
    answer, moved = served(one_executor, sql)
    assert answer.num_rows > 100
    assert moved == dict.fromkeys(moved, 0) and len(moved) == 4


# -- crafted: a decimal tie, and a pair without lines in the year -----------

# part -> its lines' (l_quantity, l_extendedprice); every part is Brand#23
# and MED BOX but 7. A tie is a line whose 5 * qty * count equals its part's
# sum: 0.2 * AVG is exactly its quantity, and SQL's < drops it.
Q17_LINES = {
    1: [(1, 1000.0), (4, 2.0), (10, 3.0)],           # tie at 1: 5*1*3 = 15
    2: [(1, 100.0), (9, 5.0), (20, 6.0)],            # 1 < 0.2 * 10 = 2
    3: [(7, 700.0), (48, 8.0), (50, 9.0)],           # tie at 7: 0.2 * 35
    4: [(3, 300.0), (17, 11.0), (20, 12.0), (20, 13.0)],  # tie at 3
    5: [(9, 900.0)] + [(49, 14.0)] * 9,              # tie at 9: 0.2 * 45
    6: [(2, 10.5), (30, 15.0), (30, 16.0)],          # 2 < 0.2 * 62 / 3
    7: [(1, 5000.0), (4, 17.0), (10, 18.0)],         # a tie, other brand
}
TIES = 1000.0 + 700.0 + 300.0 + 900.0


def q17_tables():
    rows = [(k, q, price) for k, lines in Q17_LINES.items()
            for q, price in lines]
    part = pa.table({
        "p_partkey": pa.array(list(Q17_LINES), pa.int64()),
        "p_brand": ["Brand#23"] * 6 + ["Brand#12"],
        "p_container": ["MED BOX"] * 7,
    })
    lineitem = pa.table({
        "l_partkey": pa.array([r[0] for r in rows], pa.int64()),
        "l_quantity": pa.array([float(r[1]) for r in rows]),
        "l_extendedprice": pa.array([r[2] for r in rows]),
    })
    return {"part": part, "lineitem": lineitem}


def test_q17_drops_the_lines_at_an_exact_decimal_tie(templates):
    mod = templates["q17"]
    tables = q17_tables()
    frames = verify.frames(tables, {"q17": mod})
    reference = mod.reference(frames, mod.VALIDATION)
    # the reference keeps the two lines below a fifth of their average and
    # drops the four at a tie
    assert reference.avg_yearly[0] == pytest.approx((100.0 + 10.5) / 7.0)
    sql = mod.SQL.format(**mod.VALIDATION)
    ctx = standalone(tables, concurrent_tasks=4)
    try:
        answer, moved = served(ctx, sql)
        # a tie decided as <= keeps the four
        lenient, _ = served(ctx, sql.replace("l_quantity <", "l_quantity <="))
    finally:
        ctx.close()
    verdict = verify.judge([("q17", 0, answer)], {"q17": mod},
                           {("q17", 0): reference}, 0)
    assert verdict["correct"], (verdict["numbers"], answer.to_pylist())
    # reduced to the six Brand#23 parts: part 7's lines are not grouped
    assert moved["subquery.agg_rows"] == sum(
        len(lines) for k, lines in Q17_LINES.items() if k != 7)
    assert moved["subquery.agg_groups"] == 6
    assert moved["subquery.agg_reduced"] > 0
    # the case bites: the comparison refuses the lenient answer
    assert lenient.to_pylist()[0]["avg_yearly"] == pytest.approx(
        (100.0 + 10.5 + TIES) / 7.0)
    refused = verify.judge([("q17", 0, lenient)], {"q17": mod},
                           {("q17", 0): reference}, 0)
    assert not refused["correct"]
    assert refused["numbers"]["relerr_q17"]["value"] > 1


# supplier -> (nation, [(partkey, availqty, [(shipdate, qty)])]); parts 1 and
# 2 are forest parts, 3 is not
Q20_SUPPLIERS = {
    1: (3, [(1, 100, [("1994-03-01", 30.0), ("1994-07-01", 20.0)])]),
    2: (3, [(1, 25, [("1994-05-01", 50.0)])]),       # 25 = 0.5 * 50: no
    3: (3, [(2, 9000, [("1993-05-01", 5.0), ("1995-02-01", 5.0)])]),
    4: (3, [(3, 9000, [("1994-05-01", 5.0)])]),      # not a forest part
    5: (7, [(2, 9000, [("1994-05-01", 5.0)])]),      # not in CANADA
    6: (3, [(2, 9000, [("1994-12-31", 5.0)]),
            (1, 1, [("1994-01-01", 50.0)])]),
}


def q20_tables():
    ps_rows, li_rows = [], []
    for s, (_, pairs) in Q20_SUPPLIERS.items():
        for pk, avail, lines in pairs:
            ps_rows.append((pk, s, avail))
            li_rows += [(pk, s, q, datagen.days(*map(int, d.split("-"))))
                        for d, q in lines]
    keys = list(Q20_SUPPLIERS)
    return {
        "part": pa.table({
            "p_partkey": pa.array([1, 2, 3], pa.int64()),
            "p_name": ["forest green lace", "forest mint red",
                       "navy forest tan"],
        }),
        "partsupp": pa.table({
            "ps_partkey": pa.array([r[0] for r in ps_rows], pa.int64()),
            "ps_suppkey": pa.array([r[1] for r in ps_rows], pa.int64()),
            "ps_availqty": pa.array([r[2] for r in ps_rows], pa.int32()),
        }),
        "lineitem": pa.table({
            "l_partkey": pa.array([r[0] for r in li_rows], pa.int64()),
            "l_suppkey": pa.array([r[1] for r in li_rows], pa.int64()),
            "l_quantity": pa.array([r[2] for r in li_rows]),
            "l_shipdate": pa.array([r[3] for r in li_rows],
                                   pa.int32()).cast(pa.date32()),
        }),
        "supplier": pa.table({
            "s_suppkey": pa.array(keys, pa.int64()),
            "s_name": [f"Supplier#{k:09d}" for k in keys],
            "s_address": [f"address {k}" for k in keys],
            "s_nationkey": pa.array([Q20_SUPPLIERS[k][0] for k in keys],
                                    pa.int64()),
        }),
        "nation": pa.table({
            "n_nationkey": pa.array(range(25), pa.int64()),
            "n_name": [n for n, _ in datagen.NATIONS],
        }),
    }


def test_q20_drops_a_pair_without_lines_in_the_year(templates):
    """Supplier 3's forest pair shipped in 1993 and 1995 only: no group, a
    NULL subquery, dropped. Supplier 2 holds exactly half of what it
    shipped: not more. Supplier 6 is kept by its pair with a line on the
    year's last day, not by the one that shipped 50 against 1."""
    mod = templates["q20"]
    tables = q20_tables()
    frames = verify.frames(tables, {"q20": mod})
    reference = mod.reference(frames, mod.VALIDATION)
    assert reference.s_name.tolist() == [
        "Supplier#000000001", "Supplier#000000006"]
    ctx = standalone(tables, concurrent_tasks=4)
    try:
        answer, moved = served(ctx, mod.SQL.format(**mod.VALIDATION))
    finally:
        ctx.close()
    verdict = verify.judge([("q20", 0, answer)], {"q20": mod},
                           {("q20", 0): reference}, 0)
    assert verdict["correct"], (verdict["first_mismatch"], answer.to_pylist())
    # the year's lines of the forest parts (six of nine: part 3's is not
    # one) into five (part, supplier) groups
    assert moved["subquery.agg_rows"] == 6
    assert moved["subquery.agg_groups"] == 5


# -- crafted: outer keys that repeat, that the subquery lacks, that are NULL -

# outer rows (id, k, tag, v) and the subquery's rows (k, q): keys 1 and 2
# repeat in the outer query, 3 has no subquery row (NULL: dropped), one
# outer key and one subquery key are NULL (never equal), 4 is kept by no
# outer row (its group is cut) and 0 is a key like any other
OUTER = [(1, 1, "keep", 1.0), (2, 1, "keep", 9.0), (3, 2, "keep", 2.0),
         (4, 2, "keep", 3.5), (5, 2, "drop", 0.0), (6, 3, "keep", 0.0),
         (7, None, "keep", 0.0), (8, 0, "keep", 1.0), (9, 4, "drop", 0.0),
         (10, 1, "keep", 3.0)]
INNER = [(1, 6.0), (1, 10.0), (2, 8.0), (2, 6.0), (2, 7.0), (4, 100.0),
         (4, 50.0), (None, 40.0), (0, 4.0), (5, 1.0)]
REPEATS = """select id, ok, v from o where tag = 'keep'
             and v < (select 0.5 * avg(q) from t where tk = ok)
             order by id"""


def test_outer_keys_that_repeat_miss_or_are_null_give_the_reference():
    """The reduction keeps each group some outer row can join, once however
    often its key repeats, and the answer is SQL's: a key the subquery has
    no row for, or a NULL key, drops its outer rows."""
    tables = {
        "o": pa.table({
            "id": pa.array([r[0] for r in OUTER], pa.int64()),
            "ok": pa.array([r[1] for r in OUTER], pa.int64()),
            "tag": [r[2] for r in OUTER],
            "v": pa.array([r[3] for r in OUTER]),
        }),
        "t": pa.table({
            "tk": pa.array([r[0] for r in INNER], pa.int64()),
            "q": pa.array([r[1] for r in INNER]),
        }),
    }
    reference = []
    for i, k, tag, v in OUTER:
        qs = [q for tk, q in INNER if k is not None and tk == k]
        if tag == "keep" and qs and v < 0.5 * sum(qs) / len(qs):
            reference.append({"id": i, "ok": k, "v": v})
    assert [r["id"] for r in reference] == [1, 3, 8, 10]
    ctx = standalone(tables, concurrent_tasks=4)
    try:
        answer, moved = served(ctx, REPEATS)
    finally:
        ctx.close()
    assert answer.to_pylist() == reference
    # the kept keys 0, 1, 2 and 3 hold six of the subquery's ten rows, in
    # three groups: 4's, 5's and the NULL key's are cut
    assert moved["subquery.agg_rows"] == 6
    assert moved["subquery.agg_groups"] == 3
    assert moved["subquery.agg_reduced"] > 0
