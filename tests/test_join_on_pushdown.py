"""ON-clause conjuncts that read one side of a join only
(``plan/optimizer.py _push_on_conjuncts``): pushed below the join into a side
the join does not preserve, left in the ON clause for a side it does; and the
string column that only a scan's filters read, projected away behind them.
The answers are held to a row-by-row evaluation of SQL's own definition."""

import itertools

import pyarrow as pa
import pytest

from ballista_tpu.exec.context import TpuContext
from ballista_tpu.plan.logical import Filter, Join, Projection, TableScan
from ballista_tpu.plan.optimizer import optimize

L_ROWS = [(1, "red"), (2, "blue"), (3, "red"), (3, "green"), (5, None),
          (None, "red"), (7, "blue")]
R_ROWS = [(2, "special requests"), (3, "plain"), (3, "special offer requests"),
          (3, None), (4, "plain"), (7, "special requests"), (None, "plain")]


@pytest.fixture(scope="module")
def ctx():
    c = TpuContext()
    c.register_table("l", pa.table({
        "k": pa.array([k for k, _ in L_ROWS], type=pa.int64()),
        "a": pa.array([a for _, a in L_ROWS]),
    }))
    c.register_table("r", pa.table({
        "j": pa.array([j for j, _ in R_ROWS], type=pa.int64()),
        "b": pa.array([b for _, b in R_ROWS]),
    }))
    return c


def joins(plan):
    found = [plan] if isinstance(plan, Join) else []
    return found + [j for c in plan.children() for j in joins(c)]


def scan_of(plan, table):
    if isinstance(plan, TableScan):
        return plan if plan.table_name == table else None
    return next((s for c in plan.children()
                 if (s := scan_of(c, table)) is not None), None)


def plan_of(ctx, sql):
    return optimize(ctx.sql_to_logical(sql))


def like(s, words):
    """'%w1%w2%' over a nullable string: None where s is NULL."""
    if s is None:
        return None
    at = 0
    for w in words:
        at = s.find(w, at)
        if at < 0:
            return False
        at += len(w)
    return True


def rows(ctx, sql):
    t = ctx.sql(sql).collect()
    return sorted(
        (tuple(r.values()) for r in t.to_pylist()),
        key=lambda r: tuple((v is None, v) for v in r),
    )


def srt(rs):
    return sorted(rs, key=lambda r: tuple((v is None, v) for v in r))


# side conjunct -> SQL text and its value for a (left, right) pair of rows;
# None is SQL's unknown
RIGHT_ONLY = ("b NOT LIKE '%special%requests%'",
              lambda lr, rr: None if (m := like(rr[1], ["special", "requests"]))
              is None else not m)
LEFT_ONLY = ("a = 'red'", lambda lr, rr: None if lr[1] is None
             else lr[1] == "red")


def matches(lr, cond):
    return [rr for rr in R_ROWS
            if lr[0] is not None and lr[0] == rr[0] and cond(lr, rr) is True]


@pytest.mark.parametrize("side", ["right_only", "left_only"])
def test_left_join_keeps_every_left_row(ctx, side):
    text, cond = RIGHT_ONLY if side == "right_only" else LEFT_ONLY
    sql = f"SELECT k, a, j, b FROM l LEFT JOIN r ON k = j AND {text}"
    join, = joins(plan_of(ctx, sql))
    if side == "right_only":
        # below the join, into the scan of the side that is not preserved
        assert join.filter is None
        assert [f.name() for f in scan_of(join.right, "r").filters] == [
            "b NOT LIKE '%special%requests%'"]
        assert scan_of(join.left, "l").filters == ()
    else:
        # a preserved row that fails the conjunct still comes out, unmatched
        assert join.filter is not None and "a" in join.filter.name()
        assert scan_of(join.left, "l").filters == ()
    want = []
    for lr in L_ROWS:
        found = matches(lr, cond)
        want += [lr + rr for rr in found] or [lr + (None, None)]
    assert rows(ctx, sql) == srt(want)
    assert len(want) >= len(L_ROWS)


@pytest.mark.parametrize("side", ["right_only", "left_only"])
def test_inner_join_pushes_either_side(ctx, side):
    text, cond = RIGHT_ONLY if side == "right_only" else LEFT_ONLY
    sql = f"SELECT k, a, j, b FROM l JOIN r ON k = j AND {text}"
    join, = joins(plan_of(ctx, sql))
    assert join.filter is None
    table, child = (("r", join.right) if side == "right_only"
                    else ("l", join.left))
    assert len(scan_of(child, table).filters) == 1
    want = [lr + rr for lr in L_ROWS for rr in matches(lr, cond)]
    assert rows(ctx, sql) == srt(want) and want


@pytest.mark.parametrize("negated,side", itertools.product(
    [False, True], ["right_only", "left_only"]))
def test_exists_and_not_exists_with_a_one_sided_conjunct(ctx, negated, side):
    """A semi join may filter either side first; an anti join only the side
    it searches: a left row that fails its own conjunct has no match and so
    comes out."""
    text, cond = RIGHT_ONLY if side == "right_only" else LEFT_ONLY
    sql = (f"SELECT k, a FROM l WHERE {'NOT ' if negated else ''}EXISTS "
           f"(SELECT * FROM r WHERE j = k AND {text})")
    want = [lr for lr in L_ROWS if bool(matches(lr, cond)) != negated]
    assert rows(ctx, sql) == srt(want)
    assert 0 < len(want) < len(L_ROWS)
    join, = joins(plan_of(ctx, sql))
    if negated and side == "left_only":
        assert join.filter is not None
    else:
        assert join.filter is None


def test_a_conjunct_over_both_sides_stays_in_the_on_clause(ctx):
    sql = "SELECT k, a, j, b FROM l LEFT JOIN r ON k = j AND a < b"
    join, = joins(plan_of(ctx, sql))
    assert join.filter is not None
    want = []
    for lr in L_ROWS:
        found = [rr for rr in R_ROWS
                 if lr[0] is not None and lr[0] == rr[0]
                 and lr[1] is not None and rr[1] is not None
                 and lr[1] < rr[1]]
        want += [lr + rr for rr in found] or [lr + (None, None)]
    assert rows(ctx, sql) == srt(want)


def test_a_string_column_only_the_filter_reads_goes_at_the_scan(ctx):
    """``b`` is read by the scan's filter alone: behind the filter a
    projection drops it, so its dictionary goes no further. A numeric
    column in the same place stays (it costs nothing to carry), and so does
    a string the plan reads above."""
    sql = ("SELECT k, count(j) AS n FROM l LEFT JOIN r ON k = j "
           "AND b NOT LIKE '%special%requests%' GROUP BY k")
    join, = joins(plan_of(ctx, sql))
    assert isinstance(join.right, Projection)
    assert [e.name() for e in join.right.exprs] == ["j"]
    scan = join.right.input
    assert isinstance(scan, TableScan) and scan.schema().names == ["j", "b"]
    assert join.right.schema().names == ["j"]
    want = {}
    for lr in L_ROWS:
        n = len(matches(lr, RIGHT_ONLY[1]))
        want[lr[0]] = want.get(lr[0], 0) + n
    assert rows(ctx, sql) == srt(list(want.items()))
    # read above the scan: no projection, the column stays
    kept = plan_of(ctx, "SELECT b FROM r WHERE b LIKE '%plain%'")
    assert not any(isinstance(p, Projection) and isinstance(p.input, TableScan)
                   and len(p.exprs) < len(p.input.projection or ())
                   for p in walk(kept))
    # a number that only the filter reads is left where it is
    numeric = plan_of(ctx, "SELECT b FROM r WHERE j > 2")
    scan = scan_of(numeric, "r")
    assert set(scan.projection or scan.source_schema.names) == {"j", "b"}
    assert not any(isinstance(p, Filter) for p in walk(numeric))


def walk(plan):
    return [plan] + [p for c in plan.children() for p in walk(c)]
