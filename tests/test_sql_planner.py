"""SQL -> logical-plan planner tests, driven by the 22 TPC-H queries.

The reference pins its planner behavior with TPC-H golden plans
(ballista/rust/scheduler/src/planner.rs:301-561); here the first gate is
that every TPC-H query parses and plans into a typed logical plan whose
output schema is consistent.
"""

import pathlib

import pytest

from ballista_tpu.datatypes import DataType
from ballista_tpu.expr import logical as L
from ballista_tpu.plan.logical import (
    Aggregate,
    Filter,
    Join,
    JoinType,
    Limit,
    Projection,
    Sort,
    TableScan,
)
from ballista_tpu.sql.parser import parse_sql
from ballista_tpu.sql.planner import DictCatalog, SqlPlanner
from ballista_tpu.tpch import all_schemas

QUERIES = pathlib.Path(__file__).resolve().parent.parent / "benchmarks" / "queries"


@pytest.fixture(scope="module")
def planner():
    return SqlPlanner(DictCatalog(all_schemas()))


def _plan(planner, name: str):
    sql = (QUERIES / f"{name}.sql").read_text()
    return planner.plan(parse_sql(sql))


@pytest.mark.parametrize("q", [f"q{i}" for i in range(1, 23)])
def test_tpch_query_plans(planner, q):
    plan = _plan(planner, q)
    schema = plan.schema()
    assert len(schema) > 0
    # every field must have a concrete type
    for f in schema:
        assert isinstance(f.dtype, DataType)


def test_q1_plan_shape(planner):
    plan = _plan(planner, "q1")
    # Sort <- Projection <- Aggregate <- Filter <- TableScan
    assert isinstance(plan, Sort)
    proj = plan.input
    assert isinstance(proj, Projection)
    agg = proj.input
    assert isinstance(agg, Aggregate)
    assert len(agg.group_exprs) == 2
    # q1 has 7 distinct aggregate computations (sum x4, avg x3 share args
    # with sums only partially) + count(*)
    assert len(agg.agg_exprs) >= 5
    filt = agg.input
    assert isinstance(filt, Filter)
    scan = filt.input
    assert isinstance(scan, TableScan) and scan.table_name == "lineitem"
    out = plan.schema()
    assert out.names[:2] == ["l_returnflag", "l_linestatus"]
    assert out.names[2] == "sum_qty"
    assert out.field("count_order").dtype == DataType.INT64
    assert out.field("avg_disc").dtype == DataType.FLOAT64


def test_q3_join_keys(planner):
    plan = _plan(planner, "q3")
    assert isinstance(plan, Limit) and plan.fetch == 10
    joins = []

    def walk(p):
        if isinstance(p, Join):
            joins.append(p)
        for c in p.children():
            walk(c)

    walk(plan)
    # customer x orders and orders x lineitem cross joins must have been
    # converted to equi-joins by predicate pushdown later; at logical-plan
    # time q3 uses comma joins so they stay CrossJoin until the optimizer.
    # (This test just pins current shape.)
    assert plan.schema().names[1] == "revenue"


def test_q18_semi_join(planner):
    plan = _plan(planner, "q18")
    semis = []

    def walk(p):
        if isinstance(p, Join) and p.join_type == JoinType.SEMI:
            semis.append(p)
        for c in p.children():
            walk(c)

    walk(plan)
    assert len(semis) == 1
    assert len(semis[0].on) == 1


def test_q16_not_in_and_count_distinct(planner):
    plan = _plan(planner, "q16")
    antis = []
    aggs = []

    def walk(p):
        if isinstance(p, Join) and p.join_type == JoinType.ANTI:
            antis.append(p)
        if isinstance(p, Aggregate):
            aggs.append(p)
        for c in p.children():
            walk(c)

    walk(plan)
    assert len(antis) == 1
    # count(distinct) lowers to two stacked aggregates
    assert len(aggs) == 2
    inner, outer = aggs[-1], aggs[0]
    assert len(inner.agg_exprs) == 0  # dedup level
    assert len(outer.agg_exprs) == 1


def test_q17_correlated_scalar(planner):
    plan = _plan(planner, "q17")
    inner_joins = []

    def walk(p):
        if isinstance(p, Join) and p.join_type == JoinType.INNER:
            inner_joins.append(p)
        for c in p.children():
            walk(c)

    walk(plan)
    # correlated avg subquery becomes an INNER join on l_partkey=p_partkey
    assert any("__sq" in str(j.on) for j in inner_joins)
    # the aggregate the decorrelation introduced is marked, and no other
    # (the outer SUM is not): through the optimizer and the logical serde
    from ballista_tpu.plan.optimizer import optimize
    from ballista_tpu.serde import logical_from_proto, logical_to_proto

    def aggregates(p):
        out = [p] if isinstance(p, Aggregate) else []
        for c in p.children():
            out += aggregates(c)
        return out

    for p in (plan, optimize(plan),
              logical_from_proto(logical_to_proto(optimize(plan)))):
        marks = {(tuple(g.name() for g in a.group_exprs), a.subquery)
                 for a in aggregates(p)}
        assert marks == {((), False), (("l_partkey",), True)}, marks
        assert "Aggregate: groupBy=[l_partkey], aggr=[AVG(l_quantity)], " \
               "subquery" in p.display()


def test_q17_subquery_mark_survives_serde_to_the_executor():
    """Both halves of the decorrelating aggregate carry the mark into the
    physical plan, and through the codec that ships a stage to an executor;
    q1's aggregate, which decorrelates nothing, carries none, nor q22's
    uncorrelated aggregate subquery."""
    from ballista_tpu.distributed_plan import DistributedPlanner
    from ballista_tpu.exec.aggregate import HashAggregateExec
    from ballista_tpu.exec.context import TpuContext
    from ballista_tpu.exec.planner import PhysicalPlanner
    from ballista_tpu.plan.optimizer import optimize
    from ballista_tpu.serde import BallistaCodec
    from ballista_tpu.tpch import gen_all

    ctx = TpuContext()
    for name, table in gen_all(scale=0.001).items():
        ctx.register_table(name, table)
    codec = BallistaCodec(ctx)

    def shipped_aggregates(q):
        logical = optimize(ctx.sql_to_logical((QUERIES / f"{q}.sql").read_text()))
        phys = PhysicalPlanner(ctx, 2, config=ctx.config,
                               distributed=True).plan(logical)
        out = []
        for stage in DistributedPlanner().plan_query_stages(f"job-{q}", phys):
            node = codec.physical_to_proto(stage.plan)
            wire = type(node).FromString(node.SerializeToString())

            def walk(p):
                if isinstance(p, HashAggregateExec):
                    out.append((p.mode, tuple(p.spec.group_names), p.subquery))
                for c in p.children():
                    walk(c)

            walk(codec.physical_from_proto(wire))
        return out

    q17 = shipped_aggregates("q17")
    assert sorted(m for m in q17 if m[2]) == [
        ("final", ("l_partkey",), True), ("partial", ("l_partkey",), True)]
    assert all(not m[2] for m in q17 if m[1] != ("l_partkey",))
    # the outer SUM's two halves and the two of the reduction's dedup of
    # the outer query's parts, unmarked
    assert sorted(m for m in q17 if not m[2]) == [
        ("final", (), False), ("final", ("p_partkey",), False),
        ("partial", (), False), ("partial", ("p_partkey",), False)]
    assert not any(m[2] for m in shipped_aggregates("q1"))
    # q22's AVG(c_acctbal) subquery is uncorrelated: an ordinary SELECT,
    # not grouped by any key of the outer query, so not marked
    assert not any(m[2] for m in shipped_aggregates("q22"))


# -- the semi-join reduction of a decorrelated subquery's aggregate ----------

# query -> (the marked aggregate's group key the reduction joins on, the
# table of the outer query's domain, the columns its filters read)
REDUCED = {
    "q17": ("l_partkey", "part", {"p_brand", "p_container"}),
    "q20": ("l_partkey", "part", {"p_name"}),
    "q2": ("ps_partkey", "part", {"p_size", "p_type"}),
}


def _nodes(plan, kind):
    out = [plan] if isinstance(plan, kind) else []
    for c in plan.children():
        out += _nodes(c, kind)
    return out


@pytest.fixture(scope="module")
def tpch_ctx():
    from ballista_tpu.exec.context import TpuContext
    from ballista_tpu.tpch import gen_all

    ctx = TpuContext()
    for name, table in gen_all(scale=0.001).items():
        ctx.register_table(name, table)
    return ctx


def _physical(ctx, logical):
    from ballista_tpu.exec.planner import PhysicalPlanner

    return PhysicalPlanner(ctx, 2, config=ctx.config,
                           distributed=True).plan(logical)


@pytest.mark.parametrize("q", REDUCED)
def test_the_reduction_goes_below_the_marked_aggregate(planner, q):
    """The marked aggregate's input becomes a semi join, on one of its
    group keys, to the outer query's filtered ``part``: the subquery's own
    filters stay below it, and the mark survives the logical serde."""
    from ballista_tpu.plan.optimizer import optimize
    from ballista_tpu.serde import logical_from_proto, logical_to_proto

    key, table, read = REDUCED[q]
    plan = optimize(_plan(planner, q))
    for p in (plan, logical_from_proto(logical_to_proto(plan))):
        (agg,) = [a for a in _nodes(p, Aggregate) if a.subquery]
        semi = agg.input
        assert isinstance(semi, Join) and semi.reduction
        assert semi.join_type == JoinType.SEMI and semi.filter is None
        ((a, b),) = semi.on
        assert a.name() == key
        assert key in [g.name() for g in agg.group_exprs]
        (scan,) = _nodes(semi.right, TableScan)
        assert scan.table_name == table
        assert {c for f in scan.filters for c in L.find_columns(f)} == read
        assert b.name() in scan.schema().names
        # the subquery's own scans are all under the semi join's left
        assert not any(s.table_name == "part" for s in _nodes(semi.left,
                                                              TableScan))
        assert "Join(semi, reduction): on=" in p.display()
        # one reduction, and no other join is marked
        assert sum(j.reduction for j in _nodes(p, Join)) == 1


SOURCES = {
    # the outer key comes from a table no predicate touches
    "unfiltered": """select sum(l_extendedprice) from lineitem, part
        where p_partkey = l_partkey and l_quantity < (
            select 0.2 * avg(l_quantity) from lineitem
            where l_partkey = p_partkey)""",
    # an uncorrelated scalar subquery: a cross join against one row
    "uncorrelated": """select l_partkey from lineitem, part
        where l_partkey = p_partkey and p_size = 15 and l_quantity > (
            select avg(l_quantity) from lineitem)""",
}


@pytest.mark.parametrize("source", [*SOURCES, "q11", "q22", "q4", "q21"])
def test_no_reduction_without_a_filtered_domain_or_a_mark(planner, source):
    from ballista_tpu.plan.optimizer import optimize

    sql = SOURCES.get(source) or (QUERIES / f"{source}.sql").read_text()
    plan = optimize(planner.plan(parse_sql(sql)))
    assert not any(j.reduction for j in _nodes(plan, Join))
    assert "reduction" not in plan.display()


H2O = ["g1q2", "g1q3", "g1q5", "g1q7"]


@pytest.fixture(scope="module")
def h2o_ctx():
    import pyarrow as pa

    from ballista_tpu.exec.context import TpuContext

    ids = [f"id{i:03d}" for i in (1, 2, 3, 1, 2, 3, 1, 2)]
    ctx = TpuContext()
    ctx.register_table("x", pa.table({
        "id1": ids, "id2": ids[::-1],
        "id3": [f"id{i:010d}" for i in range(8)],
        **{c: pa.array([1, 2, 3, 4, 1, 2, 3, 4], pa.int64())
           for c in ("id4", "id5", "id6", "v1", "v2")},
        "v3": pa.array([0.5 * i for i in range(8)]),
    }))
    return ctx


@pytest.mark.parametrize("q", ["q1", "q3", "q6", "q13", "q4", *H2O])
def test_plans_without_a_marked_aggregate_are_unchanged(
        request, monkeypatch, q):
    """The optimized and the physical plan are the same with the rule and
    without it."""
    from ballista_tpu.plan import optimizer

    h2o = q in H2O
    ctx = request.getfixturevalue("h2o_ctx" if h2o else "tpch_ctx")
    folder = (QUERIES.parent.parent / "perf" / "queries") if h2o else QUERIES
    logical = ctx.sql_to_logical((folder / f"{q}.sql").read_text())

    def displays():
        plan = optimizer.optimize(logical)
        return plan.display(), _physical(ctx, plan).display()

    with_rule = displays()
    monkeypatch.setattr(optimizer, "reduce_subquery_aggregates",
                        lambda plan: plan)
    assert displays() == with_rule
    assert "reduction" not in "".join(with_rule)


def test_the_reduction_is_planned_in_collect_mode(tpch_ctx):
    """With ``ballista.repartition.joins`` on (the default) every other join
    of q17 hash-exchanges both sides; the reduction builds the deduplicated
    domain once and probes inside the subquery's scan stage, and the codec
    that ships a stage carries its mark."""
    from ballista_tpu.distributed_plan import DistributedPlanner
    from ballista_tpu.exec.aggregate import HashAggregateExec
    from ballista_tpu.exec.joins import HashJoinExec
    from ballista_tpu.plan.optimizer import optimize
    from ballista_tpu.serde import BallistaCodec

    assert tpch_ctx.config.repartition_joins()
    logical = optimize(tpch_ctx.sql_to_logical(
        (QUERIES / "q17.sql").read_text()))
    phys = _physical(tpch_ctx, logical)
    joins = _nodes(phys, HashJoinExec)
    (red,) = [j for j in joins if j.reduction]
    assert (red.join_type, red.partition_mode) == (JoinType.SEMI, "collect")
    assert all(j.partition_mode == "partitioned"
               for j in joins if not j.reduction)
    assert "HashJoinExec(semi, collect, reduction): on=[l_partkey = " \
           "p_partkey]" in phys.display()
    codec = BallistaCodec(tpch_ctx)
    shipped = []
    for stage in DistributedPlanner().plan_query_stages("job-q17", phys):
        node = codec.physical_to_proto(stage.plan)
        plan = codec.physical_from_proto(type(node).FromString(
            node.SerializeToString()))
        partials = [a for a in _nodes(plan, HashAggregateExec)
                    if a.subquery and a.mode == "partial"]
        shipped += [(j.partition_mode, [j in _nodes(a, HashJoinExec)
                                         for a in partials])
                    for j in _nodes(plan, HashJoinExec) if j.reduction]
    # in one stage, under the marked partial aggregate: no probe row is
    # exchanged before it
    assert shipped == [("collect", [True])]


def test_q4_exists_to_semi(planner):
    plan = _plan(planner, "q4")
    semis = []

    def walk(p):
        if isinstance(p, Join) and p.join_type == JoinType.SEMI:
            semis.append(p)
        for c in p.children():
            walk(c)

    walk(plan)
    assert len(semis) == 1


def test_q21_exists_and_not_exists(planner):
    plan = _plan(planner, "q21")
    kinds = []

    def walk(p):
        if isinstance(p, Join) and p.join_type in (JoinType.SEMI, JoinType.ANTI):
            kinds.append((p.join_type, p.filter is not None))
        for c in p.children():
            walk(c)

    walk(plan)
    assert (JoinType.SEMI, True) in kinds  # exists with <> residual
    assert (JoinType.ANTI, True) in kinds  # not exists with residual


def test_q13_left_join(planner):
    plan = _plan(planner, "q13")
    lefts = []

    def walk(p):
        if isinstance(p, Join) and p.join_type == JoinType.LEFT:
            lefts.append(p)
        for c in p.children():
            walk(c)

    walk(plan)
    assert len(lefts) == 1
    assert lefts[0].filter is not None  # the NOT LIKE residual


def test_alias_group_by(planner):
    # q7-style: group by an alias defined in a derived table projection
    plan = _plan(planner, "q7")
    assert plan.schema().names == ["supp_nation", "cust_nation", "l_year", "revenue"]


def test_select_one_no_from(planner):
    plan = planner.plan(parse_sql("select 1"))
    assert len(plan.schema()) == 1


def test_order_by_alias_and_position(planner):
    plan = planner.plan(
        parse_sql("select l_orderkey as k, l_quantity from lineitem order by 1 desc")
    )
    assert isinstance(plan, Sort)
    assert isinstance(plan.sort_exprs[0].expr, L.Column)
    assert plan.sort_exprs[0].expr.cname == "k"
