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
    assert len(q17) == 4  # the outer SUM's two halves, unmarked
    assert not any(m[2] for m in shipped_aggregates("q1"))
    # q22's AVG(c_acctbal) subquery is uncorrelated: an ordinary SELECT,
    # not grouped by any key of the outer query, so not marked
    assert not any(m[2] for m in shipped_aggregates("q22"))


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
