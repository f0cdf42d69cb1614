"""Static plan verification (ballista_tpu/analysis/verifier.py).

Acceptance contract (ISSUE 2): the verifier accepts every TPC-H q1-q22
plan unchanged, rejects hand-mutated plans (dropped column, mismatched
shuffle partition counts, illegal dtype, schema drift at stage
boundaries) with precise diagnostics, and gates every submission path by
default (``ballista.tpu.verify_plans``)."""

import dataclasses
import pathlib

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu.analysis import (
    sql_span,
    verify_logical,
    verify_physical,
    verify_stages,
)
from ballista_tpu.config import BallistaConfig
from ballista_tpu.datatypes import DataType, Schema
from ballista_tpu.distributed_plan import (
    DistributedPlanner,
    find_unresolved_shuffles,
)
from ballista_tpu.errors import PlanVerificationError
from ballista_tpu.exec.context import DataFrame, TpuContext
from ballista_tpu.exec.planner import PhysicalPlanner
from ballista_tpu.expr import logical as L
from ballista_tpu.plan import logical as P
from ballista_tpu.plan.optimizer import optimize

QDIR = pathlib.Path(__file__).resolve().parent.parent / "benchmarks" / "queries"


@pytest.fixture(scope="module")
def ctx() -> TpuContext:
    c = TpuContext()
    r = np.random.default_rng(3)
    n = 100
    c.register_table(
        "t",
        pa.table(
            {
                "g": pa.array(r.integers(0, 5, n).astype(np.int64)),
                "v": pa.array(r.uniform(0, 10, n)),
                "s": pa.array([["a", "b", None][i % 3] for i in range(n)]),
            }
        ),
    )
    c.register_table(
        "d",
        pa.table(
            {
                "k": pa.array(np.arange(5, dtype=np.int64)),
                "w": pa.array(r.uniform(0, 1, 5)),
            }
        ),
    )
    return c


@pytest.fixture(scope="module")
def tpch_ctx() -> TpuContext:
    from ballista_tpu.tpch import gen_all

    c = TpuContext()
    for name, tab in gen_all(scale=0.001).items():
        c.register_table(name, tab)
    return c


# ------------------------------------------------------ TPC-H acceptance ---


def test_verifier_accepts_all_tpch_plans(tpch_ctx):
    """Every TPC-H q1-q22 plan passes both verifier tiers unchanged."""
    for i in range(1, 23):
        sql = (QDIR / f"q{i}.sql").read_text()
        optimized = optimize(tpch_ctx.sql_to_logical(sql))
        rl = verify_logical(optimized, sql=sql)
        assert rl.nodes > 0 and rl.checks > rl.nodes, f"q{i}: thin report"
        phys = tpch_ctx.create_physical_plan(optimized, sql=sql)
        rp = verify_physical(phys, sql=sql)
        assert rp.nodes > 0, f"q{i}"


def test_verifier_accepts_distributed_tpch_stages(tpch_ctx):
    """Stage DAGs the distributed planner cuts (repartitioned joins and
    aggregates included) are well-formed for a representative query mix."""
    for i in (1, 3, 5, 18):
        sql = (QDIR / f"q{i}.sql").read_text()
        optimized = optimize(tpch_ctx.sql_to_logical(sql))
        phys = PhysicalPlanner(
            tpch_ctx, 2, config=tpch_ctx.config, distributed=True
        ).plan(optimized)
        stages = DistributedPlanner().plan_query_stages(f"job-q{i}", phys)
        rep = verify_stages(stages, sql=sql)
        assert rep.nodes > 0 and any("stages" in d for d in rep.detail)


# ----------------------------------------------------------- mutations ----
# >= 3 distinct defect classes that previously surfaced only at executor
# runtime must be caught statically with precise diagnostics.


def test_mutation_dropped_column(ctx):
    """Defect class 1: a column dropped upstream of a consumer."""
    opt = optimize(ctx.sql_to_logical("select g, sum(v) sv from t group by g"))

    def drop(node):
        if isinstance(node, P.TableScan):
            return dataclasses.replace(node, projection=("g",))
        return node.with_children([drop(c) for c in node.children()])

    with pytest.raises(PlanVerificationError) as ei:
        verify_logical(drop(opt))
    assert "'v'" in str(ei.value)
    assert ei.value.path, "diagnostic must carry the operator path"


def test_mutation_unresolved_column_has_span(ctx):
    sql = "select g, nope from t"
    scan = P.TableScan("t", ctx.schema_of("t"))
    bad = P.Projection(scan, (L.col("g"), L.col("nope")))
    with pytest.raises(PlanVerificationError) as ei:
        verify_logical(bad, sql=sql)
    e = ei.value
    assert "nope" in str(e)
    assert e.span == (1, 11), e.span
    assert any("Projection" in p for p in e.path)


def test_mutation_illegal_dtype_sum_over_string(ctx):
    """Defect class 2: TPU dtype illegality. SUM over a dictionary-coded
    STRING column would silently sum dictionary codes at runtime."""
    bad = P.Aggregate(
        P.TableScan("t", ctx.schema_of("t")),
        (L.col("g"),),
        (L.AggregateExpr(L.AggFunc.SUM, L.col("s")),),
    )
    with pytest.raises(PlanVerificationError) as ei:
        verify_logical(bad)
    assert "SUM over non-numeric dtype string" in str(ei.value)
    assert any("Aggregate" in p for p in ei.value.path)


def test_mutation_join_key_dtype_mismatch(ctx):
    bad = P.Join(
        P.TableScan("t", ctx.schema_of("t")),
        P.TableScan("d", ctx.schema_of("d")),
        ((L.col("s"), L.col("w")),),
        P.JoinType.INNER,
    )
    with pytest.raises(PlanVerificationError) as ei:
        verify_logical(bad)
    assert "join key dtype mismatch" in str(ei.value)


def test_mutation_non_boolean_filter(ctx):
    bad = P.Filter(P.TableScan("t", ctx.schema_of("t")), L.col("v"))
    with pytest.raises(PlanVerificationError) as ei:
        verify_logical(bad)
    assert "not boolean" in str(ei.value)


def test_mutation_shuffle_partition_count(tpch_ctx):
    """Defect class 3: reader/writer disagreement on shuffle partition
    count — previously an executor-side missing-bucket failure."""
    sql = (QDIR / "q3.sql").read_text()
    optimized = optimize(tpch_ctx.sql_to_logical(sql))
    phys = PhysicalPlanner(
        tpch_ctx, 2, config=tpch_ctx.config, distributed=True
    ).plan(optimized)
    stages = DistributedPlanner().plan_query_stages("job-mut", phys)
    verify_stages(stages)  # sane before mutation
    mutated = False
    for stage in stages:
        for u in find_unresolved_shuffles(stage.plan):
            u.output_partition_count += 1
            mutated = True
            break
        if mutated:
            break
    assert mutated, "test needs a multi-stage plan"
    msg = None
    with pytest.raises(PlanVerificationError) as ei:
        verify_stages(stages)
    msg = str(ei.value)
    # the mutation is caught either at the stage boundary (reader/writer
    # count disagreement) or — when the mutated placeholder feeds a
    # partitioned join — by the join's own bucket-count check; both are
    # precise diagnoses of the same defect class
    assert (
        "partition-count mismatch" in msg
        or "disagree on partition count" in msg
    ), msg
    assert any(p.startswith("stage ") for p in ei.value.path)


def test_mutation_stage_schema_drift(tpch_ctx):
    """Defect class 4: placeholder schema drifts from the writer stage
    (the serde-gap shape of PR 1's MeshSort fetch bug)."""
    sql = (QDIR / "q3.sql").read_text()
    optimized = optimize(tpch_ctx.sql_to_logical(sql))
    phys = PhysicalPlanner(
        tpch_ctx, 2, config=tpch_ctx.config, distributed=True
    ).plan(optimized)
    stages = DistributedPlanner().plan_query_stages("job-drift", phys)
    mutated = False
    for stage in stages:
        for u in find_unresolved_shuffles(stage.plan):
            if len(u._schema.fields) < 2:
                # customer's exchange carries its key alone (the segment
                # goes at the scan): without it the join's key check
                # would speak first
                continue
            u._schema = Schema(list(u._schema.fields)[:-1])
            mutated = True
            break
        if mutated:
            break
    assert mutated
    with pytest.raises(PlanVerificationError) as ei:
        verify_stages(stages)
    assert "schema mismatch" in str(ei.value)


def test_mutation_partitioned_join_bucket_mismatch(ctx):
    from ballista_tpu.exec.joins import HashJoinExec
    from ballista_tpu.exec.repartition import HashRepartitionExec

    left = HashRepartitionExec(ctx.scan("t", None, 2), [L.col("g")], 4)
    right = HashRepartitionExec(ctx.scan("d", None, 2), [L.col("k")], 3)
    bad = HashJoinExec(
        left, right, [(L.col("g"), L.col("k"))], P.JoinType.INNER,
        partition_mode="partitioned",
    )
    with pytest.raises(PlanVerificationError) as ei:
        verify_physical(bad)
    assert "disagree on partition count" in str(ei.value)


# ----------------------------------------------------- submission gates ---


def test_collect_gated_by_default(ctx):
    """DataFrame.collect routes through the verifier by default; turning
    the config off reaches execution (and would silently produce wrong
    results for this plan — the motivating defect class)."""
    assert BallistaConfig().verify_plans() is True
    bad = P.Aggregate(
        P.TableScan("t", ctx.schema_of("t")),
        (L.col("g"),),
        (L.AggregateExpr(L.AggFunc.SUM, L.col("s")),),
    )
    with pytest.raises(PlanVerificationError):
        DataFrame(ctx, bad).collect()

    off = TpuContext(
        BallistaConfig({"ballista.tpu.verify_plans": "false"})
    )
    off.register_table("t", pa.table({"g": [1, 2], "s": ["a", "b"]}))
    bad2 = P.Aggregate(
        P.TableScan("t", off.schema_of("t")),
        (L.col("g"),),
        (L.AggregateExpr(L.AggFunc.SUM, L.col("s")),),
    )
    try:
        DataFrame(off, bad2).collect()  # runs: sums dictionary codes
    except PlanVerificationError:  # pragma: no cover
        pytest.fail("verify off must not verify")
    except Exception:
        pass  # any runtime failure is fine — the point is no static gate


def test_explain_verify_reports(ctx):
    tab = ctx.sql(
        "explain verify select g, sum(v) sv from t group by g order by g"
    ).collect()
    rows = dict(
        zip(tab.column("plan_type").to_pylist(), tab.column("plan").to_pylist())
    )
    assert "verification" in rows
    assert "logical plan: OK" in rows["verification"]
    assert "physical plan: OK" in rows["verification"]
    # plain EXPLAIN is unchanged
    tab2 = ctx.sql("explain select g from t").collect()
    assert "verification" not in tab2.column("plan_type").to_pylist()


def test_sql_span_locator():
    sql = "select g,\n       nope\nfrom t"
    assert sql_span(sql, "nope") == (2, 8)
    assert sql_span(sql, "t.g") == (1, 8)  # falls back to the base name
    assert sql_span(sql, "absent") is None
    assert sql_span(None, "g") is None


def test_standalone_submission_gates():
    """Both cluster gates: the client verifies before serializing, and the
    scheduler independently rejects bad submissions (typed failure)."""
    from ballista_tpu.client.context import BallistaContext

    dctx = BallistaContext.standalone()
    try:
        dctx.register_table(
            "t", pa.table({"g": [1, 2, 3], "s": ["a", "b", "c"]})
        )
        frame = dctx.sql("select g from t")
        bad = P.Aggregate(
            P.TableScan("t", dctx.schema_of("t")),
            (L.col("g"),),
            (L.AggregateExpr(L.AggFunc.SUM, L.col("s")),),
        )
        # client-side gate (RemoteDataFrame.collect -> collect_logical)
        frame.logical = bad
        with pytest.raises(PlanVerificationError):
            frame.collect()
        # scheduler-side gate (direct submission bypassing the client)
        sched = dctx._standalone_cluster.scheduler
        with pytest.raises(PlanVerificationError):
            sched.submit_logical(bad, dctx.session_id)
        # sanity: a good query still round-trips the full cluster
        out = dctx.sql("select g from t order by g").collect()
        assert out.column("g").to_pylist() == [1, 2, 3]
    finally:
        dctx.close()
