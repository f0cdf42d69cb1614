"""TPC-H's outer-join, EXISTS and NOT IN templates (q13, q4, q16: the
templates of the cell ``tpch-sf1-subq-mem.subquery``) on the served path,
against each template's own plain reference, at a size the CPU runs.

SF 0.02 under a seed chosen so that two of the 3,000 customers have no order
(the generator draws ``o_custkey`` uniformly: q13's ``c_count = 0`` row, which
only a join that keeps its unmatched rows returns), one of the 200 suppliers
carries the complaints text (q16's anti join removes rows) and every quarter
has late orders (q4's semi join keeps some and drops some). Each template at
clause 2.4's validation parameters and at two draws, and once more on two
executors, where the stages of an outer, a semi and an anti join are cut
across a shuffle that crosses executors."""

import pathlib
import sys

import numpy as np
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

SF = 0.02
SEED = 3_600_000_017
TEMPLATES = ("q13", "q4", "q16")
NEEDED = ("customer", "orders", "lineitem", "part", "partsupp", "supplier")
# the join of each template that preserves a side, and whether it emits
# rows that found no match
JOIN = {"q13": ("LEFT", True), "q4": ("SEMI", False), "q16": ("ANTI", True)}


@pytest.fixture(scope="module")
def templates():
    return traffic.load_templates(TEMPLATES)


@pytest.fixture(scope="module")
def data(templates):
    tables = {n: t for n, t in datagen.gen_all(SF, SEED).items()
              if n in NEEDED}
    with_orders = np.unique(np.asarray(tables["orders"].column("o_custkey")))
    assert tables["customer"].num_rows - len(with_orders) == 2
    return tables, verify.frames(tables, templates)


def parameters(templates) -> dict:
    """(template, which) -> parameters: clause 2.4's validation values and
    two draws of each template's own ``draw``."""
    out = {}
    for i, (name, mod) in enumerate(templates.items()):
        rng = np.random.default_rng([36, i])
        out[name, "validation"] = mod.VALIDATION
        out[name, "draw0"] = mod.draw(rng)
        out[name, "draw1"] = mod.draw(rng)
    return out


@pytest.fixture(scope="module")
def one_executor(data):
    from ballista_tpu.client.context import BallistaContext

    ctx = BallistaContext.standalone(concurrent_tasks=4)
    for name, table in data[0].items():
        ctx.register_table(name, table)
    yield ctx
    ctx.close()


@pytest.fixture(scope="module")
def two_executors(data):
    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.config import BallistaConfig

    config = BallistaConfig({"ballista.shuffle.partitions": "2"})
    ctx = BallistaContext.standalone(config, concurrent_tasks=2,
                                     n_executors=2)
    for name, table in data[0].items():
        ctx.register_table(name, table)
    yield ctx
    ctx.close()


CASES = ([(t, which, "one_executor") for t in TEMPLATES
          for which in ("validation", "draw0", "draw1")]
         + [(t, "validation", "two_executors") for t in TEMPLATES])


@pytest.mark.parametrize("name,which,cluster", CASES)
def test_the_served_path_gives_the_reference_answer(
        request, templates, data, name, which, cluster):
    ctx = request.getfixturevalue(cluster)
    mod, p = templates[name], parameters(templates)[name, which]
    reference = mod.reference(data[1], p)
    before = metrics.snapshot()
    answer = ctx.sql(mod.SQL.format(**p)).collect()
    moved = {k: v - before[k] for k, v in metrics.snapshot().items()
             if k.startswith("join.")}
    verdict = verify.judge([(name, 0, answer)], {name: mod},
                           {(name, 0): reference}, 0)
    assert verdict["correct"], (verdict["numbers"], verdict["first_mismatch"])
    assert verdict["numbers"]["mismatched"] == {"value": 0, "limit": 0}
    assert mod.LIMITS == {}  # keys and counts: nothing is held to a limit
    assert len(reference) > 0
    if name == "q13":
        # the two customers without an order: found only if the outer join
        # emits its unmatched rows and count() skips their NULL order key
        # (or all of whose orders the pattern took: one more at a draw)
        zero = reference[reference.c_count == 0].custdist.tolist()
        assert len(zero) == 1 and zero[0] >= 2
        got = answer.to_pandas()
        assert got[got.c_count == 0].custdist.tolist() == zero
        assert got.custdist.sum() == data[0]["customer"].num_rows
    elif name == "q4":
        assert len(reference) == 5 and reference.order_count.min() > 0
    else:
        assert len(reference) > 100
        assert reference.supplier_cnt.is_monotonic_decreasing
    # the join that preserves a side ran, in as many tasks as its side has
    # partitions, over every live row of that side
    kind, emits_unmatched = JOIN[name]
    assert moved["join.noninner.tasks"] >= 1, (kind, moved)
    assert moved["join.noninner.probe_rows"] > 0
    assert (moved["join.noninner.unmatched_rows"] > 0) == emits_unmatched
    # every join kind's probe rows, of which those are a part; each join
    # built at least one probe table
    assert moved["join.probe_rows"] >= moved["join.noninner.probe_rows"]
    assert moved["join.builds"] >= 1 and moved["join.build_rows"] > 0
    if name == "q13":
        assert moved["join.noninner.probe_rows"] == 3_000
        # the two without an order, and whoever's orders are all special
        assert moved["join.noninner.unmatched_rows"] >= 2
