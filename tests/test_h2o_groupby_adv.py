"""h2oai db-benchmark's advanced group-by questions (the templates of the cell
``h2o-g1-1e7-adv-mem.advanced``) on the served path, against each template's
own plain reference, at a size the CPU runs.

g1q6 is an exact median and a sample deviation by two keys, g1q8 the rows
that ``row_number()`` numbers 1 and 2 in every partition of a third. On the
distributed tier both stand on a hash exchange by their keys
(``PhysicalPlanner._whole_groups``): two buckets under the default
``ballista.shuffle.partitions``, so two tasks run the operator and between
them sort every row once. The table of the edge cases is made here: groups of
one row (no deviation, one row of two), of an even and of an odd count, and
values that repeat inside a group, so that ``row_number`` is arbitrary among
rows that are equal in what is returned.
"""

import json
import pathlib
import sys

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
PERF = ROOT / "perf"
for p in (str(ROOT), str(PERF)):
    if p not in sys.path:
        sys.path.insert(0, p)

import control  # noqa: E402
import dataset  # noqa: E402
import traffic  # noqa: E402
import verify  # noqa: E402

from ballista_tpu.compilecache import metrics  # noqa: E402

ROWS = 200_000
SEED = 3_300_000_021
# template -> (tasks that run the operator, argsort passes each dispatches)
# of a query over the two hash buckets: validity, the keys, the value. g1q6's
# two keys ride as (value, is-null flag) pairs through ``split_percentiles``,
# since an Arrow table's columns are nullable.
CASES = {"g1q6": (2, 6), "g1q8": (2, 3)}


def holistic() -> dict:
    return {k: v for k, v in metrics.snapshot().items()
            if k.startswith("holistic.")}


@pytest.fixture(scope="module")
def templates():
    return traffic.load_templates(CASES)


def edge_table() -> pa.Table:
    """9,000 rows: ``id6`` 1..1200 drawn so that a hundred or so values come
    once, ``(id4, id5)`` over a 40 x 40 grid with combinations of one row,
    ``v3`` of one decimal so that it repeats inside a group."""
    rng = np.random.default_rng(SEED)
    n = 9_000
    id6 = np.concatenate([rng.integers(1, 1001, n - 200),
                          np.arange(1001, 1201)])
    rng.shuffle(id6)
    x = pa.table({
        "id4": pa.array(rng.integers(1, 41, n)),
        "id5": pa.array(rng.integers(1, 41, n)),
        "id6": pa.array(id6),
        "v3": pa.array(rng.integers(0, 1000, n) / 10.0),
    })
    sizes = x.to_pandas().groupby("id6").size()
    assert (sizes == 1).sum() >= 200 and (sizes % 2 == 0).any()
    assert ((sizes % 2 == 1) & (sizes > 1)).any()
    pairs = x.to_pandas().groupby(["id4", "id5"]).size()
    assert (pairs == 1).any() and (pairs > 4).any()
    return x


def aligned(frame: pd.DataFrame) -> pd.DataFrame:
    keys = [c for c in frame.columns if c not in ("v3", "median_v3",
                                                   "stddev_v3")]
    return frame.sort_values(keys, kind="stable").reset_index(drop=True)


def same(got: pd.DataFrame, want: pd.DataFrame) -> None:
    got, want = aligned(got), aligned(want)
    assert list(got.columns) == list(want.columns)
    assert got.shape == want.shape
    for c in want.columns:
        if c in ("median_v3", "stddev_v3"):
            # the deviation comes from the sums of v3 and of its squares,
            # which cancel in a group of a few close values (the cell's
            # groups have a thousand rows; its limit is PERF.md's)
            np.testing.assert_allclose(got[c].to_numpy(dtype=float),
                                       want[c].to_numpy(dtype=float),
                                       rtol=1e-12 if c == "median_v3" else 1e-6,
                                       equal_nan=True)
        else:  # keys, row numbers and the values that pass through: exact
            assert got[c].tolist() == want[c].tolist(), c


@pytest.fixture(scope="module")
def edges(templates):
    """template -> (the served path's answer, ``TpuContext``'s, the
    reference) over the table of the edge cases."""
    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.exec.context import TpuContext

    tables = {"x": edge_table()}
    frames = verify.frames(tables, templates)
    served = BallistaContext.standalone(concurrent_tasks=4)
    local = TpuContext()
    try:
        served.register_table("x", tables["x"])
        local.register_table("x", tables["x"])
        return {
            name: (served.sql(mod.SQL).collect().to_pandas(),
                   local.sql(mod.SQL).collect().to_pandas(),
                   mod.reference(frames, {}))
            for name, mod in templates.items()
        }
    finally:
        served.close()


@pytest.mark.parametrize("name", CASES)
def test_edge_cases_on_the_served_path(edges, name):
    got, _, want = edges[name]
    if name == "g1q8":  # one row for a group of one, two for the others
        per_group = want.groupby("id6").size()
        assert set(per_group) == {1, 2} and len(want) == per_group.sum()
    else:  # a group of one row has a median and no deviation
        assert want.stddev_v3.isna().any() and want.median_v3.notna().all()
    same(got, want)


@pytest.mark.parametrize("name", CASES)
def test_the_local_context_gives_the_same_answers(edges, name):
    served, local, _ = edges[name]
    same(local, served)


@pytest.fixture(scope="module")
def served(templates):
    """template -> two runs of it over 2e5 rows of the data set (1e4 groups
    of 20 rows, 2,000 partitions of 100) with what the counters moved by."""
    from ballista_tpu.client.context import BallistaContext

    cfg = {"dataset": "h2o_g1", "rows": ROWS, "k": 100}
    tables = dataset.load(cfg).tables(cfg, SEED)
    frames = verify.frames(tables, templates)
    ctx = BallistaContext.standalone(concurrent_tasks=4)
    out = {}
    try:
        ctx.register_table("x", tables["x"])
        for name, mod in templates.items():
            runs = []
            for _ in range(2):
                before = holistic()
                answer = ctx.sql(mod.SQL).collect()
                runs.append((answer, {k: v - before[k]
                                      for k, v in holistic().items()}))
            out[name] = (runs, mod.reference(frames, {}))
    finally:
        ctx.close()
    return out


@pytest.mark.parametrize("name", CASES)
def test_the_served_path_gives_the_reference_answer(served, templates, name):
    runs, reference = served[name]
    assert len(reference) == {"g1q6": 10_000, "g1q8": 4_000}[name]
    for answer, _ in runs:
        verdict = verify.judge([(name, 0, answer)], {name: templates[name]},
                               {(name, 0): reference}, 0)
        assert verdict["correct"], (verdict["numbers"],
                                    verdict["first_mismatch"])


@pytest.mark.parametrize("name", CASES)
def test_the_counters_read_what_the_plan_says(served, name):
    """Every row goes through one sort, divided over the exchange's two
    buckets: a task each."""
    tasks, passes = CASES[name]
    for _, moved in served[name][0]:
        assert moved == {"holistic.tasks": tasks,
                         "holistic.rows_sorted": ROWS,
                         "holistic.sort_passes": tasks * passes}


@pytest.mark.parametrize("name", CASES)
def test_without_the_exchange_one_task_gathers_every_row(templates, name):
    """``ballista.repartition.windows`` off: the gather into one partition,
    a stage boundary as an ORDER BY's is, one task for the operator, the
    same answer."""
    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.config import BallistaConfig

    table = edge_table()
    cfg = BallistaConfig().with_setting("ballista.repartition.windows",
                                        "false")
    ctx = BallistaContext.standalone(cfg, concurrent_tasks=4)
    try:
        ctx.register_table("x", table)
        before = holistic()
        got = ctx.sql(templates[name].SQL).collect().to_pandas()
        moved = {k: v - before[k] for k, v in holistic().items()}
    finally:
        ctx.close()
    assert moved["holistic.tasks"] == 1
    assert moved["holistic.rows_sorted"] == table.num_rows
    assert moved["holistic.sort_passes"] == CASES[name][1]
    frames = verify.frames({"x": table}, {name: templates[name]})
    same(got, templates[name].reference(frames, {}))


def test_the_counters_are_declared_at_zero():
    """A reader tells "none" from a parent's "no such counter", and the
    templates' ``needs`` check finds the declaration in the file's text."""
    from queries import g1_adv_needs

    assert set(metrics.HOLISTIC_COUNTERS) <= set(metrics.snapshot())
    assert g1_adv_needs.COUNTER.strip('"') in metrics.HOLISTIC_COUNTERS
    g1_adv_needs.check("g1q6")  # this checkout declares it: no exit


@pytest.mark.parametrize("number", ["relerr_g1q6_median", "relerr_g1q6_sd",
                                    "relerr_g1q8_v3"])
def test_float32_control_is_over_the_limit(number):
    """The reference computed in float32, the precision below the
    configuration's float64, judged as if it were the program's answer."""
    name = number.split("_")[1]
    mix = {"templates": [name], "pool": 1, "param_seed": 33}
    cfg = json.loads(
        (PERF / "configs" / "h2o-g1-1e7-adv-mem.json").read_text())
    verdict = control.control_run(mix, ROWS / cfg["rows"], SEED, "float32",
                                  cfg)
    n = verdict["numbers"][number]
    assert not verdict["correct"]
    assert n["value"] > 1e-8 and n["value"] > 10 * n["limit"], n
    assert verdict["numbers"]["mismatched"]["value"] == 0
