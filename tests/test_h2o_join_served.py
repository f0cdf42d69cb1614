"""h2oai db-benchmark's join questions (the templates of the cell
``h2o-j1-1e7-mem.join``) on the served path, against each template's own
plain reference, at a size the CPU runs, with the join's counters
(``join.builds``, ``join.build_rows``, ``join.probe_rows``,
``join.key_remaps``) held to what numpy counts over the same data.

At 30,000 rows the key sets are those of the cell in miniature: ``key3``
over 30,000 (27,000 common, 3,000 left only, 3,000 right only) and ``key2``
over 30 (27 common, 3 a side), so x's and medium's dictionaries of ``id5``
differ in three entries each and every shared string has another code in
each. Device batches of 4,096 rows give each of x's scan tasks several
probe batches: the first unifies the two dictionaries and rebuilds
medium's probe table, and every later one finds the build's dictionary
whole and builds nothing.
"""

import pathlib
import sys

import numpy as np
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

ROWS = 30_000
BATCH_ROWS = 4_096
SEED = 4_200_000_021
TEMPLATES = ["j1q5", "j1q4"]
CFG = {"dataset": "h2o_j1", "rows": ROWS}


@pytest.fixture(scope="module")
def tables():
    return dataset.load(CFG).tables(CFG, SEED)


@pytest.fixture(scope="module")
def templates():
    return traffic.load_templates(TEMPLATES)


@pytest.fixture(scope="module")
def served(tables, templates):
    """name -> (the partitions of x and of each exchange, and what each
    of two runs of the template gave: its answer and how the join's
    counters moved), in one context."""
    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.config import BallistaConfig

    cfg = BallistaConfig().with_setting(
        "ballista.tpu.batch_rows", str(BATCH_ROWS))
    ctx = BallistaContext.standalone(cfg, concurrent_tasks=4)
    out = {}
    try:
        for name, t in tables.items():
            ctx.register_table(name, t)
        for name in TEMPLATES:
            runs = []
            for _ in range(2):
                before = metrics.snapshot()
                answer = ctx.sql(templates[name].SQL).collect()
                after = metrics.snapshot()
                runs.append((answer, {
                    k: after[k] - before.get(k, 0) for k in after
                    if k.startswith("join.")}))
            out[name] = runs
    finally:
        ctx.close()
    return cfg.default_shuffle_partitions(), out


def test_the_data_has_left_only_and_right_only_keys(tables):
    x, big, medium = (tables[t] for t in ("x", "big", "medium"))
    x3, b3 = x["id3"].to_numpy(), big["id3"].to_numpy()
    assert len(np.unique(x3)) == len(x3) == ROWS  # each key once a side
    assert len(np.unique(b3)) == len(b3) == ROWS
    assert len(np.intersect1d(x3, b3)) == ROWS * 9 // 10
    xd = set(x["id5"].unique().to_pylist())
    md = set(medium["id5"].to_pylist())
    assert len(xd) == len(md) == ROWS // 1000
    assert len(xd & md) == 27 and xd - md and md - xd
    # a shared string's code differs between the two sorted dictionaries
    shared = sorted(xd & md)
    assert any(sorted(xd).index(s) != sorted(md).index(s) for s in shared)


@pytest.mark.parametrize("name", TEMPLATES)
def test_the_served_path_gives_the_reference_answer(
        tables, templates, served, name):
    mod = templates[name]
    reference = mod.reference(verify.frames(tables, {name: mod}), {})
    for answer, _ in served[1][name]:
        verdict = verify.judge([(name, 0, answer)], {name: mod},
                               {(name, 0): reference}, 0)
        assert verdict["correct"], (verdict["numbers"],
                                    verdict["first_mismatch"])
    assert reference.n[0] > ROWS * 0.85


@pytest.mark.parametrize("name", TEMPLATES)
def test_the_join_counters_read_what_numpy_counts(tables, served, name):
    """Every live probe row of x once; j1q5's build is big's rows, split
    between the exchange's buckets; j1q4 builds medium twice in each of x's
    scan tasks, on its own codes to decide the strategy as every partition
    does and again after the remap its first probe batch brings, and never
    again for the batches after it."""
    partitions, runs = served
    x, big, medium = (tables[t].num_rows for t in ("x", "big", "medium"))
    assert x // BATCH_ROWS >= 2 * partitions  # several batches a task
    want = {
        "j1q5": {"join.builds": partitions, "join.build_rows": big,
                 "join.probe_rows": x, "join.key_remaps": 0},
        "j1q4": {"join.builds": 2 * partitions,
                 "join.build_rows": 2 * partitions * medium,
                 "join.probe_rows": x, "join.key_remaps": partitions},
    }[name]
    for _, moved in runs[name]:
        assert {k: moved.get(k, 0) for k in want} == want, moved
        assert moved.get("join.noninner.probe_rows", 0) == 0
        # each build gathered its key column: 8 bytes a slot of big's int64
        # key, 4 of medium's string codes. big's buckets, probed by batches
        # smaller than themselves, keep their payload in place; medium's
        # 30 rows, probed by 4,096-row batches, are gathered into sorted
        # order once a probed build (the decision builds are never probed)
        assert moved["join.builds_in_place"] == partitions
        width = {"j1q5": 8, "j1q4": 4}[name]
        assert moved["join.build_gather_bytes"] >= width * moved[
            "join.build_rows"]


def test_the_counters_are_declared_at_zero():
    from ballista_tpu.compilecache.metrics import JOIN_COUNTERS

    assert set(JOIN_COUNTERS) == {"join.builds", "join.build_rows",
                                  "join.probe_rows", "join.key_remaps",
                                  "join.build_gather_bytes",
                                  "join.builds_in_place"}
    assert set(JOIN_COUNTERS) <= set(metrics.snapshot())


@pytest.mark.parametrize("name", TEMPLATES)
def test_float32_control_is_over_the_limit(name):
    """The reference computed in float32, the precision below the
    configuration's float64, judged as if it were the program's answer."""
    mix = {"templates": [name], "pool": 1, "param_seed": 42}
    cfg = {"dataset": "h2o_j1", "rows": 10**7}
    verdict = control.control_run(mix, 0.01, SEED, "float32", cfg)
    number = verdict["numbers"][f"relerr_{name}"]
    assert not verdict["correct"]
    assert number["value"] > number["limit"], number
    assert verdict["numbers"]["mismatched"]["value"] == 0
