"""The benchmark's files for the cell ``tpch-sf1-subq-mem.subquery``, held by
the tier-1 run as ``test_perf_h2o_adv_cell.py`` holds the advanced group-by
cell's: the configuration is ``tpch-sf1-mem``'s cut in ``queries`` only (q13
and q4; q16, cut from the traffic by the rule of a cold run, keeps its
files and its tests), the three ``.sql`` files are upstream's once clause
2.4's validation parameters are substituted, the draws are the clause's, the cell runs in rehearsal
through the harness's own ``run_cell`` and comes out as the comparison said,
its traced rehearsal reports every metric the driver holds it to, and
``verify.judge`` refuses the faults an outer, a semi and an anti join and a
distinct count can have."""

import argparse
import datetime
import json
import os
import pathlib
import subprocess
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
import run  # noqa: E402
import traffic  # noqa: E402
import verify  # noqa: E402

CONFIG = "tpch-sf1-subq-mem"
CELL = f"{CONFIG}.subquery"
TEMPLATES = ["q13", "q4", "q16"]  # written, referenced and tested
IN_THE_CELL = ["q13", "q4"]  # q16 was cut by the rule of a cold run
BROUGHT = {"dict_predicate_entries_per_query": None,
           "noninner_join_probe_rows_per_query": None,
           "noninner_join_tasks_per_query": None,
           "dict_predicate_ms_per_query": [CELL]}


def test_the_cell_is_tpch_sf1_mem_cut_in_queries_only():
    cfg = json.loads((PERF / "configs" / f"{CONFIG}.json").read_text())
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    assert entry["reduced"] == ["queries", "scale_factor"]
    assert set(cfg["reduced"]) == set(entry["reduced"])
    assert entry["source"] == cfg["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "subquery", 1)
    # the data set, deployment and session of tpch-sf1-mem: one
    # configuration's repair lands under the other
    mem = json.loads((PERF / "configs" / "tpch-sf1-mem.json").read_text())
    for key in ("deployment", "scale_factor", "scale_factors_published",
                "queries_published", "tables", "concurrent_tasks",
                "task_scheduling_policy", "session_settings", "chips"):
        assert cfg[key] == mem[key], key
    assert cfg["session_settings"] == {} and "dataset" not in cfg
    assert set(cfg["guarantees"]) >= {"like", "outer_join", "not_in",
                                      "result_cache"}
    assert {"comments", "o_custkey"} <= set(cfg["assumed"])
    mix = traffic.load("subquery")
    assert mix["templates"] == IN_THE_CELL
    assert cfg["queries"] == len(mix["templates"]) <= cfg["queries_published"]
    assert "q16" in cfg["reduced"]["queries"] and "q16" in mix["about"]
    assert (mix["clients"], mix["pool"], mix["param_seed"], mix["order"]) == (
        1, 2, 36, "shuffled")
    # the per-layer metrics this cell brought, each with a reader; three are
    # every cell's, the phase's is this cell's alone
    for name, cells in BROUGHT.items():
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (PERF / "layers" / f"{name}.py").is_file()
        assert m["moves"] == "queries_per_s"
        assert m["source"] == "program_counter"
        assert m.get("workloads") == cells
    # appended together, after what was there (later PRs append after them)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(next(iter(BROUGHT)))
    assert names[at:at + len(BROUGHT)] == list(BROUGHT)


@pytest.mark.parametrize("name", TEMPLATES)
def test_a_template_is_upstreams_query_at_the_validation_parameters(name):
    """``benchmarks/queries/q{13,4,16}.sql`` letter for letter once clause
    2.4's validation values stand in the placeholders; exact answers, and an
    ORDER BY that names every column it needs to be a total order."""
    mod = traffic.load_templates([name])[name]
    upstream = (ROOT / "benchmarks" / "queries" / f"{name}.sql").read_text()
    assert mod.SQL.format(**mod.VALIDATION) == upstream
    assert mod.SQL != upstream and "{" not in upstream
    assert mod.LIMITS == {}
    assert mod.least_bytes({"customer": 10, "orders": 100, "lineitem": 400,
                            "part": 20, "partsupp": 80, "supplier": 1}) > 0


def test_the_draws_are_clause_2_4s():
    mods = traffic.load_templates(TEMPLATES)
    rng = np.random.default_rng(36)
    seen = {name: [mods[name].draw(rng) for _ in range(200)]
            for name in TEMPLATES}
    words = set(datagen.COMMENT_WORDS)
    for p in seen["q13"]:
        assert set(p) == {"word1", "word2"} and set(p.values()) <= words
    assert {p["word1"] for p in seen["q13"]} == {
        "special", "pending", "unusual", "express"}
    assert {p["word2"] for p in seen["q13"]} == {
        "packages", "requests", "accounts", "deposits"}
    days = {datetime.date.fromisoformat(p["date"]) for p in seen["q4"]}
    assert all(d.day == 1 for d in days)
    assert datetime.date(1993, 1, 1) <= min(days)
    assert max(days) <= datetime.date(1997, 10, 1) and len(days) > 40
    for p in seen["q16"]:
        assert set(p) == set(mods["q16"].VALIDATION)
        sizes = [p[f"size{i}"] for i in range(1, 9)]
        assert len(set(sizes)) == 8 and 1 <= min(sizes) <= max(sizes) <= 50
        first, second = p["type"].split(" ")
        assert first in datagen.TYPE_S1 and second in datagen.TYPE_S2
        assert p["brand"][:6] == "Brand#" and p["brand"][6] in "12345"
        assert p["brand"][7] in "12345" and len(p["brand"]) == 8
    # the pool of the cell: two draws a template, the same for every seed
    pool = traffic.pool(traffic.load("subquery"), mods)
    assert {t: len(ps) for t, ps in pool.items()} == dict.fromkeys(
        IN_THE_CELL, 2)
    assert pool == traffic.pool(traffic.load("subquery"), mods)


def test_the_cell_in_rehearsal_is_what_the_comparison_said(capsys, monkeypatch):
    """``--rehearse-sf 0.02`` through ``BallistaContext.standalone`` as the
    chip run goes: 3,000 customers, 30,000 orders, 200 suppliers."""
    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", "off")
    result = run.run_cell(argparse.Namespace(
        workload=CELL, seed=3_600_000_017, seconds=1.0, trace=0,
        rehearse_sf=0.02,
    ))
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 2 and result["attempted"] % 2 == 0
    # no float is computed: the exact comparison is all there is
    assert set(result["compared"]) == {"mismatched", "failed", "answered"}
    assert result["compared"]["mismatched"] == {"value": 0, "limit": 0}
    assert set(result["metrics"]) == {"queries_per_s", "setup_s"}
    err = capsys.readouterr().err.splitlines()
    assert any(line.startswith("perf: data: tpch ") and "orders 30000 rows"
               in line for line in err)
    assert sum(line.startswith("perf: stages: ") for line in err) == 1


def test_a_traced_run_of_the_cell_reports_every_metric_it_is_held_to(tmp_path):
    """As ``test_perf_h2o_adv_cell.py``'s: every per-layer metric with no
    ``workloads`` key, or with one that lists the cell, that a rehearsal on a
    CPU can read (the device's trace and memory it has not)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    held_to = {m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", [CELL])
               and m["source"] != "device_trace" and m["layer"] != "device"}
    assert set(BROUGHT) <= held_to
    assert {"holistic_tasks_per_query", "agg_groups_per_query",
            "task_unnamed_ms_per_query",
            "agg_dense_factored_passes_per_query"} <= held_to
    done = subprocess.run(
        [sys.executable, str(PERF / "run.py"), "--workload", CELL,
         "--seed", "3600000034", "--seconds", "1", "--trace", "1",
         "--rehearse-sf", "0.01"],
        cwd=ROOT, capture_output=True, text=True, timeout=280,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "BALLISTA_TPU_HINT_CACHE": "off", "TMPDIR": str(tmp_path)})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert held_to - set(line["metrics"]) == set()
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # each template's join that preserves a side, its side in two partitions
    assert metrics["noninner_join_tasks_per_query"] == 2
    assert metrics["noninner_join_probe_rows_per_query"] > 0
    # every (dictionary, pattern) of the window was met in warm-up
    assert metrics["dict_predicate_entries_per_query"] == 0
    assert metrics["dict_predicate_ms_per_query"] >= 0
    assert metrics["holistic_tasks_per_query"] == 0
    # q4's dense aggregate has 6 slots, within the one-hot kernels (PR 37)
    assert metrics["agg_dense_factored_passes_per_query"] == 0
    # q13's and q4's builds gathered their keys, 4 bytes a row at least
    assert metrics["join_build_gather_mb_per_query"] >= 4e-6 * metrics[
        "join_build_rows_per_query"] > 0


# -- planted faults -----------------------------------------------------------------


@pytest.fixture(scope="module")
def sound():
    """template -> (module, its reference over seeded data at the validation
    parameters as an Arrow table: the answer a sound program gives; the
    data's frames)."""
    templates = traffic.load_templates(TEMPLATES)
    tables = datagen.gen_all(0.02, 3_600_000_017)
    frames = verify.frames(tables, templates)
    return {
        name: (mod, pa.Table.from_pandas(
            mod.reference(frames, mod.VALIDATION), preserve_index=False),
            frames)
        for name, mod in templates.items()
    }


def inner_for_outer(table, frames):
    """q13 with ``LEFT`` planned as ``INNER``: the customers without an
    order are in no group, and the ``c_count = 0`` row is gone."""
    keep = np.asarray(table.column("c_count")) != 0
    assert not keep.all()
    return table.filter(pa.array(keep))


def not_dropped_from_not_like(table, frames):
    """q13 with the ``NOT`` of ``NOT LIKE`` dropped: orders are counted only
    where the comment matches, some 1 % of them."""
    c, o = frames["customer"], frames["orders"]
    special = o.o_comment.astype(str).str.contains("special.*requests")
    kept = o.loc[special.to_numpy(), ["o_custkey", "o_orderkey"]]
    j = c[["c_custkey"]].merge(kept, how="left", left_on="c_custkey",
                               right_on="o_custkey")
    dist = j.groupby("c_custkey").o_orderkey.count().value_counts()
    out = (dist.rename_axis("c_count").reset_index(name="custdist")
           .sort_values(["custdist", "c_count"], ascending=[False, False]))
    return pa.Table.from_pandas(out.astype(np.int64), preserve_index=False)


def nulls_counted(table, frames):
    """``count(*)`` where ``count(o_orderkey)`` is asked for: the NULL row
    of a customer without a match counts as one order."""
    cc = np.asarray(table.column("c_count")).copy()
    cc[cc == 0] = 1
    return table.set_column(0, table.schema.field(0), pa.array(cc))


def every_order_for_the_late_ones(table, frames):
    """q4 without its ``EXISTS``: every order of the quarter is counted."""
    mod = traffic.load_templates(["q4"])["q4"]
    o = frames["orders"]
    lo = (datetime.date(1993, 7, 1) - mod.EPOCH).days
    hi = (datetime.date(1993, 10, 1) - mod.EPOCH).days
    q = o[(o.o_orderdate >= lo) & (o.o_orderdate < hi)]
    counts = q.o_orderpriority.astype(str).value_counts().sort_index()
    return pa.table({"o_orderpriority": counts.index.to_numpy(),
                     "order_count": counts.to_numpy().astype(np.int64)})


def lines_counted_for_orders(table, frames):
    """q4's semi join run as an inner join: an order counts once a late
    line."""
    col = np.asarray(table.column("order_count")) * 2
    return table.set_column(1, table.schema.field(1), pa.array(col))


def complainers_kept(table, frames):
    """q16 without its ``NOT IN``: the supplier with a complaint on file is
    counted where it supplies."""
    mod = traffic.load_templates(["q16"])["q16"]
    patched = dict(frames)
    patched["supplier"] = frames["supplier"].assign(
        s_comment=frames["supplier"].s_comment.astype(str).str.replace(
            "Complaints", "Compliments"))
    out = mod.reference(patched, mod.VALIDATION)
    assert len(out) == table.num_rows
    return pa.Table.from_pandas(out, preserve_index=False)


def count_for_distinct_count(table, frames):
    """q16's ``COUNT(DISTINCT ps_suppkey)`` as ``COUNT(ps_suppkey)``: the
    same wherever a supplier comes once, one more in the first row."""
    col = np.asarray(table.column("supplier_cnt")).copy()
    col[0] += 1
    return table.set_column(3, table.schema.field(3), pa.array(col))


def ascending_for_descending(table, frames):
    """q16 ordered by ``supplier_cnt`` ascending."""
    return table.take(pa.array(np.arange(table.num_rows)[::-1]))


# fault -> (the template it is planted in, how)
FAULTS = {
    "inner_for_outer": ("q13", inner_for_outer),
    "not_dropped_from_not_like": ("q13", not_dropped_from_not_like),
    "nulls_counted": ("q13", nulls_counted),
    "every_order_for_the_late_ones": ("q4", every_order_for_the_late_ones),
    "lines_counted_for_orders": ("q4", lines_counted_for_orders),
    "complainers_kept": ("q16", complainers_kept),
    "count_for_distinct_count": ("q16", count_for_distinct_count),
    "ascending_for_descending": ("q16", ascending_for_descending),
}


@pytest.mark.parametrize("name", TEMPLATES)
def test_judge_passes_a_sound_answer(sound, name):
    mod, answer, _ = sound[name]
    assert answer.num_rows > 0
    verdict = verify.judge([(name, 0, answer)], {name: mod},
                           {(name, 0): answer.to_pandas()}, 0)
    assert verdict["correct"] and verdict["numbers"]["mismatched"]["value"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_judge_refuses_a_fault_of_a_join_that_preserves_a_side(sound, fault):
    name, plant = FAULTS[fault]
    mod, answer, frames = sound[name]
    verdict = verify.judge([(name, 0, plant(answer, frames))], {name: mod},
                           {(name, 0): answer.to_pandas()}, 0)
    assert not verdict["correct"]
    assert verdict["numbers"]["mismatched"] == {"value": 1, "limit": 0}
