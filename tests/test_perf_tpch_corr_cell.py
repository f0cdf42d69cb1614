"""The benchmark's files for the cell ``tpch-sf1-corr-mem.correlated``, held
by the tier-1 run as ``test_perf_tpch_subq_cell.py`` holds the subquery
cell's: the configuration is ``tpch-sf1-mem``'s cut in ``queries`` only (q17
and q20), the two ``.sql`` files are upstream's once clause 2.4's validation
parameters are substituted, the draws are the clause's, the cell runs in
rehearsal through the harness's own ``run_cell`` and comes out as the
comparison said, its traced rehearsal reports every metric the benchmark
holds it to, and ``verify.judge`` refuses the faults a correlated scalar subquery
can have: a tie decided the wrong way, a NULL subquery taken for 0, a
supplier lost, the order reversed."""

import argparse
import datetime
import json
import os
import pathlib
import subprocess
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

import datagen  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402
import verify  # noqa: E402

CONFIG = "tpch-sf1-corr-mem"
CELL = f"{CONFIG}.correlated"
TEMPLATES = ["q17", "q20"]
# the traffic lists q20 first: the warm-up meets the largest aggregate first
IN_THE_CELL = ["q20", "q17"]
BROUGHT = ["subquery_agg_rows_per_query", "subquery_agg_groups_per_query",
           "subquery_agg_self_ms_per_query"]
# and the one its aggregates' reduction brought after them
REDUCED = "subquery_agg_reduced_per_query"
SEED = 4_000_000_017  # at SF 0.02 every draw of the pool keeps lines


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
        CONFIG, "correlated", 1)
    # the data set, deployment and session of tpch-sf1-mem
    mem = json.loads((PERF / "configs" / "tpch-sf1-mem.json").read_text())
    for key in ("deployment", "scale_factor", "scale_factors_published",
                "queries_published", "tables", "concurrent_tasks",
                "task_scheduling_policy", "session_settings", "chips"):
        assert cfg[key] == mem[key], key
    assert cfg["session_settings"] == {} and "dataset" not in cfg
    assert set(cfg["guarantees"]) >= {"decimal_ties", "null_subquery",
                                      "keys_and_order", "result_cache"}
    assert {"generator", "p_name", "decimals"} <= set(cfg["assumed"])
    assert "q2 " in cfg["reduced"]["queries"]
    mix = traffic.load("correlated")
    assert mix["templates"] == IN_THE_CELL
    assert cfg["queries"] == len(mix["templates"]) <= cfg["queries_published"]
    assert (mix["clients"], mix["pool"], mix["param_seed"], mix["order"]) == (
        1, 2, 40, "shuffled")
    # the three per-layer metrics this cell brought and the reduction's
    # count: every cell's, each with a reader, appended together after what
    # was there
    for name in BROUGHT + [REDUCED]:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (PERF / "layers" / f"{name}.py").is_file()
        assert m["moves"] == "queries_per_s"
        assert m["source"] == "program_counter" and "workloads" not in m
    # appended together, after what was there (later PRs append after them)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(BROUGHT[0])
    assert names[at:at + 4] == BROUGHT + [REDUCED]
    reduced = next(m for m in bench["per_layer"] if m["name"] == REDUCED)
    assert (reduced["layer"], reduced["better"]) == ("executor", "higher")
    # the configuration and the cell after every one that was there before
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index(CONFIG) == configs.index("tpch-sf1-subq-mem") + 1
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) == cells.index("tpch-sf1-subq-mem.subquery") + 1


@pytest.mark.parametrize("name", TEMPLATES)
def test_a_template_is_upstreams_query_at_the_validation_parameters(name):
    mod = traffic.load_templates([name])[name]
    upstream = (ROOT / "benchmarks" / "queries" / f"{name}.sql").read_text()
    assert mod.SQL.format(**mod.VALIDATION) == upstream
    assert mod.SQL != upstream and "{" not in upstream
    assert mod.least_bytes({"lineitem": 400, "part": 20, "partsupp": 80,
                            "supplier": 1, "nation": 25}) > 0
    # q17's one float is held to a limit; q20's answer compares exactly
    assert mod.LIMITS == ({"relerr_q17": (("avg_yearly",), 1e-12)}
                          if name == "q17" else {})


def test_the_draws_are_clause_2_4s():
    mods = traffic.load_templates(TEMPLATES)
    rng = np.random.default_rng(40)
    seen = {name: [mods[name].draw(rng) for _ in range(400)]
            for name in TEMPLATES}
    for p in seen["q17"]:
        assert set(p) == {"brand", "container"}
        assert p["brand"][:6] == "Brand#" and len(p["brand"]) == 8
        assert p["brand"][6] in "12345" and p["brand"][7] in "12345"
        assert p["container"] in datagen.CONTAINERS
    assert len({p["container"] for p in seen["q17"]}) == 40
    assert len({p["brand"] for p in seen["q17"]}) == 25
    assert mods["q20"].COLORS == datagen.P_NAME_WORDS
    assert mods["q20"].NATIONS == [n for n, _ in datagen.NATIONS]
    for p in seen["q20"]:
        assert set(p) == {"color", "date", "nation"}
        assert p["color"] in datagen.P_NAME_WORDS
        assert p["nation"] in mods["q20"].NATIONS
    assert {p["date"] for p in seen["q20"]} == {
        f"{y}-01-01" for y in range(1993, 1998)}
    # the pool of the cell: two draws a template, the same for every seed
    pool = traffic.pool(traffic.load("correlated"), mods)
    assert {t: len(ps) for t, ps in pool.items()} == dict.fromkeys(
        IN_THE_CELL, 2)
    assert pool == traffic.pool(traffic.load("correlated"), mods)


def test_the_cell_in_rehearsal_is_what_the_comparison_said(capsys, monkeypatch):
    """``--rehearse-sf 0.02`` through ``BallistaContext.standalone`` as the
    chip run goes: 4,000 parts, 120,000 lines, 200 suppliers."""
    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", "off")
    result = run.run_cell(argparse.Namespace(
        workload=CELL, seed=SEED, seconds=1.0, trace=0, rehearse_sf=0.02,
    ))
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 2 and result["attempted"] % 2 == 0
    assert set(result["compared"]) == {"relerr_q17", "mismatched", "failed",
                                       "answered"}
    assert result["compared"]["mismatched"] == {"value": 0, "limit": 0}
    assert result["compared"]["relerr_q17"]["value"] < 1e-12
    assert set(result["metrics"]) == {"queries_per_s", "setup_s"}
    err = capsys.readouterr().err.splitlines()
    assert any(line.startswith("perf: data: tpch ") and "part 4000 rows"
               in line for line in err)
    assert sum(line.startswith("perf: stages: ") for line in err) == 1


def test_a_traced_run_of_the_cell_reports_every_metric_it_is_held_to(tmp_path):
    """As ``test_perf_tpch_subq_cell.py``'s: every per-layer metric with no
    ``workloads`` key, or with one that lists the cell, that a rehearsal on a
    CPU can read (the device's trace and memory it has not)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    held_to = {m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", [CELL])
               and m["source"] != "device_trace" and m["layer"] != "device"}
    assert set(BROUGHT) | {REDUCED} <= held_to
    assert {"noninner_join_tasks_per_query", "agg_groups_per_query",
            "agg_self_ms_per_query", "agg_capacity_retries_in_window",
            "task_unnamed_ms_per_query"} <= held_to
    done = subprocess.run(
        [sys.executable, str(PERF / "run.py"), "--workload", CELL,
         "--seed", "4000000034", "--seconds", "1", "--trace", "1",
         "--rehearse-sf", "0.01"],
        cwd=ROOT, capture_output=True, text=True, timeout=280,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "BALLISTA_TPU_HINT_CACHE": "off", "TMPDIR": str(tmp_path)})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert held_to - set(line["metrics"]) == set()
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # reduced to the outer query's parts: q17 groups the lines of the one
    # or two of 2,000 parts its brand and container keep (of 60,000 lines
    # at SF 0.01), q20 the year's lines of the some 21 its colour keeps
    assert 0 < metrics["subquery_agg_rows_per_query"] < 1_000
    assert 0 < metrics["subquery_agg_groups_per_query"] < 1_000
    assert metrics[REDUCED] > 0
    assert metrics["subquery_agg_self_ms_per_query"] > 0
    assert metrics["subquery_agg_self_ms_per_query"] <= metrics[
        "agg_self_ms_per_query"]
    # q20's two semi joins ran; nothing was run twice
    assert metrics["noninner_join_tasks_per_query"] > 0
    assert metrics["agg_capacity_retries_in_window"] == 0
    assert metrics["holistic_tasks_per_query"] == 0
    # the reductions' and the joins' builds gathered their keys
    assert metrics["join_build_gather_mb_per_query"] > 0


def test_the_reduced_aggregates_reader():
    """A number per completed query where the program counts reduced tasks,
    0 where it declares the counter and nothing was reduced (every other
    cell), ``None`` where the program has no such counter (a parent
    commit), so that the metric is left out of the line."""
    from layers import subquery_agg_reduced_per_query

    key = "subquery.agg_reduced"
    done = {"error": None, "template": "q17", "t0": 10.0, "t1": 11.0}
    failed = {"error": "Boom", "template": "q20", "t0": 11.0, "t1": 11.5}

    def read(before, after, queries=(done, done, failed)):
        return subquery_agg_reduced_per_query.read(
            {"queries": list(queries), "counters_before": before,
             "counters_after": after})

    assert read({key: 3}, {key: 7}) == pytest.approx(2.0)
    assert read({key: 0}, {key: 0}) == 0.0
    old = {"subquery.agg_rows": 0, "agg.sort_passes": 4}
    assert read(old, old) is None
    assert read(None, None) is None
    assert read({key: 0}, {key: 2}, [failed]) is None


# -- planted faults -----------------------------------------------------------------


@pytest.fixture(scope="module")
def sound():
    """template -> (module, its reference over seeded data at the validation
    parameters as an Arrow table: the answer a sound program gives; the
    data's frames)."""
    templates = traffic.load_templates(TEMPLATES)
    tables = datagen.gen_all(0.02, SEED)
    frames = verify.frames(tables, templates)
    return {
        name: (mod, pa.Table.from_pandas(
            mod.reference(frames, mod.VALIDATION), preserve_index=False),
            frames)
        for name, mod in templates.items()
    }


def tie_kept(table, frames):
    """q17 with one more line of a kept part in the sum: what a tie decided
    as ``<=`` adds (its price over 7, about 1e-3 of the answer at SF1)."""
    mod = traffic.load_templates(["q17"])["q17"]
    kept = mod.small_lines(frames, mod.VALIDATION)
    li = frames["lineitem"]
    other = li[li.l_partkey.isin(kept.l_partkey).to_numpy()
               & ~li.index.isin(kept.index)]
    extra = float(other.l_extendedprice.min()) / 7.0
    avg = np.asarray(table.column("avg_yearly")) + extra
    return table.set_column(0, table.schema.field(0), pa.array(avg))


def null_as_zero(table, frames):
    """q20 with a pair that has no line in the year kept, as if its
    subquery were 0 and not NULL: more suppliers than SQL's answer."""
    mod = traffic.load_templates(["q20"])["q20"]
    patched = dict(frames)
    # every pair ships one line of quantity 0 on the year's first day
    ps = frames["partsupp"]
    day = (datetime.date(1994, 1, 1) - mod.EPOCH).days
    zero = pd.DataFrame({"l_partkey": ps.ps_partkey, "l_suppkey": ps.ps_suppkey,
                         "l_quantity": 0.0, "l_shipdate": np.int32(day)})
    li = frames["lineitem"][list(zero.columns)]
    patched["lineitem"] = pd.concat([li, zero], ignore_index=True)
    out = mod.reference(patched, mod.VALIDATION)
    assert len(out) > table.num_rows
    return pa.Table.from_pandas(out, preserve_index=False)


def supplier_lost(table, frames):
    """q20 without its last supplier."""
    return table.slice(0, table.num_rows - 1)


def descending(table, frames):
    """q20 ordered by ``s_name`` descending."""
    return table.take(pa.array(np.arange(table.num_rows)[::-1]))


# fault -> (the template it is planted in, how, the number that refuses it)
FAULTS = {
    "tie_kept": ("q17", tie_kept, "relerr_q17"),
    "null_as_zero": ("q20", null_as_zero, "mismatched"),
    "supplier_lost": ("q20", supplier_lost, "mismatched"),
    "descending": ("q20", descending, "mismatched"),
}


@pytest.mark.parametrize("name", TEMPLATES)
def test_judge_passes_a_sound_answer(sound, name):
    mod, answer, _ = sound[name]
    assert answer.num_rows > 0
    verdict = verify.judge([(name, 0, answer)], {name: mod},
                           {(name, 0): answer.to_pandas()}, 0)
    assert verdict["correct"] and verdict["numbers"]["mismatched"]["value"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_judge_refuses_a_fault_of_a_correlated_subquery(sound, fault):
    name, plant, number = FAULTS[fault]
    mod, answer, frames = sound[name]
    verdict = verify.judge([(name, 0, plant(answer, frames))], {name: mod},
                           {(name, 0): answer.to_pandas()}, 0)
    assert not verdict["correct"]
    n = verdict["numbers"][number]
    assert n["value"] > n["limit"]
