"""The benchmark's files for the cell ``h2o-g1-1e7-adv-mem.advanced``, held
by the tier-1 run as ``test_perf_h2o_cell.py`` holds the group-by cell's (the
data set's and the session settings' tests are there): the configuration is
the published data set cut in ``rows`` and ``queries`` only, the templates
refuse a checkout from before their operators' repairs, the cell runs in
rehearsal through the harness's own ``run_cell`` and comes out as the
comparison said, and ``verify.judge`` refuses the faults an order statistic
and a window can have."""

import argparse
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
PERF = ROOT / "perf"
for p in (str(ROOT), str(PERF)):
    if p not in sys.path:
        sys.path.insert(0, p)

import dataset  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402
import verify  # noqa: E402

CONFIG = "h2o-g1-1e7-adv-mem"
CELL = f"{CONFIG}.advanced"
# 50 rows a (id4, id5) group and 100 an id6 partition: no group of one row,
# whose deviation is NULL and which the comparison calls not finite
G1 = {"dataset": "h2o_g1", "rows": 500_000, "k": 100}
NUMBERS = {"relerr_g1q6_median", "relerr_g1q6_sd", "relerr_g1q8_v3"}


def cell_config() -> dict:
    return json.loads((PERF / "configs" / f"{CONFIG}.json").read_text())


def test_the_cell_is_the_published_data_set_cut_in_rows_and_queries_only():
    cfg = cell_config()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    assert entry["reduced"] == ["rows", "queries"] == list(cfg["reduced"])
    assert entry["source"] == cfg["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "advanced", 1)
    # the deployment and the table of the group-by cell: one configuration's
    # repair lands under the other
    groupby = json.loads(
        (PERF / "configs" / "h2o-g1-1e7-mem.json").read_text())
    for key in ("dataset", "rows", "rows_published", "k", "tables",
                "deployment", "concurrent_tasks", "task_scheduling_policy",
                "session_settings", "chips", "queries_published"):
        assert cfg[key] == groupby[key], key
    assert set(cfg["guarantees"]) >= {"median", "stddev", "largest_two"}
    assert "stricter" in cfg["guarantees"]["median"]
    mix = traffic.load("advanced")
    assert mix["templates"] == ["g1q6", "g1q8"]
    assert cfg["queries"] == len(mix["templates"]) <= cfg["queries_published"]
    assert (mix["clients"], mix["pool"], mix["param_seed"], mix["order"]) == (
        1, 1, 33, "shuffled")
    # the questions as benchmarks/db_benchmark.py has them: g1q6 letter for
    # letter, g1q8 with the row number beside upstream's select list
    sys.path.insert(0, str(ROOT / "benchmarks"))
    from db_benchmark import GROUPBY_QUERIES

    templates = traffic.load_templates(mix["templates"])
    assert templates["g1q6"].SQL.strip() == GROUPBY_QUERIES["q6"]
    upstream = " ".join(GROUPBY_QUERIES["q8"].split()).lower()
    ours = " ".join(templates["g1q8"].SQL.split()).lower()
    assert ours == upstream.replace("select id6, v3 from",
                                    "select id6, v3, row from", 1) != upstream
    for mod in templates.values():
        assert mod.ORDER == [] and mod.draw(None) == {}
    assert set().union(*(m.LIMITS for m in templates.values())) == NUMBERS
    # the per-layer metrics this cell brought, each with a reader
    brought = [m for m in bench["per_layer"] if m["name"] in (
        "holistic_rows_sorted_per_query", "holistic_tasks_per_query",
        "holistic_device_ms_per_query", "holistic_roofline_share")]
    assert len(brought) == 4
    for m in brought:
        assert (PERF / "layers" / f"{m['name']}.py").is_file()
        assert m["moves"] == "queries_per_s"
        assert m.get("workloads", [CELL]) == [CELL]


@pytest.mark.parametrize("declared", [True, False])
def test_the_templates_refuse_a_checkout_without_the_counters(
        tmp_path, monkeypatch, capsys, declared):
    """Exit code 2 at once, naming what the checkout lacks, where its
    program is from before the operators' repairs (a parent commit under
    this PR's benchmark files); nothing where it declares the counter."""
    from queries import g1_adv_needs

    store = tmp_path / "metrics.py"
    store.write_text('HOLISTIC_COUNTERS = ("holistic.tasks",)\n' if declared
                     else 'AGG_COUNTERS = ("agg.capacity_retries",)\n')
    monkeypatch.setattr(g1_adv_needs, "DECLARED_IN", store)
    monkeypatch.setattr(g1_adv_needs, "ROOT", tmp_path)
    if declared:
        g1_adv_needs.check("g1q8")
        return
    with pytest.raises(SystemExit) as stopped:
        g1_adv_needs.check("g1q8")
    assert stopped.value.code == 2
    said = capsys.readouterr().err
    assert '"holistic.tasks"' in said and "g1q8" in said


def test_the_cell_in_rehearsal_is_what_the_comparison_said(capsys, monkeypatch):
    """``--rehearse-sf 0.02``: 2e5 rows, 1e4 groups of 20 and 2,000
    partitions of 100, through ``BallistaContext.standalone`` as the chip
    run goes."""
    # as tests/test_perf_h2o_cell.py: a run never points the hint file back
    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", "off")
    result = run.run_cell(argparse.Namespace(
        workload=CELL, seed=3_300_000_032, seconds=1.0, trace=0,
        rehearse_sf=0.02,
    ))
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 2 and result["attempted"] % 2 == 0
    compared = result["compared"]
    assert set(compared) == NUMBERS | {"mismatched", "failed", "answered"}
    for name in NUMBERS:
        assert compared[name]["value"] <= compared[name]["limit"]
    # on a CPU float64 is whole: what passes through comes back bit for bit
    assert compared["relerr_g1q8_v3"]["value"] == 0
    assert compared["mismatched"]["value"] == 0
    assert set(result["metrics"]) == {"queries_per_s", "setup_s"}
    err = capsys.readouterr().err.splitlines()
    assert any(line.startswith("perf: data: h2o_g1 ") and "x 200000 rows"
               in line for line in err)
    assert sum(line.startswith("perf: stages: ") for line in err) == 1


def test_a_traced_run_of_the_cell_reports_every_metric_it_is_held_to(tmp_path):
    """The driver refuses a ``--trace 1`` line that lacks a per-layer metric
    with no ``workloads`` key, or with one that lists the cell. A rehearsal
    on a CPU has every counter and host clock the chip run has (the device's
    trace and memory it has not), so a reader that finds nothing in this
    cell, as ``dict_merge_ms_per_query`` does where no query reads a string
    column, shows here and not first in the driver's check."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    held_to = {m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", [CELL])
               and m["source"] != "device_trace" and m["layer"] != "device"}
    assert {"holistic_tasks_per_query", "holistic_rows_sorted_per_query",
            "status_polls_per_query",
            "agg_dense_factored_passes_per_query"} <= held_to
    done = subprocess.run(
        [sys.executable, str(PERF / "run.py"), "--workload", CELL,
         "--seed", "3300000034", "--seconds", "1", "--trace", "1",
         "--rehearse-sf", "0.01"],
        cwd=ROOT, capture_output=True, text=True, timeout=280,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "BALLISTA_TPU_HINT_CACHE": "off", "TMPDIR": str(tmp_path)})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert held_to - set(line["metrics"]) == set()
    assert line["metrics"]["holistic_tasks_per_query"]["value"] == 2
    assert 1 <= line["metrics"]["status_polls_per_query"]["value"] < 10
    # int64 keys: no dense pass, none on the factorized one-hot (PR 37)
    assert line["metrics"]["agg_dense_factored_passes_per_query"]["value"] == 0
    # the two-key join's builds gathered their keys through the permutation
    assert line["metrics"]["join_build_gather_mb_per_query"]["value"] > 0


# -- planted faults -----------------------------------------------------------------


@pytest.fixture(scope="module")
def sound():
    """template -> (module, its reference over seeded data as an Arrow
    table: the answer a sound program gives; the data's frames)."""
    templates = traffic.load_templates(["g1q6", "g1q8"])
    tables = dataset.load(G1).tables(G1, 3_300_000_033)
    frames = verify.frames(tables, templates)
    return {
        name: (mod, pa.Table.from_pandas(mod.reference(frames, {}),
                                         preserve_index=False), frames)
        for name, mod in templates.items()
    }


def median_off_by_one_row(table, frames):
    """The first group's median taken one row further up its sorted values,
    as an interpolation index off by one gives it."""
    x = frames["x"]
    k4, k5 = table.column("id4")[0].as_py(), table.column("id5")[0].as_py()
    v = np.sort(x.v3[(x.id4 == k4) & (x.id5 == k5)].to_numpy())
    n = len(v)
    shifted = (v[n // 2] if n % 2 == 0 else (v[n // 2] + v[n // 2 + 1]) / 2)
    col = table.column("median_v3").to_numpy().copy()
    assert shifted != col[0]
    col[0] = shifted
    at = table.schema.get_field_index("median_v3")
    return table.set_column(at, table.schema.field(at), pa.array(col))


def deviation_of_the_population(table, frames):
    """``stddev_pop`` where the sample deviation is asked for: n in the
    place of n - 1, a 2.5 % smaller answer in groups of 50 rows."""
    n = frames["x"].groupby(["id4", "id5"]).size().to_numpy()
    at = table.schema.get_field_index("stddev_v3")
    col = table.column(at).to_numpy() * np.sqrt((n - 1) / n)
    return table.set_column(at, table.schema.field(at), pa.array(col))


def dropped_second_row(table, frames):
    """``row <= 1`` for one partition: its second row is not returned."""
    second = pc.index(table.column("row"), 2).as_py()
    keep = np.ones(table.num_rows, dtype=bool)
    keep[second] = False
    return table.filter(pa.array(keep))


def third_largest_for_second(table, frames):
    """One partition's second row holds its third largest value."""
    second = pc.index(table.column("row"), 2).as_py()
    key = table.column("id6")[second].as_py()
    x = frames["x"]
    third = np.sort(x.v3[x.id6 == key].to_numpy())[-3]
    col = table.column("v3").to_numpy().copy()
    assert third != col[second]
    col[second] = third
    at = table.schema.get_field_index("v3")
    return table.set_column(at, table.schema.field(at), pa.array(col))


def swapped_key(table, frames):
    """The first two rows under each other's key: ``id5`` for g1q6 (two
    groups), ``id6`` for g1q8's rows 0 and 2 (two partitions)."""
    name, other = ("id5", 1) if "id5" in table.column_names else ("id6", 2)
    col = table.column(name).to_numpy().copy()
    assert col[0] != col[other]
    col[0], col[other] = col[other], col[0]
    at = table.schema.get_field_index(name)
    return table.set_column(at, table.schema.field(at), pa.array(col))


# fault -> (the template it is planted in, how, the number that has to fail)
FAULTS = {
    "median_off_by_one_row": ("g1q6", median_off_by_one_row,
                              "relerr_g1q6_median"),
    "deviation_of_the_population": ("g1q6", deviation_of_the_population,
                                    "relerr_g1q6_sd"),
    "swapped_key_g1q6": ("g1q6", swapped_key, "relerr_g1q6_median"),
    "dropped_second_row": ("g1q8", dropped_second_row, "mismatched"),
    "third_largest_for_second": ("g1q8", third_largest_for_second,
                                 "relerr_g1q8_v3"),
    "swapped_key_g1q8": ("g1q8", swapped_key, "relerr_g1q8_v3"),
}


@pytest.mark.parametrize("name", ["g1q6", "g1q8"])
def test_judge_passes_a_sound_answer(sound, name):
    mod, answer, _ = sound[name]
    verdict = verify.judge([(name, 0, answer)], {name: mod},
                           {(name, 0): answer.to_pandas()}, 0)
    assert verdict["correct"] and verdict["numbers"]["mismatched"]["value"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_judge_refuses_a_fault_of_an_order_statistic_or_a_window(sound, fault):
    name, plant, number = FAULTS[fault]
    mod, answer, frames = sound[name]
    verdict = verify.judge([(name, 0, plant(answer, frames))], {name: mod},
                           {(name, 0): answer.to_pandas()}, 0)
    assert not verdict["correct"]
    failed = verdict["numbers"][number]
    assert failed["value"] > 100 * max(failed["limit"], 1e-12), failed
