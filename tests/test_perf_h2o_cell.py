"""The benchmark's files for the cell ``h2o-g1-1e7-mem.groupby``, held by the
tier-1 run: the ``h2o_g1`` generator has the published shape and follows its
seed, a configuration's ``session_settings`` reach the session, the cell runs
in rehearsal through the harness's own ``run_cell`` and comes out as the
comparison said, and ``verify.judge`` refuses the faults a group-by can have
(a group dropped, a key off by one, a sum in float32) in each template."""

import argparse
import json
import pathlib
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
import deployments  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402
import verify  # noqa: E402

CELL = "h2o-g1-1e7-mem.groupby"
G1 = {"dataset": "h2o_g1", "rows": 100_000, "k": 100}


def cell_config() -> dict:
    return json.loads((PERF / "configs" / "h2o-g1-1e7-mem.json").read_text())


# -- the data set ---------------------------------------------------------------


def test_h2o_g1_has_the_published_shape():
    x = dataset.load(G1).tables(G1, 2_900_000_031)["x"]
    assert x.num_rows == 100_000
    assert [(f.name, str(f.type)) for f in x.schema] == [
        ("id1", "string"), ("id2", "string"), ("id3", "string"),
        ("id4", "int64"), ("id5", "int64"), ("id6", "int64"),
        ("v1", "int64"), ("v2", "int64"), ("v3", "double"),
    ]
    x.validate(full=True)
    distinct = {c: pc.count_distinct(x[c]).as_py() for c in x.column_names}
    # K values, and N/K of them, each drawn a hundred times over
    assert [distinct[c] for c in ("id1", "id2", "id4", "id5")] == [100] * 4
    assert distinct["id3"] == distinct["id6"] == 1000
    assert distinct["v1"] == 5 and distinct["v2"] == 15
    for c, lo, hi in (("id4", 1, 100), ("id6", 1, 1000), ("v1", 1, 5),
                      ("v2", 1, 15)):
        assert pc.min_max(x[c]).as_py() == {"min": lo, "max": hi}
    # "id%03d", "id%010d" and 6 decimals, as upstream's generator writes them
    assert pc.min_max(x["id1"]).as_py() == {"min": "id001", "max": "id100"}
    assert pc.min_max(x["id3"]).as_py() == {"min": "id0000000001",
                                            "max": "id0000001000"}
    assert pc.all(pc.match_substring_regex(x["id3"], r"^id\d{10}$")).as_py()
    v3 = x["v3"].to_numpy()
    assert 0 <= v3.min() and v3.max() < 100
    assert (abs(v3 * 1e6 - (v3 * 1e6).round()) < 1e-6).all()
    assert len(set(v3.tolist())) > 99_000


def test_h2o_g1_same_seed_same_table_other_seed_another():
    a, b, c = (dataset.load(G1).tables(G1, s)["x"] for s in (7, 7, 8))
    assert a.equals(b)
    for column in a.column_names:
        assert not a[column].equals(c[column]), column
    # a rehearsal makes its share of the rows, never fewer than K
    assert dataset.load(G1).tables(G1, 7, 0.1)["x"].num_rows == 10_000
    assert dataset.load(G1).tables(G1, 7, 1e-9)["x"].num_rows == 100


def test_the_cell_is_the_published_data_set_cut_in_rows_and_queries_only():
    cfg = cell_config()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "h2o-g1-1e7-mem")
    assert entry["file"] == "perf/configs/h2o-g1-1e7-mem.json"
    assert entry["reduced"] == ["rows", "queries"] == list(cfg["reduced"])
    assert entry["source"] == cfg["source"]
    assert (cfg["dataset"], cfg["rows"], cfg["k"]) == ("h2o_g1", 10**7, 100)
    assert cfg["rows"] in cfg["rows_published"]
    mix = traffic.load("groupby")
    assert cfg["queries"] == len(mix["templates"]) <= cfg["queries_published"]
    assert (mix["clients"], mix["pool"], mix["param_seed"]) == (1, 1, 29)
    # the questions, letter for letter as benchmarks/db_benchmark.py has them
    sys.path.insert(0, str(ROOT / "benchmarks"))
    from db_benchmark import GROUPBY_QUERIES

    for name, mod in traffic.load_templates(mix["templates"]).items():
        assert mod.SQL.strip() == GROUPBY_QUERIES[name.removeprefix("g1")]
        assert mod.ORDER == [] and mod.draw(None) == {}


# -- session settings -------------------------------------------------------------


def test_session_settings_reach_the_session():
    config = deployments.session_config(
        {"session_settings": {"ballista.shuffle.partitions": 2}})
    assert config.settings() == {"ballista.shuffle.partitions": "2"}
    assert config.default_shuffle_partitions() == 2
    flags = deployments.session_config(
        {"session_settings": {"ballista.repartition.joins": False}})
    assert flags.repartition_joins() is False
    # {} is the program's defaults, and the new cell's: nothing raises the
    # aggregate's capacity for it
    assert cell_config()["session_settings"] == {}
    assert deployments.session_config(cell_config()) is None
    assert deployments.session_config({}) is None


@pytest.mark.parametrize("settings,named", [
    ({"ballista.no.such.key": 1}, "ballista.no.such.key"),
    ({"ballista.shuffle.partitions": "many"}, "many"),
])
def test_an_unknown_setting_ends_the_run(settings, named):
    with pytest.raises(SystemExit) as stopped:
        deployments.session_config({"session_settings": settings})
    assert named in str(stopped.value.code)


# -- the cell in rehearsal --------------------------------------------------------


def test_the_cell_in_rehearsal_is_what_the_comparison_said(capsys, monkeypatch):
    """``--rehearse-sf 0.01``: 1e5 rows, 1e3 groups (1e4 in g1q2), through
    ``BallistaContext.standalone`` as the chip run goes."""
    # a run points the program's hint file at a directory of its own
    # (deployments.fresh_hints) and, being a process, never points it back:
    # the tests that follow in this worker get conftest's "off" again
    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", "off")
    result = run.run_cell(argparse.Namespace(
        workload=CELL, seed=2_900_000_032, seconds=1.0, trace=0,
        rehearse_sf=0.01,
    ))
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 4 and result["attempted"] % 4 == 0
    compared = result["compared"]
    assert set(compared) == {"relerr_g1q3", "relerr_g1q5", "mismatched",
                             "failed", "answered"}
    for name in ("relerr_g1q3", "relerr_g1q5"):
        assert compared[name]["value"] <= compared[name]["limit"]
    assert compared["mismatched"]["value"] == 0
    assert set(result["metrics"]) == {"queries_per_s", "setup_s"}
    err = capsys.readouterr().err.splitlines()
    assert any(line.startswith("perf: data: h2o_g1 ") and "x 100000 rows"
               in line for line in err)
    assert sum(line.startswith("perf: stages: ") for line in err) == 1


def test_a_traced_run_of_the_cell_reports_every_metric_it_is_held_to(tmp_path):
    """As ``tests/test_perf_h2o_adv_cell.py`` does for its cell: the
    ``--trace 1`` line of a rehearsal has every counter and host-clock
    metric the cell is held to, ``status_polls_per_query`` (PR 34),
    ``dict_merge_ms_per_query`` (the string keys) and
    ``agg_dense_factored_passes_per_query`` (PR 37) among them."""
    import os
    import subprocess

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    held_to = {m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", [CELL])
               and m["source"] != "device_trace" and m["layer"] != "device"}
    assert {"status_polls_per_query", "dict_merge_ms_per_query",
            "agg_dense_factored_passes_per_query"} <= held_to
    done = subprocess.run(
        [sys.executable, str(PERF / "run.py"), "--workload", CELL,
         "--seed", "3400000034", "--seconds", "1", "--trace", "1",
         "--rehearse-sf", "0.01"],
        cwd=ROOT, capture_output=True, text=True, timeout=280,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "BALLISTA_TPU_HINT_CACHE": "off", "TMPDIR": str(tmp_path)})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert held_to - set(line["metrics"]) == set()
    # every query is asked about at least once, and a held ask is one
    assert 1 <= line["metrics"]["status_polls_per_query"]["value"] < 10
    # g1q2's 10,201 slots (PR 37): its dense passes reduce on the MXU
    assert line["metrics"]["agg_dense_factored_passes_per_query"]["value"] > 0
    # no join: the builds' gathers read 0, not nothing
    assert line["metrics"]["join_build_gather_mb_per_query"]["value"] == 0


# -- planted faults -----------------------------------------------------------------


@pytest.fixture(scope="module")
def sound():
    """template -> (module, its reference over seeded data as an Arrow
    table: the answer a sound program gives)."""
    mix = traffic.load("groupby")
    templates = traffic.load_templates(mix["templates"])
    tables = dataset.load(G1).tables(G1, 2_900_000_033)
    frames = verify.frames(tables, templates)
    return {
        name: (mod, pa.Table.from_pandas(mod.reference(frames, {}),
                                         preserve_index=False))
        for name, mod in templates.items()
    }


def dropped_group(name, table):
    return table.slice(0, table.num_rows - 1)


def key_off_by_one(name, table):
    key = table.column(0)
    if pa.types.is_integer(key.type):
        changed = pc.add(key, pa.array([1] + [0] * (len(key) - 1)))
    else:  # the first row under the last row's key
        changed = pa.chunked_array([key.slice(len(key) - 1, 1), key.slice(1)])
    return table.set_column(0, table.schema.field(0), changed)


def sum_in_float32(name, table):
    """The last column as float32 would have left it: for an integer column
    the same whole numbers, which is the point of keeping sums of integers
    exact."""
    at = table.num_columns - 1
    col = table.column(at)
    if not pa.types.is_floating(col.type):
        col = pc.add(col, pa.array([1] + [0] * (len(col) - 1)))
        return table.set_column(at, table.schema.field(at), col)
    low = pa.array(col.to_numpy().astype(np.float32).astype(np.float64))
    return table.set_column(at, table.schema.field(at), low)


FAULTS = {"dropped_group": dropped_group, "key_off_by_one": key_off_by_one,
          "sum_in_float32": sum_in_float32}


@pytest.mark.parametrize("name", ["g1q3", "g1q5", "g1q2", "g1q7"])
@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_judge_refuses_a_group_by_fault(sound, name, fault):
    mod, answer = sound[name]
    reference = answer.to_pandas()
    if fault:
        answer = FAULTS[fault](name, answer)
    verdict = verify.judge([(name, 0, answer)], {name: mod},
                           {(name, 0): reference}, 0)
    numbers = verdict["numbers"]
    if fault is None:
        assert verdict["correct"] and numbers["mismatched"]["value"] == 0
        return
    assert not verdict["correct"]
    floats = fault == "sum_in_float32" and f"relerr_{name}" in numbers
    if floats:
        n = numbers[f"relerr_{name}"]
        # a sum rounded to float32 is off by up to 6e-8 of itself
        assert n["value"] > 20 * n["limit"] and numbers["mismatched"]["value"] == 0
    else:
        assert numbers["mismatched"]["value"] == 1
