"""A configuration's data set and session settings come from its file: the
TPC-H module is ``datagen.gen_all``, ``h2o_g1`` has the published shape, a
cell over it runs from data files alone, and a name the harness or the
program does not know ends the run before any work."""

import argparse
import json

import pyarrow.compute as pc
import pytest

import datagen
import dataset
import deployments
import run
import traffic
from conftest import PERF

DATA = PERF / "tests" / "data"
G1 = {"dataset": "h2o_g1", "rows": 100_000, "k": 100}


@pytest.mark.parametrize("seed", [42, 3_000_000_019])
def test_tpch_is_datagen(seed):
    cfg = {"scale_factor": 0.01}  # no "dataset": the two files there are
    ours = dataset.load(cfg).tables(cfg, seed)
    theirs = datagen.gen_all(0.01, seed)
    assert list(ours) == list(theirs)
    for name, table in theirs.items():
        for column in table.column_names:
            assert ours[name][column].equals(table[column]), (name, column)
    small = dataset.load(cfg).tables({"scale_factor": 1}, seed, 0.01)
    assert small["lineitem"].equals(theirs["lineitem"])


def test_h2o_g1_has_the_published_shape():
    x = dataset.load(G1).tables(G1, 2_800_000_011)["x"]
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


def cell_table(tmp_path, **changed):
    """``data/cells.json`` with the toy configuration's file changed."""
    cells = json.loads((DATA / "cells.json").read_text())
    cfg = json.loads((DATA / "configs" / "h2o-g1-toy.json").read_text())
    cfg.update(changed)
    (tmp_path / "toy.json").write_text(json.dumps(cfg))
    cells["configs"][0]["file"] = str(tmp_path / "toy.json")
    (tmp_path / "cells.json").write_text(json.dumps(cells))
    return tmp_path / "cells.json"


def toy(monkeypatch, bench_file):
    """The toy cell in rehearsal: its mix and template are found under
    ``perf/tests/data/`` as the real ones are under ``perf/``."""
    monkeypatch.setattr(traffic, "HERE", DATA)
    monkeypatch.syspath_prepend(str(DATA))
    return run.run_cell(argparse.Namespace(
        workload="h2o-g1-toy.g1q3", seed=2_800_000_012, seconds=2.0,
        trace=0, rehearse_sf=0.1,
    ), bench_file)


def test_a_cell_over_another_data_set_is_files_only(monkeypatch, capsys):
    result = toy(monkeypatch, DATA / "cells.json")
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert result["compared"]["relerr_g1q3"]["value"] < 1e-9
    assert result["compared"]["mismatched"]["value"] == 0
    assert set(result["metrics"]) == {"queries_per_s", "setup_s"}
    err = capsys.readouterr().err.splitlines()
    assert any(line.startswith("perf: data: h2o_g1 ") and "x 100000 rows"
               in line for line in err)
    # where the run's seconds went, before the numbers compared, which stay
    # the last lines
    stages = [i for i, line in enumerate(err)
              if line.startswith("perf: stages: ")]
    assert len(stages) == 1 and err[stages[0]].endswith(" s of 360")
    for name in ("start", "data", "deploy", "warm-up", "window",
                 "stop and history", "verification"):
        assert f"{name} " in err[stages[0]]
    compared = [i for i, line in enumerate(err)
                if line.startswith("perf: compared ")]
    assert compared and stages[0] < compared[0]
    assert compared[-1] == len(err) - 1


def test_session_settings_reach_the_session():
    toy_cfg = json.loads((DATA / "configs" / "h2o-g1-toy.json").read_text())
    config = deployments.session_config(toy_cfg)
    assert config.settings() == {"ballista.shuffle.partitions": "2"}
    assert config.default_shuffle_partitions() == 2
    assert deployments.session_config({"session_settings": {}}) is None
    assert deployments.session_config({}) is None
    flags = deployments.session_config(
        {"session_settings": {"ballista.repartition.joins": False}})
    assert flags.repartition_joins() is False


@pytest.mark.parametrize("changed,named", [
    ({"dataset": "no_such_data"}, "no_such_data"),
    ({"session_settings": {"ballista.no.such.key": 1}}, "ballista.no.such.key"),
    ({"session_settings": {"ballista.shuffle.partitions": "many"}}, "many"),
])
def test_an_unknown_name_ends_the_run_before_any_work(
        monkeypatch, tmp_path, changed, named):
    def no_work(*a, **k):
        raise AssertionError("the run went on to work")

    monkeypatch.setattr(deployments.Standalone, "start", no_work)
    with pytest.raises(SystemExit) as stopped:
        toy(monkeypatch, cell_table(tmp_path, **changed))
    assert named in str(stopped.value.code)
