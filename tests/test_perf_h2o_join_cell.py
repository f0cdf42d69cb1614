"""The benchmark's files for the cell ``h2o-j1-1e7-mem.join``, held by the
tier-1 run as ``test_perf_tpch_corr_cell.py`` holds the correlated cell's:
the ``h2o_j1`` generator has the published tables, key sets and split and
follows its seed, the configuration is the published data set cut in rows,
questions and select list only, the two ``.sql`` files join as upstream's
questions 5 and 4 do, the cell runs in rehearsal through the harness's own
``run_cell`` and comes out as the comparison said, its traced rehearsal
reports every metric the benchmark holds it to, the join's readers tell a
program without the counters from one that counted nothing, and
``verify.judge`` refuses the faults a join can have: a row lost, a
right-side value read wrong, a sum in float32, and the right side's payload
paired with the matched left rows in another order."""

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

CONFIG = "h2o-j1-1e7-mem"
CELL = f"{CONFIG}.join"
TEMPLATES = ["j1q5", "j1q4"]
# the four counter metrics every cell reports, then the two of the trace
# that only this cell's reader finds something to read in
EVERY_CELLS = ["join_build_rows_per_query", "join_builds_per_query",
               "join_probe_rows_per_query", "join_key_remaps_per_query"]
TRACED = ["join_device_ms_per_query", "join_roofline_share"]
J1 = {"dataset": "h2o_j1", "rows": 20_000}
SEED = 4_200_000_031


def cell_config() -> dict:
    return json.loads((PERF / "configs" / f"{CONFIG}.json").read_text())


# -- the data set ---------------------------------------------------------------


def test_h2o_j1_has_the_published_tables():
    t = dataset.load(J1).tables(J1, SEED)
    assert {n: (x.num_rows, x.column_names) for n, x in t.items()} == {
        "x": (20_000, ["id1", "id2", "id3", "id4", "id5", "id6", "v1"]),
        "small": (10, ["id1", "id4", "v2"]),
        "medium": (20, ["id1", "id2", "id4", "id5", "v2"]),
        "big": (20_000, ["id1", "id2", "id3", "id4", "id5", "id6", "v2"]),
    }
    for x in t.values():
        x.validate(full=True)
        for f in x.schema:
            want = ("int64" if f.name in ("id1", "id2", "id3")
                    else "string" if f.name.startswith("id") else "double")
            assert str(f.type) == want, f
        # id4-id6 are "id" + id1-id3, unpadded
        for k in (1, 2, 3):
            if f"id{k}" in x.column_names:
                s = pc.binary_join_element_wise(
                    "id", x[f"id{k}"].cast(pa.string()), "")
                assert x[f"id{k + 3}"].equals(s)
        v = x.column(x.num_columns - 1).to_numpy()
        assert 0 <= v.min() and v.max() < 100
        assert (abs(v * 1e6 - (v * 1e6).round()) < 1e-6).all()

    def keys(table, col):
        return set(t[table][col].to_numpy().tolist())

    # split_xlr: 1.1 n keys, 0.9 n common, 0.1 n on each side only
    for col, n, small, big in (("id1", 10, "small", "big"),
                               ("id2", 20, "medium", "big"),
                               ("id3", 20_000, None, "big")):
        left, right = keys("x", col), keys(big, col)
        assert len(left) == len(right) == n  # every key drawn at least once
        assert len(left & right) == n * 9 // 10
        assert left | right == set(range(1, n + n // 10 + 1))
        if small is not None:
            assert keys(small, col) == right
    # each key of id3 once a side, of medium's id2 once, of small's id1 once
    for table, col in (("x", "id3"), ("big", "id3"), ("medium", "id2"),
                       ("small", "id1")):
        assert len(keys(table, col)) == t[table].num_rows


def test_h2o_j1_same_seed_same_tables_other_seed_others():
    a, b, c = (dataset.load(J1).tables(J1, s) for s in (7, 7, 8))
    for name in a:
        assert a[name].equals(b[name])
        assert not a[name].equals(c[name]), name
    # a rehearsal makes its share of the rows; the key sets keep 10 keys
    t = dataset.load(J1).tables(J1, 7, 0.5)
    assert t["x"].num_rows == t["big"].num_rows == 10_000
    assert t["medium"].num_rows == t["small"].num_rows == 10


# -- the configuration and the cell ------------------------------------------------


def test_the_cell_is_the_published_data_set_cut_in_rows_queries_select():
    cfg = cell_config()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    assert entry["reduced"] == ["rows", "queries", "select_list"]
    assert list(cfg["reduced"]) == entry["reduced"]
    assert entry["source"] == cfg["source"]
    assert (cfg["dataset"], cfg["rows"]) == ("h2o_j1", 10**7)
    assert cfg["rows"] == min(cfg["rows_published"])
    assert cfg["tables"] == ["x", "small", "medium", "big"]
    # the deployment and session of the two h2o cells
    g1 = json.loads((PERF / "configs" / "h2o-g1-1e7-mem.json").read_text())
    for key in ("deployment", "concurrent_tasks", "task_scheduling_policy",
                "session_settings", "chips"):
        assert cfg[key] == g1[key], key
    assert set(cfg["guarantees"]) >= {"row_count", "integer_sum",
                                      "float_sums", "result_cache"}
    assert "generator" in cfg["assumed"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "join", 1)
    mix = traffic.load("join")
    assert mix["templates"] == TEMPLATES
    assert cfg["queries"] == len(mix["templates"]) < cfg["queries_published"]
    assert (mix["clients"], mix["pool"], mix["order"]) == (1, 1, "shuffled")
    # the six per-layer metrics, each with a reader, appended together
    # after what was there (later PRs append after them)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(EVERY_CELLS[0])
    assert names[at - 1] == "subquery_agg_reduced_per_query"
    assert names[at:at + 6] == EVERY_CELLS + TRACED
    for name in EVERY_CELLS + TRACED:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (PERF / "layers" / f"{name}.py").is_file()
        assert m["moves"] == "queries_per_s"
        if name in EVERY_CELLS:
            assert m["source"] == "program_counter" and "workloads" not in m
        else:
            assert m["source"] == "device_trace"
            assert m["workloads"] == [CELL]
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index(CONFIG) == configs.index("tpch-sf1-corr-mem") + 1
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) == cells.index("tpch-sf1-corr-mem.correlated") + 1


@pytest.mark.parametrize("name,right,on", [
    ("j1q5", "big", "x.id3 = big.id3"),
    ("j1q4", "medium", "x.id5 = medium.id5"),
])
def test_a_template_is_upstreams_join_with_the_checks_select_list(
        name, right, on):
    mod = traffic.load_templates([name])[name]
    assert mod.SQL.strip() == (
        f"SELECT COUNT(*) AS n, SUM(x.v1) AS v1, SUM({right}.v2) AS v2, "
        f"SUM({right}.id2) AS id2, SUM(x.id2 * {right}.id2) AS pair "
        f"FROM x JOIN {right} ON {on}")
    assert mod.ORDER == [] and mod.draw(None) == {}
    assert mod.LIMITS == {f"relerr_{name}": (None, 1e-10)}
    rows = {"x": 10**7, "big": 10**7, "medium": 10**4, "small": 10}
    # a few hundred MB each at the cell's size: the roofline share's bytes
    assert 1e8 < mod.least_bytes(rows) < 1e9
    assert 1e8 < mod.join_least_bytes(rows) < 1e9


# -- the cell in rehearsal --------------------------------------------------------


def test_the_cell_in_rehearsal_is_what_the_comparison_said(capsys, monkeypatch):
    """``--rehearse-sf 0.002``: 20,000 rows of x and big, 20 of medium,
    through ``BallistaContext.standalone`` as the chip run goes."""
    monkeypatch.setenv("BALLISTA_TPU_HINT_CACHE", "off")
    result = run.run_cell(argparse.Namespace(
        workload=CELL, seed=SEED, seconds=1.0, trace=0, rehearse_sf=0.002,
    ))
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 2 and result["attempted"] % 2 == 0
    assert set(result["compared"]) == {"relerr_j1q5", "relerr_j1q4",
                                       "mismatched", "failed", "answered"}
    for name in ("relerr_j1q5", "relerr_j1q4"):
        assert result["compared"][name]["value"] <= 1e-14
    assert result["compared"]["mismatched"] == {"value": 0, "limit": 0}
    assert set(result["metrics"]) == {"queries_per_s", "setup_s"}
    err = capsys.readouterr().err.splitlines()
    assert any(line.startswith("perf: data: h2o_j1 ") and "x 20000 rows"
               in line and "medium 20 rows" in line for line in err)
    assert sum(line.startswith("perf: stages: ") for line in err) == 1


def test_a_traced_run_of_the_cell_reports_every_metric_it_is_held_to(tmp_path):
    """Every per-layer metric with no ``workloads`` key, or with one that
    lists the cell, that a rehearsal on a CPU can read (the device's trace
    and memory it has not); the four join counters at what the plan
    makes of the data."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    held_to = {m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", [CELL])
               and m["source"] != "device_trace" and m["layer"] != "device"}
    assert set(EVERY_CELLS) <= held_to
    assert {"noninner_join_probe_rows_per_query", "join_self_ms_per_query",
            "dict_merge_ms_per_query"} - held_to == {"dict_merge_ms_per_query"}
    done = subprocess.run(
        [sys.executable, str(PERF / "run.py"), "--workload", CELL,
         "--seed", "4200000034", "--seconds", "1", "--trace", "1",
         "--rehearse-sf", "0.002"],
        cwd=ROOT, capture_output=True, text=True, timeout=280,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "BALLISTA_TPU_HINT_CACHE": "off", "TMPDIR": str(tmp_path)})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert held_to - set(line["metrics"]) == set()
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # a round is one j1q5 and one j1q4: every row of x probes once in
    # each; j1q5 builds big's 20,000 rows, j1q4 medium's 20 in each task
    assert metrics["join_probe_rows_per_query"] == 20_000
    assert 10_000 < metrics["join_build_rows_per_query"] < 10_100
    assert metrics["join_builds_per_query"] >= 2
    assert metrics["join_key_remaps_per_query"] >= 1
    assert metrics["noninner_join_probe_rows_per_query"] == 0
    assert metrics["join_self_ms_per_query"] > 0
    # every build is exact and gathers its key column: j1q5's int64 key,
    # 8 bytes a slot of capacities past its rows, holds the most
    gathered = metrics["join_build_gather_mb_per_query"] * 1e6
    assert gathered >= 8 * metrics["join_build_rows_per_query"]


@pytest.mark.parametrize("name,counter", [
    ("join_build_rows_per_query", "join.build_rows"),
    ("join_builds_per_query", "join.builds"),
    ("join_probe_rows_per_query", "join.probe_rows"),
    ("join_key_remaps_per_query", "join.key_remaps"),
])
def test_a_join_reader(name, counter):
    """A number per completed query where the program counts, 0 where it
    declares the counter and no join ran, ``None`` where the program has no
    such counter (a parent commit), so that the metric is left out."""
    import importlib

    reader = importlib.import_module(f"layers.{name}")
    done = {"error": None, "template": "j1q5", "t0": 10.0, "t1": 11.0}
    failed = {"error": "Boom", "template": "j1q4", "t0": 11.0, "t1": 11.5}

    def read(before, after, queries=(done, done, failed)):
        return reader.read({"queries": list(queries),
                            "counters_before": before,
                            "counters_after": after})

    assert read({counter: 3}, {counter: 7}) == pytest.approx(2.0)
    assert read({counter: 0}, {counter: 0}) == 0.0
    old = {"join.noninner.probe_rows": 0, "agg.sort_passes": 4}
    assert read(old, old) is None
    assert read(None, None) is None
    assert read({counter: 0}, {counter: 2}, [failed]) is None


def test_the_build_gather_reader():
    """Megabytes per completed query where the program counts, 0 where it
    declares the counter and no join ran, ``None`` where the program has no
    such counter (a parent commit), so that the metric is left out."""
    from layers import join_build_gather_mb_per_query as reader

    counter = "join.build_gather_bytes"
    done = {"error": None, "template": "j1q5", "t0": 10.0, "t1": 11.0}
    failed = {"error": "Boom", "template": "j1q4", "t0": 11.0, "t1": 11.5}

    def read(before, after, queries=(done, done, failed)):
        return reader.read({"queries": list(queries),
                            "counters_before": before,
                            "counters_after": after})

    assert read({counter: 0}, {counter: 134_217_728}) == pytest.approx(
        67.108864)
    assert read({counter: 0}, {counter: 0}) == 0.0
    old = {"join.builds": 0, "agg.sort_passes": 4}
    assert read(old, old) is None
    assert read({counter: 0}, {counter: 2}, [failed]) is None


def test_the_trace_readers_find_nothing_without_a_trace():
    from layers import join_device_ms_per_query, join_roofline_share

    obs = {"trace": None, "peaks": {"hbm_bytes_per_s": 819e9}}
    assert join_device_ms_per_query.read(obs) is None
    assert join_roofline_share.read(obs) is None


# -- planted faults -----------------------------------------------------------------


@pytest.fixture(scope="module")
def j1_frames():
    """The templates' modules and the frames of seeded data their
    references read."""
    templates = traffic.load_templates(TEMPLATES)
    return templates, verify.frames(dataset.load(J1).tables(J1, SEED),
                                    templates)


@pytest.fixture(scope="module")
def sound(j1_frames):
    """template -> (module, its reference over seeded data as an Arrow
    table: the answer a sound program gives)."""
    templates, frames = j1_frames
    return {
        name: (mod, pa.Table.from_pandas(mod.reference(frames, {}),
                                         preserve_index=False))
        for name, mod in templates.items()
    }


def _set(table, name, value):
    at = table.column_names.index(name)
    return table.set_column(at, table.schema.field(at),
                            pa.array([value], table.schema.field(at).type))


def row_lost(table):
    """One matched row fewer: the count, and its share of each sum."""
    return _set(table, "n", table["n"][0].as_py() - 1)


def wrong_partner(table):
    """A right-side row read wrong: the counts and the float sums as they
    were, the integer sum moved by a difference of id2."""
    return _set(table, "id2", table["id2"][0].as_py() + 3)


def sum_in_float32(table):
    v = table["v1"][0].as_py()
    return _set(table, "v1", float(np.float32(v)) if np.float32(v) != v
                else v * (1 + 1e-7))


FAULTS = {"row_lost": (row_lost, "mismatched"),
          "wrong_partner": (wrong_partner, "mismatched"),
          "sum_in_float32": (sum_in_float32, "relerr")}


@pytest.mark.parametrize("name", TEMPLATES)
@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_judge_refuses_a_join_fault(sound, name, fault):
    mod, answer = sound[name]
    reference = answer.to_pandas()
    if fault:
        plant, number = FAULTS[fault]
        answer = plant(answer)
    verdict = verify.judge([(name, 0, answer)], {name: mod},
                           {(name, 0): reference}, 0)
    numbers = verdict["numbers"]
    if fault is None:
        assert verdict["correct"] and numbers["mismatched"]["value"] == 0
        return
    assert not verdict["correct"]
    if number == "relerr":
        n = numbers[f"relerr_{name}"]
        assert n["value"] > 100 * n["limit"]
        assert numbers["mismatched"]["value"] == 0
    else:
        assert numbers["mismatched"]["value"] == 1


# the right side and the key of each template's join
RIGHT = {"j1q5": ("big", "id3"), "j1q4": ("medium", "id5")}


def payload_permuted(name, frames, seed):
    """The frames with the right side's payload (v2, id2) moved among its
    matched rows by a random bijection: each matched left row gets another
    right row's payload, as a build gathered through a wrong permutation
    would pair them."""
    right, key = RIGHT[name]
    r = frames[right].copy()
    left_keys = frames["x"][key]
    if key == "id5":  # strings: compare the entries, not the codes
        matched = r[key].astype(str).isin(left_keys.cat.categories)
    else:
        matched = r[key].isin(left_keys)
    rows = np.flatnonzero(matched.to_numpy())
    moved = np.random.default_rng(seed).permutation(rows)
    for col in ("v2", "id2"):
        values = r[col].to_numpy().copy()
        values[rows] = values[moved]
        r[col] = values
    return {**frames, right: r}


@pytest.mark.parametrize("name", TEMPLATES)
def test_judge_refuses_a_right_payload_paired_in_another_order(
        j1_frames, sound, name):
    """The pairing fault a one-sided number cannot see: in j1q5, a join of
    one row to one, the count, both float sums and ``SUM(big.id2)`` stay as
    they were and only the product's sum moves; ``verify.judge`` refuses
    it in both templates."""
    templates, frames = j1_frames
    mod, answer = sound[name]
    wrong = mod.reference(payload_permuted(name, frames, SEED), {})
    right = answer.to_pandas()
    assert wrong.pair[0] != right.pair[0]
    if name == "j1q5":
        assert (wrong.n[0], wrong.id2[0]) == (right.n[0], right.id2[0])
        for col in ("v1", "v2"):
            assert wrong[col][0] == pytest.approx(right[col][0], rel=1e-12)
    verdict = verify.judge(
        [(name, 0, pa.Table.from_pandas(wrong, preserve_index=False))],
        {name: mod}, {(name, 0): right}, 0)
    assert not verdict["correct"]
    assert verdict["numbers"]["mismatched"]["value"] == 1
    if name == "j1q5":  # the product's column, and it alone
        at = list(right.columns).index("pair")
        assert verdict["first_mismatch"].endswith(f"column {at} differs")
