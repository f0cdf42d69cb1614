"""The readers of the window and percentile operators' metrics (PR 33): the
two counters per completed query, and the two that read the trace file again
for the device seconds of the programs these operators name, over the small
recorded trace of ``test_reduce_trace.py`` (whose programs stand in for
theirs by a pattern set here)."""

import pathlib
import re
import shutil
import types

import pytest

from layers import (
    _holistic,
    holistic_device_ms_per_query,
    holistic_roofline_share,
    holistic_rows_sorted_per_query,
    holistic_tasks_per_query,
)

HERE = pathlib.Path(__file__).resolve().parent
QUERIES = [{"error": None, "template": "g1q8", "t0": 5.0, "t1": 6.0},
           {"error": None, "template": "g1q6", "t0": 6.0, "t1": 7.0}]


def counters_obs(before, after):
    return {"queries": QUERIES, "counters_before": before,
            "counters_after": after}


def test_counters_per_completed_query():
    before = {"holistic.rows_sorted": 1e7, "holistic.tasks": 2}
    after = {"holistic.rows_sorted": 3e7, "holistic.tasks": 6}
    obs = counters_obs(before, after)
    assert holistic_rows_sorted_per_query.read(obs) == pytest.approx(1e7)
    assert holistic_tasks_per_query.read(obs) == pytest.approx(2.0)
    # declared at 0 by the program: a cell without such a query reads 0
    idle = {"holistic.rows_sorted": 0, "holistic.tasks": 0}
    assert holistic_tasks_per_query.read(counters_obs(idle, idle)) == 0.0
    # a parent's program has no such counter: the metric is left out
    old = {"agg.sort_passes": 4}
    assert holistic_tasks_per_query.read(counters_obs(old, old)) is None
    assert holistic_rows_sorted_per_query.read(counters_obs(old, old)) is None


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """obs of a traced run whose deployment wrote the recorded trace."""
    at = tmp_path / "perf-standalone-x" / "trace" / "plugins" / "profile" / "t"
    at.mkdir(parents=True)
    shutil.copy(HERE / "data" / "tiny_v5e.xplane.pb", at / "h.xplane.pb")
    monkeypatch.setattr(_holistic.tempfile, "gettempdir", lambda: str(tmp_path))
    monkeypatch.setattr(_holistic, "_seen", {})
    rows = {"x": 10_000_000}
    templates = {
        "g1q8": types.SimpleNamespace(sort_least_bytes=lambda r: r["x"] * 40),
        "g1q6": types.SimpleNamespace(sort_least_bytes=lambda r: r["x"] * 56),
    }
    return {"trace": {"busy_s": 0.5, "queries": QUERIES}, "rows": rows,
            "templates": templates, "peaks": {"hbm_bytes_per_s": 819e9}}


def test_device_seconds_of_the_named_programs(traced, monkeypatch):
    # none of the recorded programs is a window's or a percentile's
    assert _holistic.device_seconds(traced) is None
    assert holistic_device_ms_per_query.read(traced) is None
    assert holistic_roofline_share.read(traced) is None
    monkeypatch.setattr(_holistic, "_seen", {})
    monkeypatch.setattr(_holistic, "HOLISTIC", re.compile(r"^jit_"))
    seconds = _holistic.device_seconds(traced)
    assert seconds and seconds > 0
    assert holistic_device_ms_per_query.read(traced) == pytest.approx(
        1e3 * seconds / 2)
    share = holistic_roofline_share.read(traced)
    assert share == pytest.approx(100 * 96e7 / 819e9 / seconds)


def test_no_trace_no_number(traced, tmp_path, monkeypatch):
    shutil.rmtree(tmp_path / "perf-standalone-x")
    assert holistic_device_ms_per_query.read(traced) is None
    assert holistic_roofline_share.read(dict(traced, trace=None)) is None
    # templates without a sort of their own (the other cells'): nothing
    monkeypatch.setattr(_holistic, "device_seconds", lambda obs: 1.0)
    monkeypatch.setattr(holistic_roofline_share, "device_seconds",
                        lambda obs: 1.0)
    plain = dict(traced, templates={"g1q8": types.SimpleNamespace(),
                                    "g1q6": types.SimpleNamespace()})
    assert holistic_roofline_share.read(plain) is None
