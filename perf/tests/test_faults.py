"""A whole run with the harness's look for a chip skipped (SF 0.01 on
whatever JAX finds) comes out correct; with the timed path broken underneath
it comes out not correct. The faults a query engine can have: an answer
altered where it is produced, rows left out, and a stale answer (another
parameter draw's) served in place of the query's own. Each cell of templates
of its own has them planted in its own templates."""

import argparse

import pyarrow as pa
import pyarrow.compute as pc
import pytest

import run


USUAL = {"altered": "q6", "rows_left_out": "q3", "stale": "q1"}
FLOAT_COLUMN = {"q6": 0, "q3": 1}  # revenue


def drive(monkeypatch, fault=None, workload="tpch-sf1-mem.power",
          where=None):
    """One rehearsal of ``workload`` with ``fault`` planted in the answers of
    the template ``where`` (the fault's usual one without it)."""
    from ballista_tpu.client import context

    sound = context.RemoteDataFrame.collect
    seen: dict[str, pa.Table] = {}

    def broken(self):
        table = sound(self)
        kind = "q6" if table.num_columns == 1 else (
            "q1" if table.num_columns == 10 else "q3")
        if kind != (where or USUAL[fault]):
            return table
        if fault == "altered":
            at = FLOAT_COLUMN[kind]
            col = pc.multiply(table.column(at), 1 + 1e-6)
            return table.set_column(at, table.schema.field(at), col)
        if fault == "rows_left_out":
            return table.slice(0, table.num_rows - 1)
        return seen.setdefault(kind, table)  # stale

    if fault:
        monkeypatch.setattr(context.RemoteDataFrame, "collect", broken)
    return run.run_cell(argparse.Namespace(
        workload=workload, seed=2_400_000_011, seconds=4.0,
        trace=0, rehearse_sf=0.01,
    ))


def test_sound_run_is_correct(monkeypatch):
    result = drive(monkeypatch)
    assert result["correct"] and result["failed"] == 0
    assert set(result["metrics"]) == {"queries_per_s", "geomean_ms", "setup_s"}


@pytest.mark.parametrize("fault", ["altered", "rows_left_out", "stale"])
def test_broken_path_is_not_correct(monkeypatch, fault):
    result = drive(monkeypatch, fault)
    assert not result["correct"]
    n = result["compared"]
    if fault == "altered":
        assert n["relerr_q6"]["value"] > n["relerr_q6"]["limit"]
        assert n["mismatched"]["value"] == 0
    else:
        assert n["mismatched"]["value"] > 0


@pytest.mark.parametrize("fault", [None, "altered", "rows_left_out", "stale"])
def test_the_q3_cell_under_four_callers(monkeypatch, fault):
    result = drive(monkeypatch, fault, "tpch-sf1-mem.load-q3-4c", "q3")
    n = result["compared"]
    assert set(result["metrics"]) == {"queries_per_s", "setup_s"}
    if fault is None:
        assert result["correct"] and result["failed"] == 0
    elif fault == "altered":
        assert not result["correct"]
        assert n["relerr_q3"]["value"] > n["relerr_q3"]["limit"]
    else:
        assert not result["correct"] and n["mismatched"]["value"] > 0
