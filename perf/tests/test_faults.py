"""A whole run with the harness's look for a chip skipped (SF 0.01 on
whatever JAX finds) comes out correct; with the timed path broken underneath
it comes out not correct. The faults a query engine can have: an answer
altered where it is produced, rows left out, and a stale answer (another
parameter draw's) served in place of the query's own."""

import argparse

import pyarrow as pa
import pyarrow.compute as pc
import pytest

import run


def drive(monkeypatch, fault=None):
    from ballista_tpu.client import context

    sound = context.RemoteDataFrame.collect
    seen: dict[str, pa.Table] = {}

    def broken(self):
        table = sound(self)
        kind = "q6" if table.num_columns == 1 else (
            "q1" if table.num_columns == 10 else "q3")
        if fault == "altered" and kind == "q6":
            col = pc.multiply(table.column(0), 1 + 1e-6)
            return table.set_column(0, table.schema.field(0), col)
        if fault == "rows_left_out" and kind == "q3":
            return table.slice(0, table.num_rows - 1)
        if fault == "stale" and kind == "q1":
            return seen.setdefault(kind, table)
        return table

    if fault:
        monkeypatch.setattr(context.RemoteDataFrame, "collect", broken)
    return run.run_cell(argparse.Namespace(
        workload="tpch-sf1-mem.power", seed=2_400_000_011, seconds=4.0,
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
