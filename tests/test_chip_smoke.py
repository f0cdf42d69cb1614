"""chip_smoke.py's own checks, without a chip: its comparison refuses what it
should, and on a platform that is no TPU the script exits non-zero and prints
no result line (the driver runs it here first, where it must fail)."""

import datetime
import os
import subprocess
import sys

import pandas as pd
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402 — imports no JAX


def _q3_frame(revenues, keys=(1, 2, 3)):
    d = datetime.date(1995, 3, 1)
    return pd.DataFrame({
        "l_orderkey": list(keys),
        "revenue": list(revenues),
        "o_orderdate": [d] * len(keys),
        "o_shippriority": [0] * len(keys),
    })


def test_compare_accepts_an_answer_within_tolerance():
    want = _q3_frame([300.0, 200.0, 100.0])
    got = _q3_frame([300.0 * (1 + 5e-7), 200.0, 100.0])
    assert 4e-7 < chip_smoke.compare("q3", got, want) < 6e-7


@pytest.mark.parametrize(
    "got, why",
    [
        (_q3_frame([300.0 * (1 + 2e-6), 200.0, 100.0]), "relative error"),
        (_q3_frame([100.0, 200.0, 300.0]), "breaks ORDER BY"),
        (_q3_frame([300.0, 200.0, 100.0], keys=(1, 2, 4)), "differs"),
        (_q3_frame([300.0, 200.0], keys=(1, 2)), "rows"),
    ],
)
def test_compare_refuses(got, why):
    want = _q3_frame([300.0, 200.0, 100.0])
    with pytest.raises(chip_smoke.SmokeFailure, match=why):
        chip_smoke.compare("q3", got, want)


def test_compare_refuses_a_value_that_is_not_finite():
    with pytest.raises(chip_smoke.SmokeFailure, match="not finite"):
        chip_smoke.compare(
            "q6",
            pd.DataFrame({"revenue": [float("nan")]}),
            pd.DataFrame({"revenue": [1.0]}),
        )


def test_compare_leaves_order_open_among_ties():
    """Rows equal on every ORDER BY column may come in either order."""
    want = _q3_frame([200.0, 200.0, 100.0], keys=(1, 2, 3))
    got = _q3_frame([200.0, 200.0, 100.0], keys=(2, 1, 3))
    assert chip_smoke.compare("q3", got, want) == 0.0


@pytest.mark.parametrize("chips", ["1", "4"])
def test_fails_without_a_tpu_and_prints_no_result(chips, tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--chips", chips, "--out", str(tmp_path)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert '"ok"' not in proc.stdout
    assert "not a TPU" in proc.stderr
