"""h2oai db-benchmark, groupby task: the one table ``x`` of data set
``G1_<rows>_<k>_0_0`` (``_data/groupby-datagen.R``: no NAs, unsorted), from
the configuration's ``rows`` (N) and ``k`` (K):

    id1, id2   "id%03d" of 1..K          id4, id5   int64 in 1..K
    id3        "id%010d" of 1..N/K       id6        int64 in 1..N/K
    v1         int64 in 1..5             v2         int64 in 1..15
    v3         float64 in [0, 100), rounded to 6 decimals

Column for column what ``benchmarks/db_benchmark.py gen_g1`` makes, drawn
here from ``--seed``, with the strings gathered as bytes from the K or N/K
distinct ones (``datagen._digits``, ``_fixed``) and not formatted row by
row, and v3 drawn as a whole number of millionths, which is what rounding a
uniform draw to 6 decimals gives. It is a seeded numpy generator, not upstream's R
script: shapes, cardinalities and value domains follow it, the draws do not.
A rehearsal's number is the share of ``rows`` that is made. Imports nothing
of the program.
"""

import numpy as np
import pyarrow as pa

from datagen import _digits, _fixed

COLUMNS = ("id1", "id2", "id3", "id4", "id5", "id6", "v1", "v2", "v3")


def _ids(idx: np.ndarray, width: int, count: int) -> pa.Array:
    """``f"id{i + 1:0{width}d}"`` for every i of ``idx``, all below
    ``count``."""
    distinct = _digits(np.arange(1, count + 1), width)
    return _fixed(len(idx), "id", np.take(distinct, idx, axis=0))


def tables(cfg: dict, seed: int, rehearse: float | None = None) -> dict:
    k = int(cfg["k"])
    n = int(cfg["rows"])
    if rehearse is not None:
        n = max(k, int(n * rehearse))
    per = max(n // k, 1)
    rng = {c: np.random.default_rng(np.random.SeedSequence([seed, i]))
           for i, c in enumerate(COLUMNS)}
    return {"x": pa.table({
        "id1": _ids(rng["id1"].integers(0, k, n), 3, k),
        "id2": _ids(rng["id2"].integers(0, k, n), 3, k),
        "id3": _ids(rng["id3"].integers(0, per, n), 10, per),
        "id4": pa.array(rng["id4"].integers(1, k + 1, n)),
        "id5": pa.array(rng["id5"].integers(1, k + 1, n)),
        "id6": pa.array(rng["id6"].integers(1, per + 1, n)),
        "v1": pa.array(rng["v1"].integers(1, 6, n)),
        "v2": pa.array(rng["v2"].integers(1, 16, n)),
        "v3": pa.array(rng["v3"].integers(0, 10**8, n) / 1e6),
    })}
