"""h2oai db-benchmark, groupby task, question 2 ("sum v1 by id1:id2"), in the
SQL of upstream's ``groupby-datafusion.py``: K x K groups under two string
keys of K values each; every number of the answer is an integer, so nothing
of it has a limit but 0. See ``g1q3.py``."""

import numpy as np
import pandas as pd

from queries import g1_needs

g1_needs.check(__name__)

COLUMNS = {"x": ["id1", "id2", "v1"]}
ORDER = []  # the question states none: answers are aligned on id1, id2
LIMITS = {}  # keys and SUM(v1) are exact: ``mismatched`` holds them
VALIDATION = {}


def draw(rng) -> dict:
    return {}


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    x = f["x"]
    out = (x[["id1", "id2", "v1"]]
           .groupby(["id1", "id2"], observed=True)
           .agg(v1=("v1", "sum"))
           .reset_index())
    for key in ("id1", "id2"):  # categories sort by code
        out[key] = out[key].astype(str)
    return out.sort_values(["id1", "id2"]).reset_index(drop=True)


def least_bytes(rows: dict) -> int:
    """Two dictionary codes (4 each) and an int64 of every row."""
    return rows["x"] * (4 + 4 + 8)
