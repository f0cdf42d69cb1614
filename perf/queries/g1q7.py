"""h2oai db-benchmark, groupby task, question 7 ("max v1 - min v2 by id3"),
in the SQL of upstream's ``groupby-datafusion.py``: N/K groups under a string
key, arithmetic over two aggregates; the answer is integers, so nothing of it
has a limit but 0. See ``g1q3.py``."""

import numpy as np
import pandas as pd

from queries import g1_needs

g1_needs.check(__name__)

COLUMNS = {"x": ["id3", "v1", "v2"]}
ORDER = []  # the question states none: answers are aligned on id3
LIMITS = {}  # keys and MAX(v1) - MIN(v2) are exact: ``mismatched`` holds them
VALIDATION = {}


def draw(rng) -> dict:
    return {}


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    x = f["x"]
    g = (x[["id3", "v1", "v2"]]
         .groupby("id3", observed=True)
         .agg(hi=("v1", "max"), lo=("v2", "min"))
         .reset_index())
    out = pd.DataFrame({"id3": g.id3.astype(str),  # categories sort by code
                        "range_v1_v2": g.hi - g.lo})
    return out.sort_values("id3").reset_index(drop=True)


def least_bytes(rows: dict) -> int:
    """A dictionary code (4) and two int64 of every row."""
    return rows["x"] * (4 + 8 + 8)
