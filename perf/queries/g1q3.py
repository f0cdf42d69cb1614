"""h2oai db-benchmark, groupby task, question 3 ("sum v1 mean v3 by id3"),
in the SQL of upstream's ``groupby-datafusion.py``: N/K groups under a string
key whose dictionary has as many entries. ``q1.py`` says what a template
holds; the questions of this task have no parameters."""

import numpy as np
import pandas as pd

from queries import g1_needs

g1_needs.check(__name__)

COLUMNS = {"x": ["id3", "v1", "v3"]}
ORDER = []  # the question states none: answers are aligned on id3
# number compared -> (float columns, None for all; limit): PERF.md §2
LIMITS = {"relerr_g1q3": (None, 1e-10)}
VALIDATION = {}


def draw(rng) -> dict:
    return {}


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    x = f["x"]
    v3 = (quantize(x.v3) if quantize else x.v3).astype(real)
    out = (pd.DataFrame({"id3": x.id3, "v1": x.v1, "v3": v3})
           .groupby("id3", observed=True)
           .agg(v1=("v1", "sum"), v3=("v3", "mean"))
           .reset_index())
    out["id3"] = out["id3"].astype(str)  # categories sort by code
    return out.sort_values("id3").reset_index(drop=True)


def least_bytes(rows: dict) -> int:
    """A dictionary code (4), an int64 and a float64 of every row."""
    return rows["x"] * (4 + 8 + 8)
