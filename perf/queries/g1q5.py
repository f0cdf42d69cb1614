"""h2oai db-benchmark, groupby task, question 5 ("sum v1:v3 by id6"), in the
SQL of upstream's ``groupby-datafusion.py``: N/K groups under an int64 key,
two integer sums and a float one. See ``g1q3.py``."""

import numpy as np
import pandas as pd

from queries import g1_needs

g1_needs.check(__name__)

COLUMNS = {"x": ["id6", "v1", "v2", "v3"]}
ORDER = []  # the question states none: answers are aligned on id6
LIMITS = {"relerr_g1q5": (None, 2e-9)}
VALIDATION = {}


def draw(rng) -> dict:
    return {}


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    x = f["x"]
    v3 = (quantize(x.v3) if quantize else x.v3).astype(real)
    return (pd.DataFrame({"id6": x.id6, "v1": x.v1, "v2": x.v2, "v3": v3})
            .groupby("id6")
            .agg(v1=("v1", "sum"), v2=("v2", "sum"), v3=("v3", "sum"))
            .reset_index()
            .sort_values("id6").reset_index(drop=True))


def least_bytes(rows: dict) -> int:
    """The int64 key, two int64 and a float64 of every row."""
    return rows["x"] * (8 + 8 + 8 + 8)
