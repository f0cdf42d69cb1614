"""h2oai db-benchmark, groupby task, question 6 ("median v3 sd v3 by id4
id5"), in the SQL of upstream's ``groupby-datafusion.py``: K x K groups under
two int64 keys, an order statistic and the sample deviation of a float64.
The program's median is the exact interpolated order statistic where
upstream's ``approx_percentile_cont`` is a t-digest estimate, so the plain
reference is pandas' ``median`` (the mean of the two middle values, which is
linear interpolation at 0.5) and the answer is held to it, not to an
approximation's tolerance. ``std`` is the sample deviation (``ddof=1``).
``q1.py`` says what a template holds; ``g1q3.py`` the task."""

import numpy as np
import pandas as pd

from queries import g1_adv_needs

g1_adv_needs.check(__name__)

COLUMNS = {"x": ["id4", "id5", "v3"]}
ORDER = []  # the question states none: answers are aligned on id4, id5
# number compared -> (float columns; limit): PERF.md §2
LIMITS = {
    "relerr_g1q6_median": (["median_v3"], 1e-11),
    "relerr_g1q6_sd": (["stddev_v3"], 3e-10),
}
VALIDATION = {}


def draw(rng) -> dict:
    return {}


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    x = f["x"]
    v3 = (quantize(x.v3) if quantize else x.v3).astype(real)
    return (pd.DataFrame({"id4": x.id4, "id5": x.id5, "v3": v3})
            .groupby(["id4", "id5"])
            .agg(median_v3=("v3", "median"), stddev_v3=("v3", "std"))
            .reset_index()
            .sort_values(["id4", "id5"]).reset_index(drop=True))


def least_bytes(rows: dict) -> int:
    """Two int64 keys and a float64 of every row."""
    return rows["x"] * (8 + 8 + 8)


def sort_least_bytes(rows: dict) -> int:
    """What the question's one sort (every row by id4, id5, v3) has to move
    at least: each row's keys and value and a 4-byte position, read once and
    written once (``layers/holistic_roofline_share.py``)."""
    return rows["x"] * (8 + 8 + 8 + 4) * 2
