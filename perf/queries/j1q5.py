"""h2oai db-benchmark, join task, question 5 ("big inner on int"), the join
of upstream's ``join-datafusion.py``: ``x JOIN big ON x.id3 = big.id3``, two
tables of N rows on an int64 key that each side holds once; the 0.9 N keys
common to both match one row each.

The select list is the benchmark's check of the join, computed in the
engine: the number of rows, the two float sums, an exact integer sum of a
column of the right side, and the exact ``SUM(x.id2 * big.id2)``, the one
number that reads both sides of each joined row (upstream returns every
column of the 0.9 N rows; ``reduced`` in the configuration says why not
here). Every other number reads one side: in a join of one row to one, a
right side's payload paired with the matched left rows in another order
leaves them all as they were, and moves only the product's sum, which no
planner can push below the join either. Nothing of the join is cut: both
sides' keys are built, probed and gathered for every row. The reference is
a pandas merge over the generator's frames; it imports nothing of the
program."""

import numpy as np
import pandas as pd

COLUMNS = {"x": ["id3", "v1", "id2"], "big": ["id3", "v2", "id2"]}
ORDER = []  # one row
# number compared -> (float columns, None for all; limit): PERF.md §2
LIMITS = {"relerr_j1q5": (None, 1e-10)}
VALIDATION = {}
# the share of x's rows with a partner: key3's common keys over x's keys
MATCHED = 0.9


def draw(rng) -> dict:
    return {}


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    x, big = f["x"], f["big"]

    def real_of(col):
        return (quantize(col) if quantize else col).astype(real)

    joined = pd.DataFrame(
        {"k": x.id3, "v1": real_of(x.v1), "xid2": x.id2}
    ).merge(
        pd.DataFrame({"k": big.id3, "v2": real_of(big.v2), "id2": big.id2}),
        on="k")
    return pd.DataFrame({
        "n": [len(joined)], "v1": [joined.v1.sum()],
        "v2": [joined.v2.sum()], "id2": [joined.id2.sum()],
        "pair": [(joined.xid2 * joined.id2).sum()],
    })


def least_bytes(rows: dict) -> int:
    """x's key, v1 and id2, big's key, v2 and id2: int64 and float64
    each."""
    return rows["x"] * (8 + 8 + 8) + rows["big"] * (8 + 8 + 8)


def join_least_bytes(rows: dict) -> int:
    """The join alone: each build row's key and payload written once (big:
    id3, v2, id2), each probe row's key read once (x: id3), and each output
    row's carried columns (x's v1 and id2, big's v2 and id2) gathered once
    and written once."""
    out = int(rows["x"] * MATCHED)
    return rows["big"] * (8 + 8 + 8) + rows["x"] * 8 + out * (4 * 8) * 2
