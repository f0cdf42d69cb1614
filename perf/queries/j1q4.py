"""h2oai db-benchmark, join task, question 4 ("medium inner on factor"),
the join of upstream's ``join-datafusion.py``: ``x JOIN medium ON x.id5 =
medium.id5``, N rows against N/1e3 on a string key. Each side's dictionary
has N/1e3 entries, of which 0.9 N/1e3 are shared; every medium row's key is
its own, so each x row whose key is shared matches one row, some 0.9 N.

The select list is ``j1q5.py``'s check: the number of rows, the two float
sums, an exact integer sum of a right-side column (``medium.id2``) and the
exact ``SUM(x.id2 * medium.id2)``, which reads both sides of each joined
row. (``id5`` is ``"id" + id2`` on both sides, so a sound join pairs equal
``id2``: the product's sum is that of the squares, and a medium row's
payload given to another's partners moves it.) The reference is a pandas merge of the two frames on the key's strings, each
side's categories put in x's codes; it imports nothing of the program."""

import numpy as np
import pandas as pd

COLUMNS = {"x": ["id5", "v1", "id2"], "medium": ["id5", "v2", "id2"]}
ORDER = []  # one row
# number compared -> (float columns, None for all; limit): PERF.md §2
LIMITS = {"relerr_j1q4": (None, 1e-10)}
VALIDATION = {}
# the share of x's rows with a partner: key2's common keys over x's keys
MATCHED = 0.9


def draw(rng) -> dict:
    return {}


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    x, medium = f["x"], f["medium"]

    def real_of(col):
        return (quantize(col) if quantize else col).astype(real)

    # medium's strings in x's codes (-1: a string x does not hold)
    codes = x.id5.cat.categories.get_indexer(medium.id5.astype(str))
    right = pd.DataFrame({"k": codes, "v2": real_of(medium.v2),
                          "id2": medium.id2})
    joined = pd.DataFrame(
        {"k": x.id5.cat.codes, "v1": real_of(x.v1), "xid2": x.id2}
    ).merge(right[right.k >= 0], on="k")
    return pd.DataFrame({
        "n": [len(joined)], "v1": [joined.v1.sum()],
        "v2": [joined.v2.sum()], "id2": [joined.id2.sum()],
        "pair": [(joined.xid2 * joined.id2).sum()],
    })


def least_bytes(rows: dict) -> int:
    """x's key code, v1 and id2, medium's key code, v2 and id2."""
    return rows["x"] * (4 + 8 + 8) + rows["medium"] * (4 + 8 + 8)


def join_least_bytes(rows: dict) -> int:
    """The join alone: each build row's key code and payload written once
    (medium: id5, v2, id2), each probe row's key code read once (x: id5),
    and each output row's carried columns (x's v1 and id2, medium's v2 and
    id2) gathered once and written once."""
    out = int(rows["x"] * MATCHED)
    return (rows["medium"] * (4 + 8 + 8) + rows["x"] * 4
            + out * (4 * 8) * 2)
