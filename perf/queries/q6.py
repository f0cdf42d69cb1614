"""TPC-H Q6, forecasting revenue change (clause 2.6.1): one scan of lineitem,
three range filters and one sum. See ``q1.py`` for what a template holds."""

import datetime

import numpy as np
import pandas as pd

COLUMNS = {
    "lineitem": ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate"],
}
ORDER = []
# number compared -> (float columns, None for all; limit): see q1.py, PERF.md §2
LIMITS = {"relerr_q6": (None, 1e-10)}
VALIDATION = {"year": 1994, "discount_lo": "0.05", "discount_hi": "0.07",
              "quantity": 24}


def draw(rng) -> dict:
    """DATE is the first of January of 1993 to 1997, DISCOUNT 0.02 to 0.09,
    QUANTITY 24 or 25. The bounds DISCOUNT -/+ 0.01 are written as two-decimal
    literals: the doubles the data holds, with no arithmetic in between."""
    year = int(rng.integers(1993, 1998))
    cents = int(rng.integers(2, 10))
    return {
        "year": year,
        "discount_lo": f"{(cents - 1) / 100:.2f}",
        "discount_hi": f"{(cents + 1) / 100:.2f}",
        "quantity": int(rng.integers(24, 26)),
    }


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    li = f["lineitem"]
    epoch = datetime.date(1970, 1, 1)
    lo = (datetime.date(p["year"], 1, 1) - epoch).days
    hi = (datetime.date(p["year"] + 1, 1, 1) - epoch).days
    d = li[
        (li.l_shipdate >= lo) & (li.l_shipdate < hi)
        & (li.l_discount >= float(p["discount_lo"]))
        & (li.l_discount <= float(p["discount_hi"]))
        & (li.l_quantity < p["quantity"])
    ]
    price, disc = (
        (quantize(d[c]) if quantize else d[c]).astype(real)
        for c in ("l_extendedprice", "l_discount")
    )
    revenue = (price * disc).sum()
    return pd.DataFrame({"revenue": [revenue]})


def least_bytes(rows: dict) -> int:
    """Three float64 and one date32: 28 bytes of every lineitem row."""
    return rows["lineitem"] * (3 * 8 + 4)
