"""TPC-H Q1, pricing summary report (clause 2.4.1): one scan of lineitem,
a filter on the ship date and eight aggregates over four groups.

A template is three things the harness finds by name: ``draw`` (the
substitution parameters of clause 2.4.1.3, as the placeholders of ``q1.sql``),
``reference`` (the plain answer from numpy/pandas over the same columns,
nothing of the program) and ``least_bytes`` (what any implementation has to
read at least once).
"""

import datetime

import numpy as np
import pandas as pd

# columns the reference reads; dates arrive as int32 days since 1970
COLUMNS = {
    "lineitem": ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
                 "l_returnflag", "l_linestatus", "l_shipdate"],
}
# ORDER BY as (column position in the answer, ascending)
ORDER = [(0, True), (1, True)]
# number compared -> (the float columns it is the largest relative error of,
# its limit); the readings the limits were set from are in PERF.md §2. The
# quantities are exact in the program (integers through the f32-split kernel);
# prices and discounts go through that kernel's split and carry its 2e-8.
LIMITS = {
    "relerr_q1_qty": (("sum_qty", "avg_qty"), 1e-10),
    "relerr_q1_money": (("sum_base_price", "sum_disc_price", "sum_charge",
                         "avg_price", "avg_disc"), 1e-6),
}
VALIDATION = {"delta": 90}


def draw(rng) -> dict:
    """DELTA is drawn uniformly from 60 to 120 days."""
    return {"delta": int(rng.integers(60, 121))}


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    """``real`` is the type the arithmetic runs in: float64 as the
    configuration states it. The controls lower it to float32, and
    ``quantize`` rounds every float column as it is read (to bfloat16)."""
    li = f["lineitem"]
    cutoff = (datetime.date(1998, 12, 1) - datetime.timedelta(days=p["delta"])
              - datetime.date(1970, 1, 1)).days
    d = li[li.l_shipdate <= cutoff]
    qty, price, disc, tax = (
        (quantize(d[c]) if quantize else d[c]).astype(real)
        for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax")
    )
    disc_price = price * (real(1) - disc)
    g = pd.DataFrame({
        "l_returnflag": d.l_returnflag, "l_linestatus": d.l_linestatus,
        "qty": qty, "price": price, "disc_price": disc_price,
        "charge": disc_price * (real(1) + tax), "disc": disc,
    }).groupby(["l_returnflag", "l_linestatus"], observed=True)
    out = g.agg(
        sum_qty=("qty", "sum"),
        sum_base_price=("price", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("qty", "mean"),
        avg_price=("price", "mean"),
        avg_disc=("disc", "mean"),
        count_order=("qty", "count"),
    ).reset_index()
    keys = ["l_returnflag", "l_linestatus"]
    out[keys] = out[keys].astype(str)  # categories sort by code, not by text
    return out.sort_values(keys).reset_index(drop=True)


def least_bytes(rows: dict) -> int:
    """Four float64 (8 each), one date32 (4), two dictionary codes (4 each):
    44 bytes of every lineitem row."""
    return rows["lineitem"] * (4 * 8 + 4 + 2 * 4)
