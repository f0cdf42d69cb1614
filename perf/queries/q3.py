"""TPC-H Q3, shipping priority (clause 2.3.1): customer, orders and lineitem
joined, grouped by order, the ten largest revenues. See ``q1.py`` for what a
template holds."""

import datetime

import numpy as np
import pandas as pd

COLUMNS = {
    "customer": ["c_custkey", "c_mktsegment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "lineitem": ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"],
}
ORDER = [(1, False), (2, True)]
# number compared -> (float columns, None for all; limit): see q1.py, PERF.md §2
LIMITS = {"relerr_q3": (None, 1e-9)}
VALIDATION = {"segment": "BUILDING", "date": "1995-03-15"}
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]


def draw(rng) -> dict:
    """SEGMENT is one of the five, DATE a day of March 1995."""
    return {
        "segment": SEGMENTS[int(rng.integers(0, 5))],
        "date": f"1995-03-{int(rng.integers(1, 32)):02d}",
    }


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    c, o, li = f["customer"], f["orders"], f["lineitem"]
    day = (datetime.date.fromisoformat(p["date"])
           - datetime.date(1970, 1, 1)).days
    j = c[c.c_mktsegment == p["segment"]].merge(
        o[o.o_orderdate < day], left_on="c_custkey", right_on="o_custkey",
    )
    j = j.merge(
        li[li.l_shipdate > day], left_on="o_orderkey", right_on="l_orderkey",
    )
    price, disc = (
        (quantize(j[c]) if quantize else j[c]).astype(real)
        for c in ("l_extendedprice", "l_discount")
    )
    j["revenue"] = price * (real(1) - disc)
    return (
        j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])
        .revenue.sum()
        .reset_index()
        .sort_values(["revenue", "o_orderdate", "l_orderkey"],
                     ascending=[False, True, True])
        .head(10)[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]]
        .reset_index(drop=True)
    )


def least_bytes(rows: dict) -> int:
    """lineitem: key and two float64 (8 each) and a date32 (4), 28 bytes a
    row; orders: two keys (8), a date32 (4), an int32 (4), 24 bytes a row;
    customer: a key (8) and a dictionary code (4), 12 bytes a row."""
    return (rows["lineitem"] * 28 + rows["orders"] * 24
            + rows["customer"] * 12)
