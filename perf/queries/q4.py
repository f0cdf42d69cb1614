"""TPC-H Q4, order priority checking (clause 2.4.4): the orders of a quarter
with at least one line received after its commit date, counted by priority.
A correlated ``EXISTS`` (a semi join whose build side is lineitem under a
column-to-column predicate, duplicate keys) and a date interval.

Keys and counts only: ``LIMITS`` is empty, the comparison exact. ``q1.py``
says what a template holds."""

import datetime

import numpy as np
import pandas as pd

from queries import tpch_subq_needs

tpch_subq_needs.check(__name__)

COLUMNS = {
    "orders": ["o_orderkey", "o_orderdate", "o_orderpriority"],
    "lineitem": ["l_orderkey", "l_commitdate", "l_receiptdate"],
}
ORDER = [(0, True)]
LIMITS = {}  # keys and counts only: nothing is a float, PERF.md §2
VALIDATION = {"date": "1993-07-01"}
EPOCH = datetime.date(1970, 1, 1)


def draw(rng) -> dict:
    """Clause 2.4.4.3: DATE is the first day of a month drawn between
    January 1993 and October 1997."""
    m = int(rng.integers(0, 58))
    return {"date": f"{1993 + m // 12}-{m % 12 + 1:02d}-01"}


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    o, li = f["orders"], f["lineitem"]
    lo = datetime.date.fromisoformat(p["date"])
    m = lo.month + 3
    hi = datetime.date(lo.year + (m - 1) // 12, (m - 1) % 12 + 1, 1)
    late = li.l_orderkey[li.l_commitdate < li.l_receiptdate].unique()
    q = o[(o.o_orderdate >= (lo - EPOCH).days)
          & (o.o_orderdate < (hi - EPOCH).days)]
    q = q[q.o_orderkey.isin(late)]
    counts = q.o_orderpriority.astype(str).value_counts().sort_index()
    return pd.DataFrame({
        "o_orderpriority": counts.index.to_numpy(),
        "order_count": counts.to_numpy().astype(np.int64),
    })


def least_bytes(rows: dict) -> int:
    """orders: a key (8), a date32 (4), a char(15) priority; lineitem: a key
    (8) and two date32 (4 each)."""
    return rows["orders"] * (8 + 4 + 15) + rows["lineitem"] * (8 + 4 + 4)
