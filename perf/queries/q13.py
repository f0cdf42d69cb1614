"""TPC-H Q13, customer distribution (clause 2.4.13): customers by how many
orders they have, those with none included. A ``LEFT OUTER JOIN`` whose
``ON`` clause carries a ``NOT LIKE`` over ``o_comment`` (at SF1 a dictionary
of very nearly as many entries as orders has rows), a count of a nullable
column into one group a customer, then a count of the counts.

Every number of the answer is a key or a count: ``LIMITS`` is empty and the
comparison is exact (``mismatched``, limit 0). ``ORDER BY custdist desc,
c_count desc`` is a total order of the answer. ``q1.py`` says what a
template holds."""

import re

import numpy as np
import pandas as pd

from queries import tpch_subq_needs

tpch_subq_needs.check(__name__)

COLUMNS = {
    "customer": ["c_custkey"],
    "orders": ["o_orderkey", "o_custkey", "o_comment"],
}
ORDER = [(1, False), (0, False)]
LIMITS = {}  # keys and counts only: nothing is a float, PERF.md §2
VALIDATION = {"word1": "special", "word2": "requests"}
WORD1 = ["special", "pending", "unusual", "express"]
WORD2 = ["packages", "requests", "accounts", "deposits"]
# o_comment is six words of perf/datagen.py COMMENT_WORDS (39 words, 6.82
# letters on average) and five spaces
O_COMMENT_MEAN_BYTES = 46


def draw(rng) -> dict:
    """Clause 2.4.13.3: WORD1 and WORD2 each one of four."""
    return {
        "word1": WORD1[int(rng.integers(0, 4))],
        "word2": WORD2[int(rng.integers(0, 4))],
    }


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    c, o = f["customer"], f["orders"]
    # '%word1%word2%': word1, anything, word2, anywhere in the comment
    rx = re.escape(p["word1"]) + ".*" + re.escape(p["word2"])
    special = o.o_comment.astype(str).str.contains(rx, regex=True)
    kept = o.loc[~special.to_numpy(), ["o_custkey", "o_orderkey"]]
    j = c[["c_custkey"]].merge(kept, how="left", left_on="c_custkey",
                               right_on="o_custkey")
    # count() skips the NULL order key of a customer without a match
    c_count = j.groupby("c_custkey").o_orderkey.count()
    dist = c_count.value_counts()
    out = pd.DataFrame({
        "c_count": dist.index.to_numpy().astype(np.int64),
        "custdist": dist.to_numpy().astype(np.int64),
    })
    return out.sort_values(["custdist", "c_count"],
                           ascending=[False, False]).reset_index(drop=True)


def least_bytes(rows: dict) -> int:
    """customer: a key (8); orders: two keys (8 each) and the comment at its
    mean length."""
    return (rows["customer"] * 8
            + rows["orders"] * (8 + 8 + O_COMMENT_MEAN_BYTES))
