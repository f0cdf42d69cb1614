"""TPC-H Q16, parts/supplier relationship (clause 2.4.16): how many
suppliers without a complaint on file can supply parts of given sizes, not of
a given brand nor of a given type. ``NOT IN (subquery)`` (an anti join) over
a ``LIKE`` of supplier's comments, ``<>``, ``NOT LIKE 'prefix%'`` and an
eight-value ``IN`` beside a join of partsupp to part, ``COUNT(DISTINCT)``
into groups of two string keys and an integer, an answer of some 1.8e4 rows
ordered by four keys.

Keys and counts only: ``LIMITS`` is empty, the comparison exact, and the four
``ORDER BY`` keys are the whole row, so the order is total. ``q1.py`` says
what a template holds."""

import numpy as np
import pandas as pd

from queries import tpch_subq_needs

tpch_subq_needs.check(__name__)

COLUMNS = {
    "partsupp": ["ps_partkey", "ps_suppkey"],
    "part": ["p_partkey", "p_brand", "p_type", "p_size"],
    "supplier": ["s_suppkey", "s_comment"],
}
ORDER = [(3, False), (0, True), (1, True), (2, True)]
LIMITS = {}  # keys and counts only: nothing is a float, PERF.md §2
VALIDATION = {"brand": "Brand#45", "type": "MEDIUM POLISHED",
              **{f"size{i + 1}": s
                 for i, s in enumerate((49, 14, 23, 45, 19, 3, 36, 9))}}
TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
# s_comment is five words of perf/datagen.py COMMENT_WORDS and four spaces
S_COMMENT_MEAN_BYTES = 38


def draw(rng) -> dict:
    """Clause 2.4.16.3: BRAND is Brand#MN with M and N of 1..5, TYPE the
    first two syllables of a part type, SIZE1..8 eight distinct values of
    1..50."""
    sizes = rng.permutation(50)[:8] + 1
    return {
        "brand": f"Brand#{int(rng.integers(1, 6))}{int(rng.integers(1, 6))}",
        "type": (f"{TYPE_S1[int(rng.integers(0, 6))]} "
                 f"{TYPE_S2[int(rng.integers(0, 5))]}"),
        **{f"size{i + 1}": int(s) for i, s in enumerate(sizes)},
    }


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    ps, pt, s = f["partsupp"], f["part"], f["supplier"]
    complained = s.s_suppkey[
        s.s_comment.astype(str).str.contains("Customer.*Complaints",
                                             regex=True).to_numpy()]
    brand, type_ = pt.p_brand.astype(str), pt.p_type.astype(str)
    sizes = [p[f"size{i}"] for i in range(1, 9)]
    keep = ((brand != p["brand"]) & ~type_.str.startswith(p["type"])
            & pt.p_size.isin(sizes))
    parts = pd.DataFrame({
        "p_partkey": pt.p_partkey[keep], "p_brand": brand[keep],
        "p_type": type_[keep], "p_size": pt.p_size[keep],
    })
    j = ps[~ps.ps_suppkey.isin(complained)].merge(
        parts, left_on="ps_partkey", right_on="p_partkey")
    out = (j.groupby(["p_brand", "p_type", "p_size"]).ps_suppkey.nunique()
           .reset_index(name="supplier_cnt"))
    out["supplier_cnt"] = out.supplier_cnt.astype(np.int64)
    return out.sort_values(
        ["supplier_cnt", "p_brand", "p_type", "p_size"],
        ascending=[False, True, True, True]).reset_index(drop=True)


def least_bytes(rows: dict) -> int:
    """partsupp: two keys (8 each); part: a key (8), a char(10) brand, a
    varchar(25) type, an int32 size; supplier: a key (8) and the comment at
    its mean length."""
    return (rows["partsupp"] * 16 + rows["part"] * (8 + 10 + 25 + 4)
            + rows["supplier"] * (8 + S_COMMENT_MEAN_BYTES))
