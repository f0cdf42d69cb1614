"""TPC-H Q17, small-quantity-order revenue (clause 2.4.17): the yearly
revenue lost if the lines of one brand and container below a fifth of their
part's average quantity were not taken. A correlated scalar subquery: the
program groups all of lineitem by part for the average (200,000 groups at
SF1, in every query) and joins it back to the few hundred parts the outer
query keeps, with ``l_quantity < 0.2 * AVG(l_quantity)`` left above the join.

The comparison is decided here in exact integers: quantities are decimals of
two places, so ``qty < 0.2 * sum / count`` is ``5 * qty100 * count <
sum100`` over quantities scaled by 100. At SF1 about 0.6 lines a draw sit at
an exact tie (``5 * qty * count = sum``): a program that rounds ``0.2 *
AVG`` up there keeps a line SQL drops, and ``avg_yearly`` moves by about
1e-3 of itself. One float, ``relerr_q17``. ``q1.py`` says what a template
holds."""

import numpy as np
import pandas as pd

COLUMNS = {
    "part": ["p_partkey", "p_brand", "p_container"],
    "lineitem": ["l_partkey", "l_quantity", "l_extendedprice"],
}
ORDER = []  # one row
# number compared -> (float columns, limit): the readings are in PERF.md §2
LIMITS = {"relerr_q17": (("avg_yearly",), 1e-12)}
VALIDATION = {"brand": "Brand#23", "container": "MED BOX"}
CONTAINER_S1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINER_S2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]


def draw(rng) -> dict:
    """Clause 2.4.17.3: BRAND is Brand#MN with M and N of 1..5, CONTAINER
    one of the 40 two-syllable containers."""
    return {
        "brand": f"Brand#{int(rng.integers(1, 6))}{int(rng.integers(1, 6))}",
        "container": (f"{CONTAINER_S1[int(rng.integers(0, 5))]} "
                      f"{CONTAINER_S2[int(rng.integers(0, 8))]}"),
    }


def small_lines(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    """The lines the outer query sums: of a kept part, below a fifth of that
    part's average quantity over all of lineitem. In float64 the decision is
    made in exact integers; in a lower ``real`` (the controls) as a program
    in that precision would make it."""
    pt, li = f["part"], f["lineitem"]
    kept = pt.p_partkey[(pt.p_brand.astype(str) == p["brand"]).to_numpy()
                        & (pt.p_container.astype(str) == p["container"])
                        .to_numpy()]
    qty = quantize(li.l_quantity) if quantize else li.l_quantity
    if real is np.float64:
        q100 = np.rint(qty.to_numpy() * 100).astype(np.int64)
        by_part = pd.DataFrame({"k": li.l_partkey.to_numpy(), "q": q100})
        g = by_part.groupby("k").q.agg(["sum", "count"])
        lines = li.assign(q100=q100)[li.l_partkey.isin(kept).to_numpy()]
        s = g["sum"].reindex(lines.l_partkey).to_numpy()
        c = g["count"].reindex(lines.l_partkey).to_numpy()
        return lines[5 * lines.q100.to_numpy() * c < s]
    avg = qty.astype(real).groupby(li.l_partkey).mean()
    lines = li[li.l_partkey.isin(kept).to_numpy()]
    bound = real(0.2) * avg.reindex(lines.l_partkey).to_numpy().astype(real)
    return lines[qty[lines.index].astype(real).to_numpy() < bound]


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    lines = small_lines(f, p, real, quantize)
    price = lines.l_extendedprice
    price = (quantize(price) if quantize else price).astype(real)
    total = price.sum() if len(price) else np.nan
    return pd.DataFrame({"avg_yearly": [float(real(total) / real(7.0))]})


def least_bytes(rows: dict) -> int:
    """lineitem: a key and two float64 (8 each); part: a key (8), a char(10)
    brand and a char(10) container."""
    return rows["lineitem"] * 24 + rows["part"] * (8 + 10 + 10)
