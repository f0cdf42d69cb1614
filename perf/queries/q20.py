"""TPC-H Q20, potential part promotion (clause 2.4.20): the suppliers of a
nation that hold more of a colour's parts than half of what they shipped of
each in a year. A correlated scalar subquery on two keys (lineitem of the
year grouped by part and supplier: some 547,000 groups at SF1) joined back
to partsupp after a semi join to ``p_name LIKE 'colour%'``, with
``ps_availqty > 0.5 * SUM(l_quantity)`` above the join, then a semi join
into supplier and ``ORDER BY s_name``.

A pair with no line in the year has no group: its subquery is NULL, the
comparison is not true and the pair is dropped. The answer is names and
addresses ordered by a unique name, so it compares row by row with limit 0:
``LIMITS`` is empty. The comparison is decided in exact integers
(``2 * 100 * availqty > sum100`` over quantities scaled by 100). ``q1.py``
says what a template holds."""

import datetime

import numpy as np
import pandas as pd

COLUMNS = {
    "part": ["p_partkey", "p_name"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_availqty"],
    "lineitem": ["l_partkey", "l_suppkey", "l_quantity", "l_shipdate"],
    "supplier": ["s_suppkey", "s_name", "s_address", "s_nationkey"],
    "nation": ["n_nationkey", "n_name"],
}
ORDER = [(0, True)]
LIMITS = {}  # names and addresses only: nothing is a float, PERF.md §2
VALIDATION = {"color": "forest", "date": "1994-01-01", "nation": "CANADA"}
EPOCH = datetime.date(1970, 1, 1)
# the 93 words of p_name (perf/datagen.py P_NAME_WORDS)
COLORS = [
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished",
    "chartreuse", "chiffon", "chocolate", "coral", "cornflower", "cornsilk",
    "cream", "cyan", "dark", "deep", "dim", "dodger", "drab", "firebrick",
    "floral", "forest", "frosted", "gainsboro", "ghost", "goldenrod",
    "green", "grey", "honeydew", "hot", "hotpink", "indian", "ivory",
    "khaki", "lace", "lavender", "lawn", "lemon", "light", "lime", "linen",
    "magenta", "maroon", "medium", "metallic", "midnight", "mint", "misty",
    "moccasin", "navajo", "navy", "olive", "orange", "orchid", "pale",
    "papaya", "peach", "peru", "pink", "plum", "powder", "puff", "purple",
    "red", "rose", "rosy", "royal", "saddle", "salmon", "sandy", "seashell",
    "sienna", "sky", "slate", "smoke", "snow", "spring", "steel", "tan",
    "thistle", "tomato", "turquoise", "violet", "wheat", "white", "yellow",
]
NATIONS = [
    "ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
    "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN",
    "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA", "ROMANIA",
    "SAUDI ARABIA", "VIETNAM", "RUSSIA", "UNITED KINGDOM", "UNITED STATES",
]
# p_name is five words of COLORS and four spaces; s_name is Supplier# and
# nine digits, s_address two words of perf/datagen.py COMMENT_WORDS
P_NAME_MEAN_BYTES = 33
S_ADDRESS_MEAN_BYTES = 15


def draw(rng) -> dict:
    """Clause 2.4.20.3: COLOR one of the words of P_NAME, DATE January 1 of
    a year of 1993..1997, NATION one of the 25."""
    return {
        "color": COLORS[int(rng.integers(0, len(COLORS)))],
        "date": f"{int(rng.integers(1993, 1998))}-01-01",
        "nation": NATIONS[int(rng.integers(0, len(NATIONS)))],
    }


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    pt, ps, li = f["part"], f["partsupp"], f["lineitem"]
    s, n = f["supplier"], f["nation"]
    lo = datetime.date.fromisoformat(p["date"])
    hi = lo.replace(year=lo.year + 1)
    colour = pt.p_partkey[
        pt.p_name.astype(str).str.startswith(p["color"]).to_numpy()]
    year = li[((li.l_shipdate >= (lo - EPOCH).days)
               & (li.l_shipdate < (hi - EPOCH).days)).to_numpy()]
    qty = quantize(year.l_quantity) if quantize else year.l_quantity
    if real is np.float64:
        shipped = (pd.DataFrame({
            "l_partkey": year.l_partkey.to_numpy(),
            "l_suppkey": year.l_suppkey.to_numpy(),
            "q": np.rint(qty.to_numpy() * 100).astype(np.int64)})
            .groupby(["l_partkey", "l_suppkey"]).q.sum().reset_index())
    else:
        shipped = (year.assign(q=qty.astype(real))
                   .groupby(["l_partkey", "l_suppkey"]).q.sum()
                   .reset_index())
    # an inner join: a pair without a line in the year has no group, its
    # subquery is NULL and the pair is dropped
    j = ps[ps.ps_partkey.isin(colour).to_numpy()].merge(
        shipped, left_on=["ps_partkey", "ps_suppkey"],
        right_on=["l_partkey", "l_suppkey"])
    avail = j.ps_availqty.to_numpy()
    if real is np.float64:
        more = 2 * 100 * avail.astype(np.int64) > j.q.to_numpy()
    else:
        more = avail.astype(real) > real(0.5) * j.q.to_numpy().astype(real)
    keys = n.n_nationkey[(n.n_name.astype(str) == p["nation"]).to_numpy()]
    hit = (s.s_suppkey.isin(j.ps_suppkey[more]).to_numpy()
           & s.s_nationkey.isin(keys).to_numpy())
    return pd.DataFrame({
        "s_name": s.s_name[hit].astype(str).to_numpy(),
        "s_address": s.s_address[hit].astype(str).to_numpy(),
    }).sort_values("s_name").reset_index(drop=True)


def least_bytes(rows: dict) -> int:
    """lineitem: two keys and a float64 (8 each) and a date32 (4); partsupp:
    two keys (8 each) and an int32; part: a key (8) and the name at its mean
    length; supplier: a key (8), a char(18) name, the address at its mean
    length and a key (8); nation: a key (8) and a char(25) name."""
    return (rows["lineitem"] * 28 + rows["partsupp"] * 20
            + rows["part"] * (8 + P_NAME_MEAN_BYTES)
            + rows["supplier"] * (8 + 18 + S_ADDRESS_MEAN_BYTES + 8)
            + rows["nation"] * (8 + 25))
