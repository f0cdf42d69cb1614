"""The benchmark's own TPC-H data: eight tables, every column, from a seed.

A copy of ``ballista_tpu/tpch.py``'s generator, kept here so that a later PR
cannot change the yardstick's data, with the per-row Python loops replaced by
numpy byte arithmetic (strings are assembled as Arrow buffers):
SF1 in a few seconds instead of about forty. The random draws are made in the
original's order, so the tables equal the program's generator value for value
(``perf/tests/test_datagen.py`` holds that at SF 0.01). It is the repo's
seeded generator, not dbgen: cardinalities, keys and value domains follow
TPC-H, the text does not. Imports nothing of the program.
"""

from __future__ import annotations

import datetime

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

TABLES = (
    "part", "supplier", "partsupp", "customer", "orders", "lineitem",
    "nation", "region",
)
EPOCH = datetime.date(1970, 1, 1)


def days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - EPOCH).days


NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
SHIPINSTRUCT = [
    "DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN",
]
CONTAINERS = [
    f"{a} {b}"
    for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
    for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM")
]
TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
P_NAME_WORDS = [
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
COMMENT_WORDS = [
    "carefully", "quickly", "slowly", "furiously", "blithely", "express",
    "regular", "special", "final", "pending", "ironic", "even", "bold",
    "silent", "unusual", "deposits", "requests", "packages", "accounts",
    "instructions", "theodolites", "platelets", "foxes", "ideas", "asymptotes",
    "dependencies", "excuses", "pinto", "beans", "sleep", "haggle", "nag",
    "wake", "cajole", "integrate", "detect", "among", "above", "along",
]

# TPC-H base cardinalities at SF=1; lineitem is 1 to 7 per order
CARD = {"part": 200_000, "supplier": 10_000, "customer": 150_000,
        "orders": 1_500_000}
DATE_LO = days(1992, 1, 1)
DATE_HI = days(1998, 12, 1)


def _rows(table: str, scale: float) -> int:
    return max(1, int(CARD[table] * scale))


def _vocab(words, idx) -> pa.Array:
    return pc.take(pa.array(words, pa.string()), pa.array(idx))


def _strings(data: np.ndarray, offsets: np.ndarray) -> pa.Array:
    return pa.StringArray.from_buffers(
        len(offsets) - 1,
        pa.py_buffer(np.ascontiguousarray(offsets, dtype=np.int32)),
        pa.py_buffer(np.ascontiguousarray(data, dtype=np.uint8)),
    )


def _words(words, idx2d) -> pa.Array:
    """Row-wise ' '.join of the vocabulary words picked by ``idx2d[n, k]``,
    built as bytes: every word padded to one width with its trailing space,
    gathered, and the padding masked away."""
    lens = np.array([len(w) for w in words])
    width = int(lens.max()) + 1
    table = np.full((len(words), width), ord(" "), dtype=np.uint8)
    for i, w in enumerate(words):
        table[i, : len(w)] = np.frombuffer(w.encode(), dtype=np.uint8)
    col = np.arange(width)
    with_space = col[None, :] <= lens[:, None]
    bare = col[None, :] < lens[:, None]
    keep = with_space[idx2d]
    keep[:, -1, :] = bare[idx2d[:, -1]]  # no space after the last word
    row_len = lens[idx2d].sum(axis=1) + idx2d.shape[1] - 1
    offsets = np.concatenate([[0], np.cumsum(row_len)])
    return _strings(table[idx2d][keep], offsets)


def _comments(rng, n: int, nwords: int = 5) -> pa.Array:
    return _words(COMMENT_WORDS, rng.integers(0, len(COMMENT_WORDS),
                                              (n, nwords)))


def _digits(a, width: int) -> np.ndarray:
    """``f"{a:0{width}d}"`` as a [n, width] matrix of ASCII bytes."""
    a = np.asarray(a, dtype=np.int64)
    powers = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
    return ((a[:, None] // powers) % 10 + ord("0")).astype(np.uint8)


def _fixed(n: int, *parts) -> pa.Array:
    """Strings of one width: each part a literal or a [n, w] byte matrix."""
    cols = [
        np.broadcast_to(np.frombuffer(p.encode(), dtype=np.uint8),
                        (n, len(p)))
        if isinstance(p, str) else p
        for p in parts
    ]
    data = np.concatenate(cols, axis=1)
    return _strings(data.ravel(), np.arange(n + 1) * data.shape[1])


def _tagged(prefix: str, keys) -> pa.Array:
    """``f"{prefix}{k:09d}"`` for every key."""
    return _fixed(len(keys), prefix, _digits(keys, 9))


def _phone(rng, nk) -> pa.Array:
    n = len(nk)
    a = rng.integers(100, 1000, n)
    b = rng.integers(100, 1000, n)
    c = rng.integers(1000, 10000, n)
    return _fixed(n, _digits(10 + nk, 2), "-", _digits(a, 3), "-",
                  _digits(b, 3), "-", _digits(c, 4))


def _dates(d) -> pa.Array:
    return pa.array(np.asarray(d, dtype=np.int32)).cast(pa.date32())


def _retail(pk):
    return (90000 + (pk % 20001) + 100 * (pk % 1000)) / 100.0


def _rng(seed: int, table: str):
    return np.random.default_rng(
        np.random.SeedSequence([seed, TABLES.index(table)])
    )


def gen_region(scale, seed):
    rng = _rng(seed, "region")
    return pa.table({
        "r_regionkey": pa.array(np.arange(5, dtype=np.int64)),
        "r_name": pa.array(REGIONS),
        "r_comment": _comments(rng, 5),
    })


def gen_nation(scale, seed):
    rng = _rng(seed, "nation")
    return pa.table({
        "n_nationkey": pa.array(np.arange(len(NATIONS), dtype=np.int64)),
        "n_name": pa.array([n for n, _ in NATIONS]),
        "n_regionkey": pa.array(
            np.asarray([r for _, r in NATIONS], dtype=np.int64)
        ),
        "n_comment": _comments(rng, len(NATIONS)),
    })


def gen_part(scale, seed):
    rng = _rng(seed, "part")
    n = _rows("part", scale)
    keys = np.arange(1, n + 1, dtype=np.int64)
    names = _words(P_NAME_WORDS, rng.integers(0, len(P_NAME_WORDS), (n, 5)))
    mfgr = rng.integers(1, 6, n)
    brand = mfgr * 10 + rng.integers(1, 6, n)
    t1 = rng.integers(0, len(TYPE_S1), n)
    t2 = rng.integers(0, len(TYPE_S2), n)
    t3 = rng.integers(0, len(TYPE_S3), n)
    types = _words(
        TYPE_S1 + TYPE_S2 + TYPE_S3,
        np.stack([t1, t2 + len(TYPE_S1), t3 + len(TYPE_S1) + len(TYPE_S2)],
                 axis=1),
    )
    return pa.table({
        "p_partkey": pa.array(keys),
        "p_name": names,
        "p_mfgr": _fixed(n, "Manufacturer#", _digits(mfgr, 1)),
        "p_brand": _fixed(n, "Brand#", _digits(brand, 2)),
        "p_type": types,
        "p_size": pa.array(rng.integers(1, 51, n).astype(np.int32)),
        "p_container": _vocab(CONTAINERS,
                              rng.integers(0, len(CONTAINERS), n)),
        "p_retailprice": pa.array(_retail(keys)),
        "p_comment": _comments(rng, n, 3),
    })


def gen_supplier(scale, seed):
    rng = _rng(seed, "supplier")
    n = _rows("supplier", scale)
    keys = np.arange(1, n + 1, dtype=np.int64)
    nk = rng.integers(0, len(NATIONS), n).astype(np.int64)
    # spec: 5 suppliers per 10000 carry the Complaints text
    comments = _comments(rng, n).to_pylist()
    for i in rng.choice(n, max(1, n // 2000), replace=False):
        comments[i] = "wake Customer Complaints sleep"
    for i in rng.choice(n, max(1, n // 2000), replace=False):
        comments[i] = "even Customer Recommends haggle"
    return pa.table({
        "s_suppkey": pa.array(keys),
        "s_name": _tagged("Supplier#", keys),
        "s_address": _comments(rng, n, 2),
        "s_nationkey": pa.array(nk),
        "s_phone": _phone(rng, nk),
        "s_acctbal": pa.array(np.round(rng.uniform(-999.99, 9999.99, n), 2)),
        "s_comment": pa.array(comments, pa.string()),
    })


def _part_supplier(pk, i, nsupp):
    """The spec's formula: the i-th of a part's four suppliers."""
    return (pk + i * (nsupp // 4 + ((pk - 1) // nsupp))) % nsupp + 1


def gen_partsupp(scale, seed):
    rng = _rng(seed, "partsupp")
    npart, nsupp = _rows("part", scale), _rows("supplier", scale)
    pk = np.repeat(np.arange(1, npart + 1, dtype=np.int64), 4)
    n = len(pk)
    i = np.tile(np.arange(4, dtype=np.int64), npart)
    return pa.table({
        "ps_partkey": pa.array(pk),
        "ps_suppkey": pa.array(_part_supplier(pk, i, nsupp)),
        "ps_availqty": pa.array(rng.integers(1, 10000, n).astype(np.int32)),
        "ps_supplycost": pa.array(np.round(rng.uniform(1.0, 1000.0, n), 2)),
        "ps_comment": _comments(rng, n, 8),
    })


def gen_customer(scale, seed):
    rng = _rng(seed, "customer")
    n = _rows("customer", scale)
    keys = np.arange(1, n + 1, dtype=np.int64)
    nk = rng.integers(0, len(NATIONS), n).astype(np.int64)
    return pa.table({
        "c_custkey": pa.array(keys),
        "c_name": _tagged("Customer#", keys),
        "c_address": _comments(rng, n, 2),
        "c_nationkey": pa.array(nk),
        "c_phone": _phone(rng, nk),
        "c_acctbal": pa.array(np.round(rng.uniform(-999.99, 9999.99, n), 2)),
        "c_mktsegment": _vocab(SEGMENTS, rng.integers(0, 5, n)),
        "c_comment": _comments(rng, n, 6),
    })


def gen_orders(scale, seed):
    rng = _rng(seed, "orders")
    ncust, n = _rows("customer", scale), _rows("orders", scale)
    # spec: order keys are sparse (a quarter of the key space is used)
    keys = (np.arange(n, dtype=np.int64) * 4) + 1
    ck = rng.integers(1, ncust + 1, n).astype(np.int64)
    odate = rng.integers(DATE_LO, DATE_HI - 151, n).astype(np.int32)
    status = np.where(
        odate + 100 < days(1995, 6, 17), 0,
        np.where(odate > days(1996, 1, 1), 1, 2),
    )
    return pa.table({
        "o_orderkey": pa.array(keys),
        "o_custkey": pa.array(ck),
        "o_orderstatus": _vocab(["F", "O", "P"], status),
        "o_totalprice": pa.array(np.round(rng.uniform(850.0, 555000.0, n), 2)),
        "o_orderdate": _dates(odate),
        "o_orderpriority": _vocab(PRIORITIES, rng.integers(0, 5, n)),
        "o_clerk": _tagged("Clerk#", rng.integers(1, max(2, n // 1000), n)),
        "o_shippriority": pa.array(np.zeros(n, dtype=np.int32)),
        "o_comment": _comments(rng, n, 6),
    })


def gen_lineitem(scale, seed, orders: pa.Table | None = None):
    if orders is None:
        orders = gen_orders(scale, seed)
    rng = _rng(seed, "lineitem")
    okeys = orders["o_orderkey"].to_numpy()
    odates = orders["o_orderdate"].cast(pa.int32()).to_numpy()
    npart, nsupp = _rows("part", scale), _rows("supplier", scale)
    nline = rng.integers(1, 8, len(okeys))
    lok = np.repeat(okeys, nline)
    lod = np.repeat(odates, nline)
    n = len(lok)
    first = np.cumsum(nline) - nline
    linenumber = (np.arange(n) - np.repeat(first, nline) + 1).astype(np.int32)
    pk = rng.integers(1, npart + 1, n).astype(np.int64)
    # supplier chosen among the part's four partsupp suppliers (FK integrity)
    sk = _part_supplier(pk, rng.integers(0, 4, n).astype(np.int64), nsupp)
    qty = rng.integers(1, 51, n).astype(np.float64)
    eprice = np.round(_retail(pk) * qty, 2)
    sdate = (lod + rng.integers(1, 122, n)).astype(np.int32)
    cdate = (lod + rng.integers(30, 91, n)).astype(np.int32)
    rdate = (sdate + rng.integers(1, 31, n)).astype(np.int32)
    cut = days(1995, 6, 17)
    rf = np.where(rdate <= cut, np.where(rng.random(n) < 0.5, 0, 1), 2)
    ls = np.where(sdate > cut, 0, 1)
    return pa.table({
        "l_orderkey": pa.array(lok),
        "l_partkey": pa.array(pk),
        "l_suppkey": pa.array(sk),
        "l_linenumber": pa.array(linenumber),
        "l_quantity": pa.array(qty),
        "l_extendedprice": pa.array(eprice),
        "l_discount": pa.array(np.round(rng.integers(0, 11, n) / 100.0, 2)),
        "l_tax": pa.array(np.round(rng.integers(0, 9, n) / 100.0, 2)),
        "l_returnflag": _vocab(["R", "A", "N"], rf),
        "l_linestatus": _vocab(["O", "F"], ls),
        "l_shipdate": _dates(sdate),
        "l_commitdate": _dates(cdate),
        "l_receiptdate": _dates(rdate),
        "l_shipinstruct": _vocab(SHIPINSTRUCT, rng.integers(0, 4, n)),
        "l_shipmode": _vocab(SHIPMODES, rng.integers(0, 7, n)),
        "l_comment": _comments(rng, n, 4),
    })


_GEN = {
    "part": gen_part, "supplier": gen_supplier, "partsupp": gen_partsupp,
    "customer": gen_customer, "orders": gen_orders, "nation": gen_nation,
    "region": gen_region,
}


def gen_all(scale: float, seed: int) -> dict[str, pa.Table]:
    """All eight tables; ``seed`` is any whole number (the driver's are past
    2**31, which ``SeedSequence`` takes as they are)."""
    out = {t: _GEN[t](scale, seed) for t in TABLES if t != "lineitem"}
    out["lineitem"] = gen_lineitem(scale, seed, out["orders"])
    return {t: out[t] for t in TABLES}
