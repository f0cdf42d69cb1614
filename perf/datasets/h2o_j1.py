"""h2oai db-benchmark, join task: the four tables of data set
``J1_<rows>_NA_0_0`` (``_data/join-datagen.R``: no NAs, unsorted), from the
configuration's ``rows`` (N).

Keys are split as upstream's ``split_xlr(n)`` splits them: a permutation of
1..1.1n cut into 0.9n keys common to both sides, 0.1n on the left only and
0.1n on the right only. Three such sets: ``key1`` over N/1e6, ``key2`` over
N/1e3, ``key3`` over N.

    x       N rows     id1, id2 every common and left key of key1, key2
                       once, the rest drawn from them (``sample_all``);
                       id3 key3's common and left keys, each once
    small   N/1e6      id1 key1's common and right keys, each once
    medium  N/1e3      id1 as x's over key1's common and right keys;
                       id2 key2's common and right keys, each once
    big     N          id1, id2 as x's over the common and right keys;
                       id3 key3's common and right keys, each once

Each ``id<k>`` has its string ``id<k + 3>``, ``"id" + id<k>`` unpadded, as
``paste0`` writes it (small: id1, id4; medium: id1, id2, id4, id5; x and
big: id1-id6); then ``v1`` (x) or ``v2`` (the others), ``round(runif(max =
100), 6)``, drawn here as a whole number of millionths. A seeded numpy
generator, not upstream's R script: the shapes, the key sets and their split
follow it, the draws do not. The strings are made by Arrow's kernels in
bulk, not formatted row by row. A rehearsal's number is the share of ``rows`` that is made; the two
smaller key sets then keep at least 10 keys, so that every split is there.
Imports nothing of the program.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

TABLES = ("x", "small", "medium", "big")


def split_xlr(rng, n: int) -> dict:
    """``sample.int(1.1 n)`` cut into the keys of both sides (``x``), of
    the left only (``l``) and of the right only (``r``)."""
    key = rng.permutation(np.arange(1, n + n // 10 + 1, dtype=np.int64))
    common = n - n // 10
    return {"x": key[:common], "l": key[common:n], "r": key[n:]}


def sample_all(rng, keys: np.ndarray, size: int) -> np.ndarray:
    """Every key at least once, the rest drawn from them with replacement,
    in a random order: upstream's ``sample_all``, with the keys put at
    random places of the draw instead of a shuffle of the whole."""
    out = rng.choice(keys, size, replace=True)
    out[rng.choice(size, len(keys), replace=False)] = keys
    return out


def id_strings(values: np.ndarray) -> pa.Array:
    """``"id" + str(v)`` of every value, unpadded."""
    return pc.binary_join_element_wise(
        "id", pa.array(values).cast(pa.string()), "")


def tables(cfg: dict, seed: int, rehearse: float | None = None) -> dict:
    n = int(cfg["rows"])
    if rehearse is not None:
        n = max(100, int(n * rehearse))
    rng = {t: np.random.default_rng(np.random.SeedSequence([seed, i]))
           for i, t in enumerate(("keys",) + TABLES)}
    key1 = split_xlr(rng["keys"], max(n // 10**6, 10))
    key2 = split_xlr(rng["keys"], max(n // 10**3, 10))
    key3 = split_xlr(rng["keys"], n)

    def left(key):
        return np.concatenate([key["x"], key["l"]])

    def right(key):
        return np.concatenate([key["x"], key["r"]])

    r = {t: rng[t] for t in TABLES}
    ids = {
        "x": [sample_all(r["x"], left(key1), n),
              sample_all(r["x"], left(key2), n),
              r["x"].permutation(left(key3))],
        "small": [r["small"].permutation(right(key1))],
        "medium": [sample_all(r["medium"], right(key1), len(right(key2))),
                   r["medium"].permutation(right(key2))],
        "big": [sample_all(r["big"], right(key1), n),
                sample_all(r["big"], right(key2), n),
                r["big"].permutation(right(key3))],
    }
    # the value: round(runif(max = 100), 6), a whole number of millionths
    values = {t: r[t].integers(0, 10**8, len(ids[t][0])) / 1e6
              for t in TABLES}
    # the strings, column by column on threads: Arrow's kernels let go of
    # the interpreter
    with ThreadPoolExecutor(6) as pool:
        strings = {t: list(pool.map(id_strings, ids[t])) for t in TABLES}
    out = {}
    for t in TABLES:
        cols = {f"id{i + 1}": pa.array(k) for i, k in enumerate(ids[t])}
        cols.update({f"id{i + 4}": s for i, s in enumerate(strings[t])})
        cols["v1" if t == "x" else "v2"] = pa.array(values[t])
        out[t] = pa.table(cols)
    return out
