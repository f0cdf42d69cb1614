"""String predicates over a dictionary (``columnar/dict_util.py
predicate_table`` and its three tables): one vectorised evaluation per
(dictionary, pattern), kept with the dictionary and gone with it, equal entry
for entry to the per-entry Python evaluation it replaced; and the dictionary's
own identity, which every jit dispatch hashes."""

import re

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu.columnar import dict_util
from ballista_tpu.columnar.arrow_interop import batch_from_arrow
from ballista_tpu.columnar.batch import Dictionary
from ballista_tpu.compilecache import metrics
from ballista_tpu.expr import Like, ScalarFunction, col, compile_expr, lit

WORDS = ["special", "requests", "pending", "Customer", "Complaints", "a.b",
         "x*y", "(paren)", "[set]", "50%", "under_score", "back\\slash",
         "naïve", "日本語", "ß", "line\nbreak", "MEDIUM", "POLISHED", "^caret$",
         "q?", "pipe|", "{3}", "+plus", ""]


def seeded_dictionary(n: int, seed: int = 36) -> Dictionary:
    """``n`` distinct entries of one to four of ``WORDS`` and a number, the
    empty string among them, sorted as the engine's dictionaries are."""
    rng = np.random.default_rng(seed)
    picks = rng.integers(0, len(WORDS), (n, 4))
    cut = rng.integers(1, 5, n)
    vals = {""}
    for i in range(n):
        vals.add(" ".join(WORDS[j] for j in picks[i, : cut[i]]) + f" {i}")
        if len(vals) == n:
            break
    return Dictionary(tuple(sorted(vals)))


@pytest.fixture(scope="module")
def big():
    d = seeded_dictionary(100_000)
    assert len(d) == 100_000 and "" in d.values
    return d


def like_per_entry(values, pattern: str) -> np.ndarray:
    """The evaluation this replaced: an anchored regular expression, ``%``
    any run of characters and ``_`` one, matched against every entry."""
    rx = re.compile(
        "".join(".*" if ch == "%" else "." if ch == "_" else re.escape(ch)
                for ch in pattern),
        re.DOTALL,
    )
    return np.asarray([rx.fullmatch(s) is not None for s in values],
                      dtype=bool)


def counters() -> tuple[float, float]:
    snap = metrics.snapshot()
    return snap["dict_predicate.entries"], snap["dict_predicate.reused"]


PATTERNS = [
    "%special%requests%", "%Customer%Complaints%", "MEDIUM POLISHED%",
    "%", "", "_", "%_", "____%", "%a.b%", "%x*y%", "%(paren)%", "%[set]%",
    "%50%%", "%under_score%", "%under%score%", "%back\\slash%", "%naïve%",
    "%日本_%", "_ß%", "%line\nbreak%", "%^caret$%", "%q?%", "%pipe|%",
    "%{3}%", "%+plus%", "special%1", "%9", "% 12_4",
]


@pytest.mark.parametrize("pattern", PATTERNS)
def test_like_table_equals_the_per_entry_evaluation(big, pattern):
    got = dict_util.like_table(big, pattern)
    want = like_per_entry(big.values, pattern)
    assert got.dtype == bool and got.shape == want.shape
    assert np.array_equal(got, want), np.flatnonzero(got != want)[:5]
    if pattern not in ("", "_", "%9", "% 12_4", "special%1"):
        assert want.any()  # the pattern is one the data can match


@pytest.mark.parametrize("start,length", [
    (1, 3), (1, None), (2, 5), (5, None), (3, 0), (40, 4), (1, 1000),
    (0, 2), (-2, None),
])
def test_substr_table_equals_the_per_entry_slices(big, start, length):
    table, uniq = dict_util.substr_table(big, start, length)
    lo = start - 1
    cut = [s[lo:] if length is None else s[lo:lo + length]
           for s in big.values]
    assert uniq.values == tuple(sorted(set(cut)))
    assert [uniq.values[c] for c in table[:2000]] == cut[:2000]
    assert np.array_equal(
        table, np.asarray([uniq.index_of(s) for s in cut], dtype=np.int32))


def test_in_codes_and_index_of_by_bisection(big):
    held = [big.values[0], big.values[777], big.values[-1], ""]
    absent = ["no such entry", "special", "\U0010ffff"]
    codes = dict_util.in_codes(big, tuple(held + absent))
    assert sorted(codes.tolist()) == sorted(big.values.index(s) for s in held)
    for s in absent:
        assert big.index_of(s) == -1
    assert Dictionary(()).index_of("x") == -1
    assert len(dict_util.in_codes(Dictionary(()), ("x",))) == 0
    assert len(dict_util.like_table(Dictionary(()), "%")) == 0


def test_a_table_is_evaluated_once_a_dictionary_and_a_pattern():
    d = seeded_dictionary(5_000, seed=1)
    entries, reused = counters()
    first = dict_util.like_table(d, "%pending%")
    assert counters() == (entries + 5_000, reused)
    again = dict_util.like_table(d, "%pending%")
    assert again is first
    assert counters() == (entries + 5_000, reused + 1)
    # another pattern over the same dictionary is another evaluation
    dict_util.like_table(d, "%pending%requests%")
    assert counters() == (entries + 10_000, reused + 1)
    # a new dictionary, equal entry for entry, is evaluated anew: the table
    # was kept with the object it was computed for and goes with it
    twin = Dictionary(d.values)
    assert twin == d and twin is not d
    fresh = dict_util.like_table(twin, "%pending%")
    assert fresh is not first and np.array_equal(fresh, first)
    assert counters() == (entries + 15_000, reused + 1)
    # substr and IN tables go the same way
    dict_util.substr_table(d, 1, 2)
    _, same = dict_util.substr_table(d, 1, 2)
    assert same is dict_util.substr_table(d, 1, 2)[1]
    dict_util.in_codes(d, ("a", "b"))
    dict_util.in_codes(d, ("a", "b"))
    assert counters() == (entries + 25_000, reused + 4)
    seconds = metrics.snapshot()["phase.task.dict_predicate.seconds"]
    assert seconds > 0


def test_a_dictionary_hashes_once_and_equals_by_value():
    d = seeded_dictionary(2_000, seed=2)
    assert d._hash is None
    h = hash(d)
    assert d._hash == h == hash(d) == hash(Dictionary(d.values))
    assert d == Dictionary(tuple(d.values)) and d == d
    assert d != Dictionary(d.values[:-1]) and d != Dictionary(())
    assert d != d.values and {d: 1}[Dictionary(d.values)] == 1
    assert "2000 entries" in repr(d)
    import pickle

    back = pickle.loads(pickle.dumps(d))
    assert back == d and back._tables == {}
    assert d.arrow() is d.arrow() and d.arrow().to_pylist() == list(d.values)


@pytest.fixture(scope="module")
def batch():
    vals = ["special requests", None, "no match", "special packages",
            "pending requests special", None, "specialrequests", ""]
    return batch_from_arrow(pa.table({"s": pa.array(vals * 25)}))


@pytest.mark.parametrize("negated", [False, True])
def test_nulls_stay_null_under_not_like(batch, negated):
    e = Like(col("s"), "%special%requests%", negated=negated)
    cv = compile_expr(e, batch.schema).evaluate(batch)
    live = np.asarray(batch.valid)
    hit = np.asarray(cv.values)[live]
    null = np.asarray(cv.nulls)[live]
    want = np.asarray([True, False, False, False, False, False, True, False]
                      * 25)
    is_null = np.asarray([False, True, False, False, False, True, False,
                          False] * 25)
    assert np.array_equal(null, is_null)
    assert np.array_equal(hit[~null], (want ^ negated)[~null])
    # a NULL passes neither LIKE nor NOT LIKE: as a filter it keeps 2 or 4
    # of every 8 rows, never the NULLs
    kept = hit & ~null
    assert kept.sum() == (100 if negated else 50)


def test_expressions_take_their_tables_from_the_dictionary(batch):
    d = batch.dictionaries["s"]
    like = compile_expr(Like(col("s"), "%packages", negated=False),
                        batch.schema)
    sub = compile_expr(ScalarFunction("substr", (col("s"), lit(1), lit(7))),
                       batch.schema)
    isin = compile_expr(col("s").in_list(["no match", "absent"]),
                        batch.schema)
    for phys in (like, sub, isin):
        phys.evaluate(batch)
    entries, reused = counters()
    first = sub.evaluate(batch)
    for phys in (like, sub, isin):
        phys.evaluate(batch)
    assert counters() == (entries, reused + 4)
    assert ("like", "%packages") in d._tables
    # substr's dictionary is one object however often it is asked for, so
    # a program traced for it is found again
    assert sub.evaluate(batch).dictionary is first.dictionary
    assert first.dictionary.values == ("", "no matc", "pending", "special")
