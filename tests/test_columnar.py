"""Substrate tests: DeviceBatch round trips, padding, dictionaries, nulls."""

import numpy as np
import pyarrow as pa

from ballista_tpu.columnar import (
    DeviceBatch,
    batch_from_arrow,
    batch_to_arrow,
    round_capacity,
    table_from_arrow,
)
from ballista_tpu.datatypes import DataType


def test_round_capacity():
    assert round_capacity(0) == 2048
    assert round_capacity(2048) == 2048
    assert round_capacity(2049) == 4096
    assert round_capacity(100_000) == 131072


def test_arrow_roundtrip(sample_table):
    rb = batch_from_arrow(sample_table)
    assert rb.capacity == round_capacity(1000)
    assert rb.num_rows() == 1000
    back = batch_to_arrow(rb)
    assert back.num_rows == 1000
    for name in ("id", "grp", "qty"):
        assert back.column(name).to_pylist() == sample_table.column(name).to_pylist()
    np.testing.assert_allclose(
        back.column("price").to_numpy(), sample_table.column("price").to_numpy()
    )
    assert back.column("flag").to_pylist() == sample_table.column("flag").to_pylist()
    assert back.column("ship").to_pylist() == sample_table.column("ship").to_pylist()


def test_table_slicing_shares_dictionary(sample_table):
    batches = table_from_arrow(sample_table, batch_rows=300)
    assert len(batches) == 4
    d0 = batches[0].dictionaries["flag"]
    for b in batches[1:]:
        assert b.dictionaries["flag"].values == d0.values
    total = sum(b.num_rows() for b in batches)
    assert total == 1000


def test_nulls_roundtrip():
    t = pa.table({"x": pa.array([1, None, 3, None], type=pa.int64())})
    rb = batch_from_arrow(t)
    assert rb.null_mask("x") is not None
    back = batch_to_arrow(rb)
    assert back.column("x").to_pylist() == [1, None, 3, None]


def test_decimal_to_f64():
    import decimal

    t = pa.table(
        {"d": pa.array([decimal.Decimal("1.50"), decimal.Decimal("2.25")])}
    )
    rb = batch_from_arrow(t)
    assert rb.schema.field("d").dtype == DataType.FLOAT64
    np.testing.assert_allclose(
        np.asarray(rb.column("d"))[:2], [1.5, 2.25]
    )


def test_string_predicate_via_dictionary(sample_table):
    rb = batch_from_arrow(sample_table)
    d = rb.dictionaries["flag"]
    code = d.index_of("B")
    assert code >= 0
    mask = np.asarray(rb.column("flag"))[: rb.num_rows()] == code
    expected = np.array(sample_table.column("flag").to_pylist()) == "B"
    np.testing.assert_array_equal(mask, expected)


def test_all_null_string_column():
    t = pa.table({"s": pa.array([None, None], type=pa.string())})
    back = batch_to_arrow(batch_from_arrow(t))
    assert back.column("s").to_pylist() == [None, None]


def test_null_type_column():
    t = pa.table({"n": pa.nulls(3)})
    back = batch_to_arrow(batch_from_arrow(t))
    assert back.column("n").to_pylist() == [None, None, None]


def test_uint64_overflow_is_schema_error():
    import pytest
    from ballista_tpu.errors import SchemaError

    t = pa.table({"u": pa.array([2**63 + 5], type=pa.uint64())})
    with pytest.raises(SchemaError):
        batch_from_arrow(t)


def test_tz_timestamp_normalized_to_utc():
    t = pa.table({"ts": pa.array([1_000_000, 2_000_000], type=pa.timestamp("us", tz="UTC"))})
    back = batch_to_arrow(batch_from_arrow(t))
    assert back.schema.field("ts").type == pa.timestamp("us")
    assert [x.timestamp() for x in back.column("ts").to_pylist()] == [1.0, 2.0]


def test_concat_unifies_many_dictionaries_in_one_merge():
    """Three batches whose string column was encoded apart: one sorted union,
    a remap table each, and one entry of the ``task.dict_merge`` phase for the
    column; batches that share a dictionary object merge nothing."""
    from ballista_tpu.compilecache import metrics
    from ballista_tpu.ops.concat import concat_batches

    def merges():
        return metrics.snapshot().get("phase.task.dict_merge.count", 0)

    words = [["pear", "apple", "pear"], ["fig", "apple"], ["zest", "fig", "kiwi"]]
    batches = [
        batch_from_arrow(pa.table({"s": pa.array(w), "n": pa.array(range(len(w)))}))
        for w in words
    ]
    assert len({b.dictionaries["s"].values for b in batches}) == 3
    before = merges()
    out = concat_batches(batches)
    assert merges() == before + 1
    assert out.dictionaries["s"].values == ("apple", "fig", "kiwi", "pear", "zest")
    live = np.asarray(out.valid)
    got = np.asarray(out.columns[0])[live]
    assert [out.dictionaries["s"].values[c] for c in got] == sum(words, [])
    before = merges()
    same = concat_batches([batches[0], batches[0]])
    assert merges() == before  # the same object: not even compared
    assert same.dictionaries["s"] is batches[0].dictionaries["s"]
