"""perf/datagen.py equals the program's generator, value for value."""

import pytest

import datagen


@pytest.mark.parametrize("seed", [42, 3_000_000_019])
def test_equals_the_programs_generator(seed):
    from ballista_tpu import tpch

    ours = datagen.gen_all(0.01, seed)
    assert tuple(ours) == tpch.TPCH_TABLES
    for name, table in ours.items():
        theirs = tpch.gen_table(name, 0.01, seed)
        assert table.schema.equals(theirs.schema), name
        assert table.equals(theirs), name


def test_same_seed_same_data_other_seed_other_data():
    a, b, c = (datagen.gen_all(0.01, s) for s in (7, 7, 8))
    assert a["lineitem"].equals(b["lineitem"])
    assert not a["lineitem"].equals(c["lineitem"])
