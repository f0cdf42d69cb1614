"""The parameterised references, at TPC-H's validation parameters, against
chip_smoke.py's literal ones on SF 0.01; and the draws stay inside clause
2.4's domains."""

import numpy as np
import pytest

import datagen
import traffic
import verify

TEMPLATES = ["q1", "q6", "q3"]


@pytest.fixture(scope="module")
def data():
    tables = datagen.gen_all(0.01, 42)
    templates = traffic.load_templates(TEMPLATES)
    return tables, templates, verify.frames(tables, templates)


@pytest.mark.parametrize("name", TEMPLATES)
def test_reference_equals_chip_smokes(data, name):
    import pyarrow as pa

    import chip_smoke

    tables, templates, frames = data
    mod = templates[name]
    ours = mod.reference(frames, mod.VALIDATION)
    theirs = chip_smoke.REFERENCES[name][0](chip_smoke.frames(tables))
    what, err = verify.compare(
        verify.answer_frame(pa.Table.from_pandas(theirs, preserve_index=False)),
        ours, mod.ORDER,
    )
    assert what == ""
    assert err <= 1e-12


@pytest.mark.parametrize("name", TEMPLATES)
def test_sql_takes_every_draw(name):
    mod = traffic.load_templates([name])[name]
    rng = np.random.default_rng(3)
    for _ in range(200):
        p = mod.draw(rng)
        mod.SQL.format(**p)
        if name == "q1":
            assert 60 <= p["delta"] <= 120
        if name == "q6":
            assert 1993 <= p["year"] <= 1997 and p["quantity"] in (24, 25)
            lo, hi = float(p["discount_lo"]), float(p["discount_hi"])
            assert 0.01 <= lo and hi <= 0.10 and round(hi - lo, 2) == 0.02
        if name == "q3":
            assert p["segment"] in mod.SEGMENTS
            assert "1995-03-01" <= p["date"] <= "1995-03-31"


def test_pool_is_the_same_for_every_seed_and_walks_differ():
    mix = traffic.load("power")
    templates = traffic.load_templates(mix["templates"])
    assert traffic.pool(mix, templates) == traffic.pool(mix, templates)
    wa, wb = traffic.walk(mix, 1, 0), traffic.walk(mix, 2, 0)
    a = [q for _ in range(8) for q in next(wa)]
    b = [q for _ in range(8) for q in next(wb)]
    assert a != b
    # every seed sends the same set: each template as often, each pool entry
    assert sorted(a) == sorted(b)
    # 4 alternating clients never all send the same template at once
    load = traffic.load("load-q1q6-4c")
    firsts = [next(traffic.walk(load, 1, c))[0][0] for c in range(4)]
    assert firsts == ["q1", "q6", "q1", "q6"]
