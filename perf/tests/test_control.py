"""The control: the reference computed in float32 has to come out as not
correct, and the float64 reference judged against itself as correct."""

import pyarrow as pa

import control
import datagen
import traffic
import verify


import pytest


@pytest.mark.parametrize("precision,fails", [
    # float32 is caught by every number but q1's money columns, which the
    # program itself sums through an f32-split matmul; bfloat16 by all
    ("float32", {"relerr_q1_qty", "relerr_q6", "relerr_q3"}),
    ("bfloat16", {"relerr_q1_qty", "relerr_q1_money", "relerr_q6",
                  "relerr_q3"}),
])
def test_control_is_not_correct(precision, fails):
    mix = traffic.load("power")
    for seed in (1, 2, 3):
        verdict = control.control_run(mix, 0.05, seed, precision)
        assert not verdict["correct"]
        n = verdict["numbers"]
        over = {k for k in n if "limit" in n[k] and n[k]["value"] > n[k]["limit"]}
        assert fails <= over


def test_float64_reference_is_correct():
    mix = traffic.load("power")
    templates = traffic.load_templates(dict.fromkeys(mix["templates"]))
    pool = traffic.pool(mix, templates)
    frames = verify.frames(datagen.gen_all(0.01, 5), templates)
    refs = {(t, k): templates[t].reference(frames, p)
            for t in templates for k, p in enumerate(pool[t])}
    answers = [(t, k, pa.Table.from_pandas(r, preserve_index=False))
               for (t, k), r in refs.items()]
    assert verify.judge(answers, templates, refs, 0)["correct"]


def test_control_is_not_correct_on_the_q3_mix():
    """``load-q3-4c`` draws q3's parameters anew (the pool is seeded by the
    template's place in the mix): its one number separates float32 too."""
    mix = traffic.load("load-q3-4c")
    for seed in (1, 2, 3):
        verdict = control.control_run(mix, 0.05, seed)
        n = verdict["numbers"]
        assert not verdict["correct"]
        assert n["relerr_q3"]["value"] > n["relerr_q3"]["limit"]
