#!/usr/bin/env python3
"""The controls of ``correct``: the plain reference put in the program's
place and computed in a lower precision. Each has to come out as not correct.

    python3 perf/control.py --traffic power --seeds 11 12 13
                            [--config tpch-sf1-mem] [--sf 0.05]
                            [--precision float32|bfloat16]

The data is the configuration's data set at its own size; ``--sf`` takes the
size's place as ``--rehearse-sf`` does in ``run.py``.

``float32`` is the nearest precision below the float64 the configurations
state, and the step a later PR would be tempted by (float64 is emulated on
this chip). ``bfloat16`` rounds every float column to bfloat16 as it is read
and computes in float32: the step below the f32-split matmul through which
the program already sums q1's prices and discounts (``ops/pallas_agg.py``),
and the upper reading of ``relerr_q1_money``, which float32 does not separate
(PERF.md §2).

For every seed it prints each number compared beside its limit and whether
``verify.judge`` fails the control. The smallest value a number shows over
the seeds is the upper reading its limit was set under. Needs no chip and
nothing of the program.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import dataset  # noqa: E402
import traffic  # noqa: E402
import verify  # noqa: E402


def to_bfloat16(column):
    """A float column rounded to bfloat16, back as float32."""
    import ml_dtypes
    import pandas as pd

    rounded = column.to_numpy().astype(ml_dtypes.bfloat16).astype(np.float32)
    return pd.Series(rounded, index=column.index)


PRECISIONS = {
    "float32": {"real": np.float32},
    "bfloat16": {"real": np.float32, "quantize": to_bfloat16},
}


def control_run(mix: dict, sf: float | None, seed: int,
                precision: str = "float32", cfg: dict | None = None) -> dict:
    """One seed: the lower-precision reference judged as if it were the
    program's answers. ``cfg`` is the configuration whose data set it is
    (TPC-H without one), ``sf`` the rehearsal's size or ``None`` for the
    configuration's own. Returns ``verify.judge``'s verdict."""
    import pyarrow as pa

    cfg = cfg or {}
    templates = traffic.load_templates(dict.fromkeys(mix["templates"]))
    pool = traffic.pool(mix, templates)
    frames = verify.frames(dataset.load(cfg).tables(cfg, seed, sf),
                           templates)
    answers, references = [], {}
    for name, mod in templates.items():
        for k, params in enumerate(pool[name]):
            references[(name, k)] = mod.reference(frames, params)
            low = mod.reference(frames, params, **PRECISIONS[precision])
            answers.append(
                (name, k, pa.Table.from_pandas(low, preserve_index=False))
            )
    return verify.judge(answers, templates, references, 0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", default="power")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--config", default="tpch-sf1-mem",
                    help="a configuration under perf/configs/")
    ap.add_argument("--sf", type=float, default=None)
    ap.add_argument("--precision", choices=sorted(PRECISIONS),
                    default="float32")
    args = ap.parse_args()
    mix = traffic.load(args.traffic)
    cfg = json.loads((HERE / "configs" / f"{args.config}.json").read_text())
    passed = 0
    for seed in args.seeds:
        v = control_run(mix, args.sf, seed, args.precision, cfg)
        print(json.dumps({
            "seed": seed, "config": args.config, "sf": args.sf,
            "precision": args.precision,
            "control_correct": v["correct"], "numbers": v["numbers"],
        }), flush=True)
        passed += bool(v["correct"])
    if passed:
        print(f"the control passed as correct on {passed} seed(s)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
