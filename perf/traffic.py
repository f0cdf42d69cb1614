"""The one traffic generator: closed-loop clients over query templates.

A traffic mix is a data file ``perf/traffic/<name>.json``:

    templates   the templates each client walks, by name (``perf/queries``)
    clients     how many callers, each waiting for its reply (closed loop)
    pool        parameter draws kept per template
    param_seed  the seed the pool is drawn from
    order       "shuffled": each client walks seeded permutations of its
                templates; "alternating": the list as written, client c
                starting c places in

The pool is the same for every ``--seed`` (each template's own ``draw``, from
``param_seed``): this engine compiles a program per distinct literal, so the
parameters a window may send have to have been sent once in warm-up, and a
pool that moved with the seed would make every run compile. ``--seed`` picks
the data and the order in which each client walks templates and pool.
"""

from __future__ import annotations

import importlib
import json
import pathlib
import sys

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load(name: str) -> dict:
    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    for key in ("templates", "clients", "pool", "param_seed", "order"):
        if key not in mix:
            raise SystemExit(f"traffic {name}: no {key!r}")
    if mix["order"] not in ("shuffled", "alternating"):
        raise SystemExit(f"traffic {name}: unknown order {mix['order']!r}")
    return mix


def load_templates(names) -> dict:
    """name -> module of ``perf/queries/<name>.py``, with ``.SQL`` set from
    the ``.sql`` beside it."""
    if str(HERE) not in sys.path:
        sys.path.insert(0, str(HERE))
    out = {}
    for name in names:
        mod = importlib.import_module(f"queries.{name}")
        mod.SQL = (HERE / "queries" / f"{name}.sql").read_text()
        out[name] = mod
    return out


def pool(mix: dict, templates: dict) -> dict:
    """template -> its ``pool`` parameter draws."""
    out = {}
    for i, name in enumerate(mix["templates"]):
        if name in out:
            continue
        rng = np.random.default_rng(
            np.random.SeedSequence([mix["param_seed"], i])
        )
        out[name] = [templates[name].draw(rng) for _ in range(mix["pool"])]
    return out


def walk(mix: dict, seed: int, client: int):
    """What client ``client`` sends, for ever, a round at a time: a list of
    (template, pool index), each template once. A client that has begun a
    round finishes it, so every window holds whole rounds and its rate does
    not hang on which template happened to come last."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1000 + client]))
    names = list(mix["templates"])
    cursor = {t: (rng.permutation(mix["pool"]), 0) for t in set(names)}
    while True:
        if mix["order"] == "shuffled":
            order = [names[i] for i in rng.permutation(len(names))]
        else:
            order = names[client % len(names):] + names[:client % len(names)]
        round_ = []
        for t in order:
            perm, at = cursor[t]
            if at == len(perm):
                perm, at = rng.permutation(mix["pool"]), 0
            cursor[t] = (perm, at + 1)
            round_.append((t, int(perm[at])))
        yield round_
