"""The data a configuration runs over: one module a data set.

A configuration names its data set under ``dataset`` (``tpch`` where the key
is absent); ``perf/datasets/<name>.py`` holds one function,

    tables(cfg, seed, rehearse=None) -> {table name: Arrow table}

which makes every table from the configuration's own size keys and the seed,
on the host, in bulk. ``rehearse`` is ``--rehearse-sf``'s number and takes the
size keys' place: what it means (a scale factor, a share of the rows) is the
data set's to say. Imports nothing of the program.
"""

from __future__ import annotations

import importlib.util
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent


def name_of(cfg: dict) -> str:
    return cfg.get("dataset", "tpch")


def load(cfg: dict):
    """The module of the configuration's data set, found by file: the name
    ``datasets`` is also an installed package's."""
    name = name_of(cfg)
    path = HERE / "datasets" / f"{name}.py"
    if not (isinstance(name, str) and name.isidentifier() and path.is_file()):
        have = sorted(p.stem for p in (HERE / "datasets").glob("*.py"))
        raise SystemExit(f"configuration: no data set {name!r} under "
                         f"perf/datasets/; there are {have}")
    if str(HERE) not in sys.path:
        sys.path.insert(0, str(HERE))
    spec = importlib.util.spec_from_file_location(f"perf_dataset_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
