"""TPC-H: the eight tables at the configuration's ``scale_factor``
(``perf/datagen.py``). A rehearsal's number is the scale factor itself."""

import datagen


def tables(cfg: dict, seed: int, rehearse: float | None = None) -> dict:
    scale = cfg["scale_factor"] if rehearse is None else rehearse
    return datagen.gen_all(scale, seed)
