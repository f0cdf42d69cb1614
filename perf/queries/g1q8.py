"""h2oai db-benchmark, groupby task, question 8 ("largest two v3 by id6"), in
the SQL of upstream's ``groupby-datafusion.py``: ``row_number()`` over N/K
partitions of an int64 key, ordered by a float64 descending, and the rows
numbered 1 and 2 kept. Keys, row numbers and the number of rows (one for a
group of one) are exact. ``v3`` passes through and is computed with nowhere,
but it passes through the chip, whose float64 is a pair of float32: a value
comes back within 2e-15 of itself (``PERF.md`` §2), never bit for bit, so it
is held to a limit as the other floats are and not to 0. The limit is far
under the 1e-8 between two neighbouring values of ``v3``, so a wrong row is
a wrong value.

The select list carries the row number beside upstream's ``id6, v3``: the
comparison (``verify.compare``) aligns an answer and its reference on their
integer columns, and (id6, row) is a total order where id6 alone leaves the
two rows of a group as they were delivered, which SQL does not fix. Rows of
equal ``v3`` may take either number; what is returned is then equal too.
``q1.py`` says what a template holds; ``g1q3.py`` the task."""

import numpy as np
import pandas as pd

from queries import g1_adv_needs

g1_adv_needs.check(__name__)

COLUMNS = {"x": ["id6", "v3"]}
ORDER = []  # the question states none: answers are aligned on id6, row
LIMITS = {"relerr_g1q8_v3": (["v3"], 1e-11)}  # PERF.md §2
VALIDATION = {}


def draw(rng) -> dict:
    return {}


def reference(f, p, real=np.float64, quantize=None) -> pd.DataFrame:
    x = f["x"]
    v3 = (quantize(x.v3) if quantize else x.v3).astype(real)
    top = (pd.DataFrame({"id6": x.id6, "v3": v3})
           .sort_values(["id6", "v3"], ascending=[True, False],
                        kind="stable"))
    top["row"] = top.groupby("id6").cumcount() + 1
    return top[top.row <= 2].reset_index(drop=True)


def least_bytes(rows: dict) -> int:
    """An int64 key and a float64 of every row."""
    return rows["x"] * (8 + 8)


def sort_least_bytes(rows: dict) -> int:
    """As ``g1q6.py``'s: every row by id6, v3."""
    return rows["x"] * (8 + 8 + 4) * 2
