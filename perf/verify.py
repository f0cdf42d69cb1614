"""The comparison that decides ``correct``: every answer the window returned
against the plain reference of its template at the same parameters.

Keys, counts and the ORDER BY order are exact; float aggregates are held to a
relative error, whose largest value per template is the number compared (the
limits and the readings they were set from are in ``PERF.md``). Imports
nothing of the program.
"""

from __future__ import annotations

import numpy as np
import pandas as pd
import pyarrow as pa
from pandas.api.types import is_float_dtype


def frames(tables: dict, templates: dict) -> dict:
    """pandas frames of the columns the templates' references read. Dates
    become int32 days, strings categoricals (6M Python strings are slow)."""
    want: dict[str, list[str]] = {}
    for mod in templates.values():
        for table, cols in mod.COLUMNS.items():
            have = want.setdefault(table, [])
            have.extend(c for c in cols if c not in have)
    out = {}
    for table, cols in want.items():
        t = normalise(tables[table].select(cols))
        out[table] = t.to_pandas()
    return out


def normalise(t: pa.Table) -> pa.Table:
    """Dates as int32 days, strings dictionary-encoded, chunks combined."""
    cols = []
    for col in t.columns:
        col = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
        if pa.types.is_date32(col.type):
            col = col.cast(pa.int32())
        elif pa.types.is_string(col.type) or pa.types.is_large_string(col.type):
            col = col.dictionary_encode()
        cols.append(col)
    return pa.table(cols, names=t.column_names)


def answer_frame(t: pa.Table) -> pd.DataFrame:
    """An answer as delivered, with dates as days and strings as strings."""
    cols = []
    for col in t.columns:
        if pa.types.is_date32(col.type):
            col = col.cast(pa.int32())
        elif pa.types.is_dictionary(col.type):
            col = col.cast(pa.string())
        cols.append(col)
    return pa.table(cols, names=t.column_names).to_pandas()


def compare(got: pd.DataFrame, want: pd.DataFrame, order,
            by_column: dict | None = None) -> tuple[str, float]:
    """Hold one answer to its reference. Returns (what differs in the exact
    part, or "" if nothing does; the largest relative error of a float).
    ``by_column`` collects the largest error of each float column."""
    if got.shape != want.shape:
        return f"shape {got.shape}, want {want.shape}", 0.0
    # the order the query's ORDER BY fixes, on the answer as delivered
    for i in range(1, len(got)):
        for pos, asc in order:
            a, b = got.iloc[i - 1, pos], got.iloc[i, pos]
            if a == b:
                continue
            if (a < b) != asc:
                return f"row {i} breaks ORDER BY column {pos}", 0.0
            break

    # ties under ORDER BY leave row order open: align both sides on a total
    # order over every non-float column before comparing values
    def canon(df):
        df = df.copy()
        df.columns = range(df.shape[1])
        ordered = [p for p, _ in order]
        by = ordered + [
            i for i in df.columns
            if i not in ordered and not is_float_dtype(df[i])
        ]
        if not by:
            return df
        return df.sort_values(by, kind="stable").reset_index(drop=True)

    g, w = canon(got), canon(want)
    worst = 0.0
    for i in g.columns:
        a, b = g[i], w[i]
        if is_float_dtype(b):
            a = a.to_numpy(dtype=float)
            b = b.to_numpy(dtype=float)
            if not np.all(np.isfinite(a)):
                return f"column {i} not finite", 0.0
            err = np.abs(a - b) / np.maximum(np.abs(b), 1e-300)
            col_err = float(err.max()) if len(err) else 0.0
            worst = max(worst, col_err)
            if by_column is not None:
                name = str(want.columns[i])
                by_column[name] = max(by_column.get(name, 0.0), col_err)
        elif list(a) != list(b):
            return f"column {i} differs", 0.0
    return "", worst


def judge(answers, templates: dict, references: dict, failed: int) -> dict:
    """``answers`` are (template, pool index, Arrow table) of every query the
    window completed; ``references`` maps (template, pool index) to its
    frame. A template's ``LIMITS`` maps the name of a number to the float
    columns it is the largest relative error of (``None``: all) and its
    limit. Returns the numbers compared, each with its limit, and
    ``correct``."""
    by_column: dict[str, dict] = {t: {} for t in templates}
    mismatched = 0
    first = ""
    for template, k, table in answers:
        what, _ = compare(
            answer_frame(table), references[(template, k)],
            templates[template].ORDER, by_column[template],
        )
        if what:
            mismatched += 1
            first = first or f"{template}[{k}]: {what}"
    numbers = {}
    for t, mod in templates.items():
        for name, (columns, limit) in mod.LIMITS.items():
            errs = [e for c, e in by_column[t].items()
                    if columns is None or c in columns]
            numbers[name] = {"value": max(errs, default=0.0), "limit": limit}
    numbers["mismatched"] = {"value": mismatched, "limit": 0}
    numbers["failed"] = {"value": failed, "limit": 0}
    numbers["answered"] = {"value": len(answers), "at_least": 1}
    correct = len(answers) > 0 and all(
        n["value"] <= n["limit"] for n in numbers.values() if "limit" in n
    )
    return {"correct": correct, "numbers": numbers, "first_mismatch": first,
            "by_column": by_column}
