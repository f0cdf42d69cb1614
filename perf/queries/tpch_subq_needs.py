"""What the templates of TPC-H's outer-join, EXISTS and NOT IN queries
(``q13.py``, ``q4.py``, ``q16.py``) ask of the checkout they run in, checked
as each is loaded, before any work: ``g1_needs.py`` says why a template
checks at all.

A program from before PR 36 answers all three, correctly, but its first run
in a checkout (an empty compile cache) does not end inside a run's 360 s:
q13's ``NOT LIKE`` stayed in the join's ON clause, so ``o_comment`` and its
dictionary of 1.5M entries crossed the stage boundary and rode through the
join's programs as static data (``PERF.md`` §6, PR 36: the parent's cold run
took 463.7 s, 372.6 of them warm-up, and a q13 took 45-61 s the first time it
met a pattern even on a warm compile cache; twice that first q13 failed on a
shuffle stream dropped under it). The repair (the conjunct pushed below the
join, the predicate's table evaluated once a dictionary) came with the
counters ``dict_predicate.*`` and ``join.noninner.*``, which the cell's
per-layer metrics read, and their declaration in the program's counter store
is what is looked for: in the file's text, since a template imports nothing
of the program.
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
DECLARED_IN = ROOT / "ballista_tpu" / "compilecache" / "metrics.py"
COUNTER = '"dict_predicate.entries"'


def check(template: str) -> None:
    if DECLARED_IN.is_file() and COUNTER in DECLARED_IN.read_text():
        return
    print(f"perf: template {template}: this checkout's program does not "
          f"declare {COUNTER} ({DECLARED_IN.relative_to(ROOT)}): it is from "
          "before PR 36, and its cold run of the TPC-H subquery cell does "
          "not end inside a run's 360 s (perf/queries/tpch_subq_needs.py)",
          file=sys.stderr, flush=True)
    raise SystemExit(2)
