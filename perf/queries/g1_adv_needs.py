"""What the templates of h2oai's advanced group-by questions (``g1q6.py``,
``g1q8.py``) ask of the checkout they run in, checked as each is loaded,
before any work: ``g1_needs.py`` says why a template checks at all.

A program from before PR 33 answers both questions, but its first run in a
checkout (an empty compile cache) does not end inside a run's 360 s: the two
sorts are by a float64 ascending and descending, and each is an argsort
program of its own that costs the TPU's compiler minutes (``PERF.md`` §6, PR
33: the parent's cold run was still warming up when it was stopped at 450 s;
on a warm cache it ends, correct, in 116 s). The repair (a float64 key sorts
as two int32 passes) came with the window and percentile operators' counters
(``holistic.tasks`` and the others, which the cell's per-layer metrics read),
and their declaration in the program's counter store is what is looked for:
in the file's text, since a template imports nothing of the program.
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
DECLARED_IN = ROOT / "ballista_tpu" / "compilecache" / "metrics.py"
COUNTER = '"holistic.tasks"'


def check(template: str) -> None:
    if DECLARED_IN.is_file() and COUNTER in DECLARED_IN.read_text():
        return
    print(f"perf: template {template}: this checkout's program does not "
          f"declare {COUNTER} ({DECLARED_IN.relative_to(ROOT)}): it is from "
          "before PR 33, and its cold run of the advanced group-by cell does "
          "not end inside a run's 360 s (perf/queries/g1_adv_needs.py)",
          file=sys.stderr, flush=True)
    raise SystemExit(2)
