"""What the templates of h2oai's group-by task (``g1q*.py``) ask of the
checkout they run in, checked as each is loaded, before any work.

A program from before PR 29 answers these questions, but its first run in a
checkout (an empty compile cache) cannot end inside a run's 360 s: it compiled
for 415 s for g1q3 alone, 381 s for g1q5 and 176 s for g1q7 (my chip runs, PR
29: every state size once at the default 65,536 aggregate slots and again
after the ``CapacityError`` retry, and integer cumsums that cost the TPU's
compiler up to 300 s each). The driver lays a PR's benchmark files over the
parent commit too and tries a new cell there; a run that is still going at
its limit is killed, where one that cannot run the cell has to say so at once.
So a checkout whose program lacks the repairs that PR brought is refused here
with exit code 2, as ``run.py`` refuses a cell it does not know. The repairs
came with the aggregate's counters (``agg.capacity_retries`` and the others,
which the cell's per-layer metrics read), and their declaration in the
program's counter store is what is looked for: in the file's text, since a
template imports nothing of the program.
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
DECLARED_IN = ROOT / "ballista_tpu" / "compilecache" / "metrics.py"
COUNTER = '"agg.capacity_retries"'


def check(template: str) -> None:
    if DECLARED_IN.is_file() and COUNTER in DECLARED_IN.read_text():
        return
    print(f"perf: template {template}: this checkout's program does not "
          f"declare {COUNTER} ({DECLARED_IN.relative_to(ROOT)}): it is from "
          "before PR 29, and its cold run of the group-by cell does not end "
          "inside a run's 360 s (perf/queries/g1_needs.py)",
          file=sys.stderr, flush=True)
    raise SystemExit(2)
