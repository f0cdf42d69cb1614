"""Hand-run tests of the benchmark's own files (not tier-1):

    JAX_PLATFORMS=cpu python -m pytest perf/tests -q

They need no chip; those that drive the system under test do so at SF 0.01.
"""

import pathlib
import sys

PERF = pathlib.Path(__file__).resolve().parent.parent
ROOT = PERF.parent
for p in (str(ROOT), str(PERF)):
    if p not in sys.path:
        sys.path.insert(0, p)
