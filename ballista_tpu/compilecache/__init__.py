"""compilecache — the compile-latency subsystem (docs/compile_cache.md).

Cold-start on a JAX/XLA engine is compile latency: every distinct
``(kernel, capacity-bucket, dtype-tuple)`` signature pays tracing + XLA
compilation once per process (PERF.md §2: `first_setup_s` against
`setup_s` of every cell).
This package attacks it end to end:

- :mod:`registry` — the CLOSED kernel vocabulary, its AOT signature
  enumeration, and the closed-vocabulary gate (CI fails when the
  vocabulary grows silently).
- :mod:`prewarm` — AOT compilation of the vocabulary at context/executor
  start (``ballista.tpu.prewarm`` on/off/background).
- :mod:`tracecache` — process-wide jitted-callable sharing keyed by
  canonical plan signature (fresh per-task plan instances stop
  re-tracing identical programs).
- :mod:`metrics` — trace/compile/persistent-cache counters surfaced via
  executor heartbeats and the scheduler REST state.
- :mod:`hints` — persisted plan-shape hints (learned join strategies,
  shrink/state capacities, the grown aggregate capacity) next to the XLA
  cache, so a fresh process skips the adaptive-learning half of
  cold-start, not just the compile half.

Shape canonicalization (the capacity-bucket ladder every static shape
rounds through) lives with the batch type in
:mod:`ballista_tpu.columnar.batch`; this package consumes it for prewarm
enumeration.
"""

from ballista_tpu.compilecache import (
    hints,
    metrics,
    prewarm,
    registry,
    tracecache,
)
from ballista_tpu.compilecache.hints import HintStore
from ballista_tpu.compilecache.prewarm import PrewarmHandle, start_prewarm
from ballista_tpu.compilecache.tracecache import (
    expr_key,
    schema_key,
    shared_callable,
)

__all__ = [
    "HintStore",
    "PrewarmHandle",
    "expr_key",
    "hints",
    "metrics",
    "prewarm",
    "registry",
    "schema_key",
    "shared_callable",
    "start_prewarm",
    "tracecache",
]
