"""ballista_tpu — a TPU-native distributed SQL query engine.

A ground-up rebuild of the capabilities of Apache Arrow Ballista
(reference: /root/reference, a Rust engine built on DataFusion/Arrow/Flight)
designed TPU-first:

- Columnar data lives on device as padded, statically-shaped JAX arrays
  (``ballista_tpu.columnar``); strings are dictionary-encoded host-side.
- All operator kernels (filter, projection, hash aggregate, hash join, sort,
  hash partition) are XLA programs (``ballista_tpu.ops``) — no numpy stand-ins
  on the compute path.
- The engine substrate the reference outsources to DataFusion (SQL parser →
  logical plan → optimizer → physical plan) is built here
  (``ballista_tpu.sql``, ``ballista_tpu.plan``, ``ballista_tpu.exec``).
- Distribution follows the reference's architecture (scheduler splits physical
  plans into query stages at repartition boundaries; executors run stage
  partitions as tasks) with two shuffle tiers: on-pod exchange via
  ``jax.lax.all_to_all`` over ICI inside jitted stage programs
  (``ballista_tpu.parallel``), and cross-pod / CPU-compat exchange via Arrow
  IPC files served over Arrow Flight (``ballista_tpu.executor``).

Layer map mirrors the reference (see SURVEY.md §1):
  client   -> ballista_tpu.client   (BallistaContext: ref ballista/rust/client/src/context.rs:76-308)
  scheduler-> ballista_tpu.scheduler(ref ballista/rust/scheduler/src)
  executor -> ballista_tpu.executor (ref ballista/rust/executor/src)
  core     -> ballista_tpu.{plan,exec,serde,config,errors}
  engine   -> ballista_tpu.{sql,ops,columnar}  (the DataFusion-equivalent substrate)
"""

import os as _os

import jax as _jax

# A SQL engine needs real 64-bit columns: int64 keys (TPC-H orderkey exceeds
# 2^31 at SF100) and float64 money sums. JAX's default silently downcasts to
# 32-bit, which corrupts both — enable x64 before any array is created.
_jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: a query plan compiles one XLA program per
# (operator, batch capacity), and one large sort program costs the TPU
# compiler 10-55 s (PERF.md), so caching across processes is the difference
# between minutes and milliseconds on re-runs of the same query shapes.


def resolve_jax_cache_dir() -> str | None:
    """The ONE rule for where compiled programs (and the plan-hint file that
    rides the same directory) persist (docs/compile_cache.md):

    - ``BALLISTA_TPU_JAX_CACHE=off`` -> None: no persistence at all;
    - ``JAX_COMPILATION_CACHE_DIR`` set -> that directory. JAX reads the
      variable itself; this package sets no directory in code;
    - otherwise ``<checkout>/.jax_cache``, resolved from this package's own
      path. The path is part of the cache key, so it is never a temporary
      name, a pid or a time.
    """
    if _os.environ.get("BALLISTA_TPU_JAX_CACHE") == "off":
        return None
    return _os.environ.get("JAX_COMPILATION_CACHE_DIR") or _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache",
    )


# The resolved cache decision — the first thing to check when cold-start
# regresses (a wrong/unwritable dir silently degrades every cold run to
# full XLA compiles). Logged here for embedders whose logging is already
# configured; the daemon entrypoints re-log it AFTER their basicConfig
# (this import-time record predates any handler in those processes).
jax_cache_dir: str | None = resolve_jax_cache_dir()

if jax_cache_dir is None:
    # off disables the cache MACHINERY, not just the directory: leaving
    # jax's default cache config half-armed still pays the per-compile
    # eligibility walk
    _jax.config.update("jax_enable_compilation_cache", False)
else:
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _jax.config.update("jax_compilation_cache_dir", jax_cache_dir)
    # the engine's vocabulary is dominated by sub-0.5s kernels
    # (argsort/gather per capacity bucket) whose FIRST cold run is exactly
    # what the cache exists to kill — jax's 0.5s default floor would never
    # persist them
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

import logging as _logging

_logging.getLogger(__name__).info(
    "jax persistent compilation cache: %s", jax_cache_dir or "disabled"
)

__version__ = "0.1.0"

from ballista_tpu.config import BallistaConfig
from ballista_tpu.errors import BallistaError

__all__ = ["BallistaConfig", "BallistaError", "__version__"]
