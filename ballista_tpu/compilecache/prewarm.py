"""AOT kernel prewarm: compile the closed vocabulary before the first
query needs it.

``ballista.tpu.prewarm`` (and, for executor processes, the
``BALLISTA_TPU_PREWARM`` env the server loops read at start):

- ``on`` — compile every enumerated signature synchronously before
  returning; startup blocks until warm (serving tiers that must never
  show a cold first query).
- ``background`` — compile on a small daemon thread pool while the
  process serves; queries that arrive mid-warm pay at most the kernels
  not yet done. The pool is JOINED by ``ExecutorServer.stop`` /
  ``PollLoop.stop`` (zero-thread-leak shutdown audit,
  tests/test_shutdown_hygiene.py).
- ``off`` — lazy compiles on first use (default).

Compiles release the GIL inside XLA, so a few workers overlap well; each
completed signature increments ``prewarmed_signatures`` and its wall time
lands in ``prewarm_seconds`` (compilecache.metrics), so the heartbeat/REST
path shows warm-up progress per executor. A process-wide latch makes
repeated prewarm requests (several contexts in one process) free.
"""

from __future__ import annotations

import logging
import os
import threading
import time

from ballista_tpu.compilecache import metrics, registry

log = logging.getLogger(__name__)

_WORKERS = 4

_LATCH_LOCK = threading.Lock()
_STARTED: set[str] = set()  # fingerprints already prewarmed this process


class PrewarmHandle:
    """A running (or finished) prewarm; ``join``/``stop`` are idempotent
    and safe from any thread."""

    def __init__(self, pool=None, futures=(), n_signatures: int = 0):
        from ballista_tpu.analysis import reswitness

        self._pool = pool
        self._futures = list(futures)
        self.n_signatures = n_signatures
        self._witness_token = (
            reswitness.acquire("thread-pool", "compile-prewarm")
            if pool is not None
            else None
        )
        # a TpuContext-started background prewarm is never stopped or
        # joined — the pool drains on its own (start_prewarm calls
        # shutdown(wait=False) right after the submits) — so the witness
        # entry must also self-release when the LAST future completes,
        # or assert_drained() would report a false leak for a pool whose
        # workers exited long ago. release() is idempotent: racing
        # _shutdown() is harmless.
        self._pending = len(self._futures)
        self._pending_lock = threading.Lock()
        if pool is not None and not self._futures:
            self._release_witness()
        for f in self._futures:
            f.add_done_callback(self._one_done)

    def _release_witness(self) -> None:
        from ballista_tpu.analysis import reswitness

        reswitness.release(self._witness_token)

    def _one_done(self, _f) -> None:
        with self._pending_lock:
            self._pending -= 1
            drained = self._pending == 0
        if drained:
            self._release_witness()

    def join(self, timeout: float | None = None) -> bool:
        """Wait for completion; True when every signature finished."""
        import concurrent.futures as cf

        deadline = None if timeout is None else time.monotonic() + timeout
        for f in self._futures:
            left = None
            if deadline is not None:
                left = max(0.0, deadline - time.monotonic())
            try:
                f.result(timeout=left)
            # 3.10: cf.TimeoutError/CancelledError are not the builtins
            except (cf.TimeoutError, TimeoutError):
                return False
            except cf.CancelledError:
                pass
            except Exception as e:  # noqa: BLE001
                # _compile_one already logged the compile failure; anything
                # ELSE escaping a worker must not vanish (lifelint
                # swallowed-error)
                log.debug("prewarm join: worker raised %s", e)
        self._shutdown(wait=True)
        return True

    def stop(self, timeout: float = 30.0) -> None:
        """Cancel queued work and join the pool threads (shutdown path:
        in-flight compiles finish — XLA compiles are not interruptible —
        queued ones are dropped). If in-flight compiles outlast
        ``timeout``, the pool is left to drain on its own rather than
        hanging shutdown (one TPU sort program takes tens of seconds to
        compile; its worker thread exits right after it)."""
        import concurrent.futures as cf

        for f in self._futures:
            f.cancel()
        deadline = time.monotonic() + timeout
        for f in self._futures:
            left = max(0.0, deadline - time.monotonic())
            try:
                f.result(timeout=left)
            except (cf.TimeoutError, TimeoutError):
                log.warning(
                    "prewarm stop: in-flight compiles still running after "
                    "%.0fs; leaving the pool to drain", timeout,
                )
                self._shutdown(wait=False)
                return
            except cf.CancelledError:
                pass
            except Exception as e:  # noqa: BLE001
                log.debug("prewarm stop: worker raised %s", e)
        self._shutdown(wait=True)

    def _cancel_queued(self) -> None:
        """atexit safety net: a caller that never stops its handle (a
        short-lived script's TpuContext) must not hang interpreter exit
        while the non-daemon pool drains dozens of queued compiles —
        cancel the queue; only in-flight compiles finish."""
        for f in self._futures:
            f.cancel()

    def _shutdown(self, wait: bool) -> None:
        import atexit

        atexit.unregister(self._cancel_queued)
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=wait, cancel_futures=not wait)
        self._release_witness()


_NOOP = PrewarmHandle()


def _compile_one(sig) -> None:
    t0 = time.perf_counter()
    try:
        sig.compile()
    except Exception as e:  # noqa: BLE001 — prewarm is best-effort
        # a failed prewarm costs only a lazy compile later; the query
        # path must never depend on prewarm having succeeded
        log.warning("prewarm %s failed: %s", sig.key, e)
        metrics.add("prewarm_failures")
        return
    metrics.add("prewarmed_signatures")
    metrics.add("prewarm_seconds", time.perf_counter() - t0)


def prewarm_buckets_from_env(default: tuple[int, ...]) -> tuple[int, ...]:
    """BALLISTA_TPU_PREWARM_BUCKETS="2048,1048576" overrides the ladder
    enumeration — tests and constrained hosts bound the warm set."""
    spec = os.environ.get("BALLISTA_TPU_PREWARM_BUCKETS", "")
    if not spec:
        return default
    return tuple(int(s) for s in spec.split(",") if s.strip())


def start_prewarm(
    mode: str,
    max_rows: int | None = None,
    buckets: tuple[int, ...] | None = None,
    once: bool = True,
) -> PrewarmHandle:
    """Kick a prewarm per ``mode``; returns a handle (no-op handle for
    ``off``/already-warmed). ``max_rows`` bounds the ladder enumeration
    (defaults to the configured device-batch row budget)."""
    if mode not in ("on", "background"):
        return _NOOP
    metrics.install()
    if buckets is None:
        from ballista_tpu.columnar.batch import capacity_ladder
        from ballista_tpu.config import BallistaConfig

        if max_rows is None:
            max_rows = BallistaConfig().tpu_batch_rows()
        buckets = capacity_ladder().buckets_upto(max_rows)
    buckets = prewarm_buckets_from_env(tuple(buckets))
    fingerprint = ",".join(str(b) for b in sorted(buckets))
    if once:
        with _LATCH_LOCK:
            if fingerprint in _STARTED:
                return _NOOP
            _STARTED.add(fingerprint)
    try:
        sigs = registry.enumerate_prewarm(buckets)
    except BaseException:
        # roll the latch back: a failed enumeration (bad bucket spec, a
        # registry bug) must not permanently disable prewarm for this
        # bucket set in this process (the latch leaked "started" state
        # for work that never started)
        if once:
            with _LATCH_LOCK:
                _STARTED.discard(fingerprint)
        raise
    log.info(
        "prewarm(%s): %d signatures over buckets %s",
        mode, len(sigs), list(buckets),
    )
    if mode == "on":
        t0 = time.perf_counter()
        for sig in sigs:
            _compile_one(sig)
        log.info(
            "prewarm: %d signatures in %.1fs",
            len(sigs), time.perf_counter() - t0,
        )
        return PrewarmHandle(n_signatures=len(sigs))
    from concurrent.futures import ThreadPoolExecutor

    pool = ThreadPoolExecutor(
        max_workers=_WORKERS, thread_name_prefix="compile-prewarm"
    )
    futures = [pool.submit(_compile_one, sig) for sig in sigs]
    # non-blocking shutdown immediately after the last submit: the pool
    # threads then exit on their own once the queue drains, so a caller
    # that never stops the handle (a long-lived TpuContext) still leaks
    # zero threads; handle.stop() additionally cancels the queue and joins
    pool.shutdown(wait=False)
    handle = PrewarmHandle(pool, futures, n_signatures=len(sigs))
    # atexit runs before threading's shutdown join of the (non-daemon)
    # workers, so un-stopped handles drop their queued compiles instead
    # of stalling process exit behind them
    import atexit

    atexit.register(handle._cancel_queued)
    return handle


def resolve_mode(explicit: str | None) -> str:
    """Prewarm mode for an executor process, which has no session config
    at start: an explicit --prewarm flag wins, else the
    BALLISTA_TPU_PREWARM env, else off."""
    if explicit is not None:
        return explicit
    return os.environ.get("BALLISTA_TPU_PREWARM", "off")


def start_server_prewarm(mode: str) -> PrewarmHandle:
    """The shared executor-server start sequence (PollLoop.start /
    ExecutorServer.startup): compile counters installed before the first
    task can trace, then the configured prewarm. A deployment with a
    non-default ladder must set BALLISTA_TPU_CAPACITY_BUCKETS alongside
    BALLISTA_TPU_PREWARM — session config arrives only with the first
    task, after prewarm has already enumerated its buckets."""
    metrics.install()
    spec = os.environ.get("BALLISTA_TPU_CAPACITY_BUCKETS")
    if spec:
        from ballista_tpu.columnar.batch import set_capacity_buckets

        set_capacity_buckets(spec)
    return start_prewarm(mode)


def reset_latch() -> None:
    """Test hook: allow the same bucket set to prewarm again."""
    with _LATCH_LOCK:
        _STARTED.clear()
