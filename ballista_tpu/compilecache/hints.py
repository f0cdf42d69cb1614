"""Persisted plan-shape hints: the learned-capacity half of cold-start.

The XLA persistent cache and the shared trace cache kill the *compile*
half of a fresh process's first query, but profiling the remaining cold
gap (docs/compile_cache.md) showed the larger half is *learning*: until
the adaptive machinery has observed the data, a cold process probes join
build strategies (collecting and sorting a fact side purely for the
decision), runs merge folds at full un-sliced state capacity, pays the
aggregate overflow→grow retry round, and re-measures every shrink site —
all process-local state in ``TaskContext.plan_cache`` and the
``agg_capacity`` hint, re-derived from scratch on every restart.

This module persists that state next to the XLA cache. Safety is
inherited, not added: every plan-cache family is either deferred-
validated speculation (a stale entry fires its flag at the task boundary
→ ``SpeculationMiss`` → invalidate + re-run, exec/base.py) or learn-only
input, so a hint file from last week degrades to one extra re-run in the
worst case and can never change results. Keys/values are serialized with
``repr`` and parsed with ``ast.literal_eval`` — an entry that fails the
round-trip (device arrays must never reach a clean task boundary, but be
defensive) is silently dropped, as is the ``__build_cache_bytes__`` HBM
tally, which meters in-process build tables that die with the process.

Layout: one JSON file, ``plan_hints.json``, in the resolved hint dir —
``BALLISTA_TPU_HINT_CACHE`` when set (``off`` disables), else the XLA
cache dir (``ballista_tpu.resolve_jax_cache_dir``), so
``BALLISTA_TPU_JAX_CACHE=off`` keeps the whole persistence surface
inert. Writes are atomic
(tmp + ``os.replace``) and debounced by content fingerprint; concurrent
executors sharing a dir are last-writer-wins, which is safe for the same
reason staleness is.

Persisting is no part of a task or a collect. The owner's hot path calls
``HintStore.mark`` — a counter, a reference and an event — and goes on; one
daemon thread per store (``hint-store-writer``, started by the first mark,
ended by ``close`` or by ``_WRITER_IDLE_S`` without a mark) wakes when
marked, waits out ``WRITE_DEBOUNCE_S``, during which further marks
coalesce, and then does the one write path there is (``save_if_changed``:
fingerprint, and only if it moved read-merge-write). ``flush`` does the
same synchronously; ``close`` (the executor's stop) flushes and joins the
writer, and a clean interpreter exit flushes every store that still has a
mark pending. So a clean stop loses nothing, and a crash loses at most the
learning of the last debounce interval — a re-run's worth, by the contract
above.

Only what a later process can read is written: an entry whose key carries
a non-empty job id (``_job_scoped``: directly, as ``exec/`` scopes its
join and aggregate strategies to the job that learned them, or through a
nested strategy key) is skipped by fingerprint and document alike. A
served job's id is never seen again, so such an entry could be read by
nobody, and writing it made file and save grow with every query answered.
In memory nothing changes — the job's later tasks find its entries in the
owner's plan cache. Entries with the empty job id (the local
``TpuContext``, whose repeated collects do read them back) and the
unscoped families (``shrink``, ``aqe``, ``agg_capacity``) persist.
"""

from __future__ import annotations

import ast
import atexit
import json
import logging
import os
import tempfile
import threading
import weakref

from ballista_tpu.compilecache import metrics

log = logging.getLogger(__name__)

HINT_FILE = "plan_hints.json"
# 2: no entry keyed by a job id. A version-1 file holds up to 4096 of them
# that the merge-under write would carry along forever; it is ignored
# wholesale and replaced by the first write (one re-learn, once)
_VERSION = 2
# how long the writer lets marks coalesce before it looks at the state
WRITE_DEBOUNCE_S = 1.0
WRITER_THREAD_NAME = "hint-store-writer"
# a writer nobody has marked for this long ends; the next mark starts one.
# An owner without a close() (the local TpuContext) then keeps no thread,
# and through it itself, alive for the rest of the process
_WRITER_IDLE_S = 30.0
# matches run_with_capacity_retry's in-memory bound; a fuller file would
# just be cleared on load anyway
_MAX_ENTRIES = 4096
# process-local tallies that meter in-process objects — never persisted
_EPHEMERAL_KEYS = frozenset({"__build_cache_bytes__"})
# families whose key[1] is the id of the job that learned the entry
# (exec/joins.py _strategy_key, exec/aggregate.py); "" outside a cluster
_JOB_SCOPED_FAMILIES = frozenset({
    "join_flags", "dec_sum", "dec_sum_last", "agg_sorted",
    "agg_state_cap", "agg_state_prefix",
})
# every live store, held weakly: what the flush at a clean interpreter exit
# goes through
_STORES: "weakref.WeakSet[HintStore]" = weakref.WeakSet()


def store_path() -> str | None:
    """Resolved hint-file path, or None when persistence is off."""
    spec = os.environ.get("BALLISTA_TPU_HINT_CACHE", "")
    if not spec:
        import ballista_tpu

        spec = ballista_tpu.resolve_jax_cache_dir()
    if spec is None or spec == "off":
        return None
    return os.path.join(spec, HINT_FILE)


def _canon(x):
    """Recursively replace numpy scalars with python natives (their repr
    — ``np.True_``, ``np.int64(8)`` — does not literal_eval) so learned
    join flags and capacities survive encoding regardless of which layer
    produced them."""
    if isinstance(x, tuple):
        return tuple(_canon(v) for v in x)
    item = getattr(x, "item", None)
    if item is not None and getattr(x, "ndim", None) == 0:
        return x.item()
    return x


def _encode(x) -> str | None:
    """repr of the canonicalized value when it literal_evals back to an
    equal value, else None."""
    s = repr(_canon(x))
    try:
        return s if ast.literal_eval(s) == x else None
    except (ValueError, SyntaxError, MemoryError, RecursionError):
        return None


class HintStore:
    """One owner's (TpuContext / Executor) handle on the hint file.

    ``load_once`` merges persisted entries under the owner's existing
    state (in-memory learning always wins). ``mark`` says the state may
    have changed and returns; the store's writer thread then goes through
    ``save_if_changed``'s body, which writes the owner's current state
    back when its fingerprint moved. ``flush`` does that now, ``close``
    flushes and joins the writer. A write failure (read-only cache dir)
    disables further writes for this store rather than warning per query.
    """

    def __init__(self) -> None:
        # load and save: the fingerprint and the file IO
        self._lock = threading.Lock()
        self._loaded = False
        self._last_fp: int | None = None
        self._write_failed = False
        # the hand-off between mark() and whoever writes; never held
        # across IO, and taken inside _lock, never around it
        self._mark_lock = threading.Lock()
        self._marked: tuple[dict, dict] | None = None
        self._writer: threading.Thread | None = None
        # set by every mark (and close()): what the idle writer waits for
        self._wake = threading.Event()
        # set by close(): ends the writer's debounce, and no mark starts
        # a writer after it
        self._closing = threading.Event()
        _STORES.add(self)

    def load_once(self, hint: dict, plan_cache: dict) -> int:
        """Merge the hint file into ``hint``/``plan_cache`` (first call
        only; later calls are free no-ops). Returns entries merged."""
        with self._lock:
            if self._loaded:
                return 0
            self._loaded = True
            path = store_path()
            if path is None:
                return 0
            try:
                with open(path, encoding="utf-8") as f:
                    doc = json.load(f)
            except FileNotFoundError:
                return 0
            except (OSError, ValueError) as e:
                log.warning("plan-hint cache unreadable (%s): %s", path, e)
                return 0
            if not isinstance(doc, dict) or doc.get("version") != _VERSION:
                return 0
            n = 0
            cap = doc.get("agg_capacity")
            if isinstance(cap, int) and cap > hint.get("agg_capacity", 0):
                hint["agg_capacity"] = cap
                n += 1
            entries = doc.get("entries")
            if isinstance(entries, dict):
                for ks, vs in entries.items():
                    try:
                        k = ast.literal_eval(ks)
                        v = ast.literal_eval(vs)
                    except (ValueError, SyntaxError, MemoryError,
                            RecursionError):
                        continue
                    if k not in plan_cache:
                        plan_cache[k] = v
                        n += 1
            if n:
                metrics.add("hints_loaded", n)
                log.info(
                    "plan-hint cache: %d entries from %s", n, path
                )
            # fingerprint AFTER the merge: a workload that learns nothing
            # new never rewrites the file
            self._last_fp = _fingerprint(hint, _own_items(plan_cache))
            return n

    def mark(self, hint: dict, plan_cache: dict) -> None:
        """The owner's state may have changed: have it persisted soon.
        O(1) and never blocked by a write — this is what a finishing task
        or collect pays for persistence."""
        metrics.add("hints.marks")
        if self._write_failed or store_path() is None:
            return
        with self._mark_lock:
            self._marked = (hint, plan_cache)
            if self._writer is None and not self._closing.is_set():
                # started under the lock so that close() never joins a
                # thread that has not started. Thread.start() waits for
                # the new thread to run, a GIL hand-off that costs a busy
                # process milliseconds: paid once, the writer then stays
                # for as long as marks keep coming
                self._writer = threading.Thread(
                    target=self._write_loop, daemon=True,
                    name=WRITER_THREAD_NAME,
                )
                self._writer.start()
        self._wake.set()

    def flush(self) -> bool:
        """Persist synchronously what is marked (shutdown, tests); waits
        out a write the writer has in flight. Returns True on a write."""
        with self._lock:
            with self._mark_lock:
                marked, self._marked = self._marked, None
            return marked is not None and self._save_locked(*marked)

    def close(self) -> None:
        """Flush, and join the writer: a clean stop persists everything
        learned and leaves no thread. A later mark (a straggling task)
        starts no writer; a clean interpreter exit still flushes it."""
        self._closing.set()
        self._wake.set()
        with self._mark_lock:
            writer = self._writer
        if writer is not None:
            writer.join(timeout=10)
            if writer.is_alive():
                log.warning("plan-hint writer outlived the join timeout")
        self.flush()

    def _write_loop(self) -> None:
        from ballista_tpu.obs import trace as obs_trace

        while True:
            marked = self._wake.wait(_WRITER_IDLE_S)
            if marked:
                # let the burst's further marks coalesce; close() ends it
                self._closing.wait(WRITE_DEBOUNCE_S)
                self._wake.clear()
                with obs_trace.phase("executor.hints_write"):
                    self.flush()
            with self._mark_lock:
                if self._closing.is_set() or (
                    not marked and self._marked is None
                ):
                    self._writer = None
                    return

    def save_if_changed(self, hint: dict, plan_cache: dict) -> bool:
        """Persist the current state when it differs from the last
        loaded/saved fingerprint. Returns True on a write. Synchronous:
        the one write path, which the writer and ``flush`` go through."""
        with self._lock:
            return self._save_locked(hint, plan_cache)

    def _save_locked(self, hint: dict, plan_cache: dict) -> bool:
        if self._write_failed:
            return False
        path = store_path()
        if path is None:
            return False
        items = _own_items(plan_cache)
        fp = _fingerprint(hint, items)
        if fp == self._last_fp:
            metrics.add("hints.writes_skipped_unchanged")
            return False
        doc = _document(hint, items)
        # merge UNDER the on-disk state rather than replacing it: the
        # owner's plan cache is cleared by table (re)registration, so
        # a wholesale write after that would destroy every other
        # query's / process's persisted learning; current in-memory
        # entries win per key, agg_capacity takes the max
        try:
            with open(path, encoding="utf-8") as f:
                prev = json.load(f)
        except (OSError, ValueError):
            prev = None
        if (
            isinstance(prev, dict)
            and prev.get("version") == _VERSION
        ):
            prev_cap = prev.get("agg_capacity")
            if isinstance(prev_cap, int) and prev_cap > (
                doc["agg_capacity"] or 0
            ):
                doc["agg_capacity"] = prev_cap
            prev_entries = prev.get("entries")
            if isinstance(prev_entries, dict):
                merged = dict(prev_entries)
                merged.update(doc["entries"])
                if len(merged) > _MAX_ENTRIES:
                    # drop oldest on-disk-only entries first; the
                    # owner's own (newest) entries always survive
                    overflow = len(merged) - _MAX_ENTRIES
                    for k in list(prev_entries):
                        if overflow == 0:
                            break
                        if k not in doc["entries"]:
                            del merged[k]
                            overflow -= 1
                doc["entries"] = merged
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            fd, tmp = tempfile.mkstemp(
                dir=os.path.dirname(path), suffix=".tmp"
            )
            try:
                with os.fdopen(fd, "w", encoding="utf-8") as f:
                    json.dump(doc, f)
                os.replace(tmp, path)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except OSError as e:
            log.warning(
                "plan-hint cache not writable (%s): %s — hint "
                "persistence disabled for this process", path, e,
            )
            self._write_failed = True
            return False
        self._last_fp = fp
        metrics.add("hints_saved")
        return True


def _snapshot_items(d: dict) -> list:
    """Stable snapshot of a dict OTHER task threads mutate concurrently:
    ``list(d.items())`` itself raises RuntimeError when the dict resizes
    mid-construction (observed live — two task-runner threads on one
    executor, one fingerprinting its save while the other committed its
    attempt cache; the bounded task retry masked it as a spurious task
    failure). Retrying is cheap and converges: resizes are rare single
    events, not a steady state. The empty-list give-up (never observed)
    at worst skips/doubles one debounced hint write — both correct."""
    for _ in range(8):
        try:
            return list(d.items())
        except RuntimeError:
            continue
    return []


def _job_scoped(key) -> bool:
    """Does ``key`` carry a non-empty job id — as ``key[1]`` of a family
    ``exec/`` scopes to the learning job, or through a strategy key nested
    in it (``("join_lut", fp)``, ``("expand_cap", fp, ...)``)? No later job
    and no later process can read such an entry."""
    if not isinstance(key, tuple) or len(key) < 2:
        return False
    if key[0] in _JOB_SCOPED_FAMILIES:
        return bool(key[1])
    return any(_job_scoped(part) for part in key[1:])


def _own_items(plan_cache: dict) -> list:
    """The (key, value) pairs a later process could read: a snapshot less
    the ephemeral tallies and the job-scoped entries, newest-biased to
    _MAX_ENTRIES. What fingerprint and document are both made from, so
    that an entry nobody will read neither moves the one nor enters the
    other."""
    items = [
        kv for kv in _snapshot_items(plan_cache)
        if kv[0] not in _EPHEMERAL_KEYS
    ]
    own = [kv for kv in items if not _job_scoped(kv[0])]
    if len(own) < len(items):
        metrics.add("hints.entries_job_scoped_skipped", len(items) - len(own))
    return own[-_MAX_ENTRIES:]


def _document(hint: dict, items: list) -> dict:
    """The file's content: every pair of ``items`` that survives the
    literal_eval round trip (``agg_capacity`` is a top-level field)."""
    cap = hint.get("agg_capacity")
    entries = {}
    for k, v in items:
        ks, vs = _encode(k), _encode(v)
        if ks is not None and vs is not None:
            entries[ks] = vs
    return {
        "version": _VERSION,
        "agg_capacity": cap if isinstance(cap, int) else None,
        "entries": entries,
    }


def _fingerprint(hint: dict, items: list) -> int:
    """Change-detection only — repr without the literal_eval validation
    _document does: this runs every time the writer wakes, and parsing
    thousands of entries to decide "nothing changed" would dwarf the
    write it debounces. Entries repr-unstable enough to fool this just
    cause one redundant (still-correct) merge-write. ``items`` is
    ``_own_items``' snapshot: the owner's task threads mutate the dict
    meanwhile, and repr() between loop steps can yield the GIL."""
    reprs = sorted((repr(_canon(k)), repr(_canon(v))) for k, v in items)
    return hash((hint.get("agg_capacity"), tuple(reprs)))


def _flush_marked() -> None:
    """At a clean interpreter exit: what a store without a ``close``
    (the local ``TpuContext``, a one-shot CLI) still has pending."""
    for store in list(_STORES):
        store.flush()


atexit.register(_flush_marked)
