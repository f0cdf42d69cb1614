"""Distributed tracing: spans over the query's whole distributed life.

A ``trace_id`` is minted at job submission (scheduler-side, only when the
session's ``ballista.tpu.trace`` is not ``off``) and propagated exactly
like ``ballista.internal.task_attempt``: through task props to executors,
and through Flight ticket settings to the serving data plane. Every
participant records **finished spans** — (trace_id, span_id, parent_id,
name, start/end unix seconds, status, attrs) — into a bounded in-process
ring; executor processes additionally stage them in an outbox that the
poll/heartbeat/status RPCs drain home, where the scheduler reassembles
the per-job span tree (submit -> stage -> task attempt -> fetch/spill).

Overhead discipline (the acceptance bar: tracing off costs NOTHING):
span creation happens only under an active trace context — ambient
(thread-local, established by an enclosing span) or explicit (a task
prop). With ``ballista.tpu.trace=off`` no trace_id is ever minted, so
:func:`span` takes the first-line early-out and allocates nothing.

JSONL export: :func:`configure` with a path makes every recorded span
append one JSON line there (``ballista.tpu.trace=<path>``); ``on`` keeps
spans in the ring only. The ring is the debugging surface
(:func:`snapshot`); chaos tests assert span-tree SHAPE from the
scheduler-side store (docs/observability.md).

Host phases (:func:`phase`, PR 25) are the second, always-on output of
this module: a closed list of leaf stretches of the served path (a
blocking device read, the executor's idle sleep, a shuffle file write...)
that each feed three things from ONE call site: a
``jax.profiler.TraceAnnotation`` on the profiler's own clock, the
process-wide counters of ``compilecache/metrics.py`` that ride the
executor's poll, and, under ``ballista.tpu.trace``, the enclosing
``task_attempt`` span's attrs. No span is minted per phase.

Operator stretches (:class:`stretch`) carry the same mechanism down into
the operators: a thread keeps a stack of the operators it is running, and
its time belongs to exactly one owner at every moment, the innermost
operator or an open phase. Each stretch is a ``ballista/op.<Operator>``
annotation and the operator's ``self_s`` timer; at most one ``ballista/``
annotation is open on a thread at any time.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import os
import threading
import time
import uuid

# no backend is touched: the package's __init__ has imported jax already,
# in the scheduler and the remote client too
from jax.profiler import TraceAnnotation

from ballista_tpu.analysis.witness import make_lock
from ballista_tpu.compilecache import metrics

# Bounded stores: tracing must never become a memory leak on a long-lived
# daemon. The ring is a debugging window, not a database; the outbox holds
# spans between poll ticks (~100ms pull / per-status push), so thousands
# of slots is already generous.
_RING_CAP = 8192
_OUTBOX_CAP = 4096

_LOCK = make_lock("obs.trace._LOCK")
_RING: collections.deque = collections.deque(maxlen=_RING_CAP)
_OUTBOX: collections.deque = collections.deque(maxlen=_OUTBOX_CAP)
_MODE: str = "off"  # JSONL export: "off" | "on" | <path>
_SHIP: bool = False  # executor processes stage spans for RPC shipping
# No-silent-caps (docs/analysis.md): both bounded stores count what they
# evict, surfaced as ballista_spans_dropped_total{buffer=...}. The two
# buffers mean different things: buffer="outbox" is REAL loss (a span
# evicted before it shipped) and must stay 0 on a healthy deployment;
# buffer="ring" is the debugging window rotating — expected once a
# traced process records more than _RING_CAP spans, alert-worthy only
# if you expected the window to hold everything. The SLO harness runs
# untraced, so it asserts the combined total is 0.
_DROPPED: dict[str, int] = {"ring": 0, "outbox": 0}

_TLS = threading.local()


@dataclasses.dataclass
class Span:
    """One finished span (the unit that crosses the wire as SpanP)."""

    trace_id: str
    span_id: str
    parent_id: str
    name: str
    start_s: float
    end_s: float = 0.0
    outcome: str = "ok"  # "ok" | "error" (wire field name: status)
    attrs: dict = dataclasses.field(default_factory=dict)

    def to_json(self) -> str:
        return json.dumps(
            {
                "trace_id": self.trace_id,
                "span_id": self.span_id,
                "parent_id": self.parent_id,
                "name": self.name,
                "start_s": round(self.start_s, 6),
                "end_s": round(self.end_s, 6),
                "status": self.outcome,
                "attrs": {k: str(v) for k, v in self.attrs.items()},
            },
            sort_keys=True,
        )


def new_trace_id() -> str:
    return uuid.uuid4().hex


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


def configure(mode: str) -> None:
    """Set the JSONL export mode (``ballista.tpu.trace``): ``off``/``on``
    keep spans in the ring only; anything else is an append path."""
    global _MODE
    with _LOCK:
        _MODE = mode or "off"


def enable_shipping(flag: bool = True) -> None:
    """Executor processes stage every recorded span in the outbox so the
    task loops can ship them home on poll/heartbeat/status RPCs."""
    global _SHIP
    with _LOCK:
        _SHIP = flag


def record(span: Span) -> None:
    with _LOCK:
        if len(_RING) == _RING_CAP:
            _DROPPED["ring"] += 1
        _RING.append(span)
        if _SHIP:
            if len(_OUTBOX) == _OUTBOX_CAP:
                _DROPPED["outbox"] += 1
            _OUTBOX.append(span)
        mode = _MODE
    if mode not in ("off", "on"):
        # OUTSIDE the lock (file IO under a lock is the racelint
        # blocking-under-lock shape). One whole line per open-append-close:
        # O_APPEND writes of a short buffered line land as a single write,
        # so concurrent recorders cannot interleave half-lines.
        line = span.to_json() + "\n"
        try:
            with open(mode, "a") as f:
                f.write(line)
        except OSError:
            # an unwritable export path must never fail the query; the
            # ring still holds the span
            pass


def snapshot() -> list[Span]:
    """Ring contents, oldest first (debugging / tests)."""
    with _LOCK:
        return list(_RING)


def ring_size() -> int:
    """O(1) ring depth (the metrics-plane gauge — scrapes must not copy
    8k spans per poll just to count them)."""
    with _LOCK:
        return len(_RING)


def dropped() -> dict[str, int]:
    """Spans evicted from the bounded stores, by buffer (the
    ``ballista_spans_dropped_total`` series)."""
    with _LOCK:
        return dict(_DROPPED)


def clear() -> None:
    """Drop ring + outbox + drop counters (test isolation)."""
    with _LOCK:
        _RING.clear()
        _OUTBOX.clear()
        _DROPPED["ring"] = 0
        _DROPPED["outbox"] = 0


def drain_outbox() -> list[Span]:
    """Take every staged span (the RPC shipping path). A failed RPC should
    :func:`requeue_outbox` what it drained — spans are shipped exactly
    once, like task statuses."""
    with _LOCK:
        out = list(_OUTBOX)
        _OUTBOX.clear()
    return out


def requeue_outbox(spans: list[Span]) -> None:
    with _LOCK:
        # re-queue at the FRONT so ordering survives a poll failure; a
        # full outbox evicts from the BACK (the newest staged spans) —
        # counted, like every bounded-store eviction here
        overflow = len(_OUTBOX) + len(spans) - _OUTBOX_CAP
        if overflow > 0:
            _DROPPED["outbox"] += overflow
        _OUTBOX.extendleft(reversed(spans))


# ---------------------------------------------------------------------------
# ambient context + recording helpers
# ---------------------------------------------------------------------------


def current() -> tuple[str, str] | None:
    """The active ``(trace_id, span_id)`` on this thread, or None."""
    stack = getattr(_TLS, "stack", None)
    if not stack:
        return None
    return stack[-1].trace_id, stack[-1].span_id


def _push(live: Span) -> None:
    """``live`` becomes this thread's ambient span (a stack of the open
    Spans themselves: :func:`phase` adds its seconds to the nearest
    ``task_attempt``)."""
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    stack.append(live)


def _pop() -> None:
    _TLS.stack.pop()


@contextlib.contextmanager
def span(
    name: str,
    trace_id: str | None = None,
    parent_id: str | None = None,
    attrs: dict | None = None,
):
    """Record a span around a block. With no explicit ``trace_id`` and no
    ambient context this is a NO-OP (the tracing-off fast path: one
    attribute read, no allocation). The span becomes the ambient context
    for the block, so nested spans parent correctly; an escaping
    exception marks ``status="error"`` (type name in attrs) and
    re-raises. Yields the live Span (or None when inactive) so callers
    can add attrs discovered mid-block."""
    if trace_id is None:
        ctx = current()
        if ctx is None:
            yield None
            return
        trace_id, parent = ctx
        if parent_id is None:
            parent_id = parent
    s = Span(
        trace_id=trace_id,
        span_id=new_span_id(),
        parent_id=parent_id or "",
        name=name,
        start_s=time.time(),
        attrs=dict(attrs or {}),
    )
    _push(s)
    try:
        yield s
    except BaseException as e:
        s.outcome = "error"
        s.attrs.setdefault("error", type(e).__name__)
        raise
    finally:
        _pop()
        s.end_s = time.time()
        record(s)


def event(
    name: str,
    trace_id: str | None = None,
    parent_id: str | None = None,
    attrs: dict | None = None,
) -> Span | None:
    """A zero-duration span (point event). Same activation rule as
    :func:`span`: without an explicit or ambient trace this is a no-op."""
    if trace_id is None:
        ctx = current()
        if ctx is None:
            return None
        trace_id, parent = ctx
        if parent_id is None:
            parent_id = parent
    now = time.time()
    s = Span(
        trace_id=trace_id,
        span_id=new_span_id(),
        parent_id=parent_id or "",
        name=name,
        start_s=now,
        end_s=now,
        attrs=dict(attrs or {}),
    )
    record(s)
    return s


def start(
    name: str, trace_id: str, parent_id: str = "", attrs: dict | None = None
) -> Span:
    """Open a span explicitly (non-lexical lifetimes: the scheduler's
    stage spans open at submission and close at completion, on different
    threads). Not recorded until :func:`finish`."""
    return Span(
        trace_id=trace_id,
        span_id=new_span_id(),
        parent_id=parent_id,
        name=name,
        start_s=time.time(),
        attrs=dict(attrs or {}),
    )


def finish(s: Span, outcome: str = "ok") -> Span:
    s.end_s = time.time()
    s.outcome = outcome
    record(s)
    return s


# ---------------------------------------------------------------------------
# host phases: profiler annotations + poll-shipped counters
# ---------------------------------------------------------------------------

# The closed list of host phases (docs/observability.md has the table of
# what each brackets). Closed like obs/history.COST_KEYS: every reader
# (perf/layers, PERF.md, /api/state) uses exactly these names.
PHASES = (
    "client.submit",
    "client.fetch_results",
    "scheduler.plan",
    "scheduler.grant",
    "scheduler.status",
    "scheduler.grant_wait",
    "executor.poll_sleep",
    "executor.status_wait",
    "executor.hints_write",
    "task.decode",
    "task.scan_host",
    "task.h2d",
    "task.d2h",
    "task.shuffle_write",
    "task.shuffle_fetch",
    "task.dict_merge",
    "task.dict_predicate",
    "task.hints_save",
    "task.report",
)
# the phase whose counters are kept by call site as well (the list of
# round trips)
_SITED = "task.d2h"
_PHASE_NAMES: dict[tuple[str, str], tuple] = {}


def _phase_names(name: str, site: str) -> tuple:
    """(annotation label, attr key, counter keys by (seconds, count,
    bytes) for the phase and, where sited, for the site), built once per
    (phase, site): sites are static strings, so the table stays small."""
    names = _PHASE_NAMES.get((name, site))
    if names is None:
        if name not in PHASES:
            raise ValueError(f"{name!r} is not in obs.trace.PHASES")
        keys = [f"phase.{name}.{k}" for k in ("seconds", "count", "bytes")]
        sited = (
            [f"{k}:{site}" for k in keys]
            if site and name == _SITED else None
        )
        label = f"ballista/{name}:{site}" if site else f"ballista/{name}"
        names = (label, f"phase.{name}_s", keys, sited)
        _PHASE_NAMES[(name, site)] = names
    return names


class phase:
    """One leaf stretch of host work or waiting on the served path::

        with obs_trace.phase("task.d2h", site="shrink.count") as ph:
            host = np.asarray(dev)
            ph.nbytes = host.nbytes

    Entering and leaving does three things: a ``TraceAnnotation`` named
    ``ballista/<name>[:<site>]`` (a flag test with no profiler running,
    in this process or in one that never opens a backend), the counters
    ``phase.<name>.seconds|count|bytes`` in ``compilecache/metrics.py``
    (always on: two clock reads and one locked add), and, under an
    ambient ``task_attempt`` span, ``phase.<name>_s`` on that span's attrs.

    Phases are LEAVES: the trace reduction gives an idle gap to the host
    event that covers most of it, so an enclosing phase would swallow
    every label inside it. A phase entered inside a phase raises under
    the tests and otherwise does nothing but count ``phase.nested``.
    A phase suspends the thread's running operator stretch and resumes it
    on exit. Never hold one across a ``yield``."""

    __slots__ = ("name", "nbytes", "_names", "_t0", "_ann", "_nested")

    def __init__(self, name: str, nbytes: int = 0, site: str = ""):
        self.name = name
        self.nbytes = nbytes
        self._names = _phase_names(name, site)

    def __enter__(self) -> "phase":
        self._nested = getattr(_TLS, "in_phase", None)
        if self._nested is not None:
            if "PYTEST_CURRENT_TEST" in os.environ:
                raise AssertionError(
                    f"phase {self.name!r} entered inside phase "
                    f"{self._nested!r}: phases are leaves"
                )
            return self
        owners = getattr(_TLS, "owners", None)
        if owners:
            owners[-1]._stop()
        _TLS.in_phase = self.name
        self._ann = TraceAnnotation(self._names[0])
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        if self._nested is not None:
            metrics.add("phase.nested")
            return False
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        _TLS.in_phase = None
        owners = getattr(_TLS, "owners", None)
        if owners:
            owners[-1]._start()
        _, attr, keys, sited = self._names
        values = (dt, 1, int(self.nbytes))
        adds = [kv for kv in zip(keys, values) if kv[1]]
        if sited is not None:
            adds += [kv for kv in zip(sited, values) if kv[1]]
        metrics.add_many(adds)
        for live in reversed(getattr(_TLS, "stack", None) or ()):
            if live.name == "task_attempt":
                live.attrs[attr] = round(live.attrs.get(attr, 0.0) + dt, 6)
                break
        return False


def account(name: str, seconds: float, count: int = 1) -> None:
    """Add to ``phase.<name>.seconds|count`` a wait that no thread spent
    inside a ``with`` block: it began on one thread (a status queued, a
    stage made runnable) and ended on another (the poll that drained or
    granted it). Counters only: there is no stretch of one thread to
    annotate, and nothing for a profiler to see."""
    keys = _phase_names(name, "")[2]
    metrics.add_many(((keys[0], seconds), (keys[1], count)))


# ---------------------------------------------------------------------------
# operator stretches: one owner of a thread's time at every moment
# ---------------------------------------------------------------------------


class stretch:
    """An operator's own stretch of a thread's time::

        with obs_trace.stretch("ballista/op.FilterExec", node.metrics):
            batch = next(it)

    Entering pushes the operator on the thread's owner stack and suspends
    the operator below it; leaving pops it and resumes that one. While it
    is the top of the stack and no phase is open, the thread's time is
    its own: a ``TraceAnnotation`` named ``label`` on the profiler's clock
    and the timer ``self_s`` in ``metrics`` (self time, without its inputs
    and without any phase). A phase suspends the top stretch for its
    length (:class:`phase`), so at most one ``ballista/`` annotation is
    open on a thread at any time. Never hold one across a ``yield``: a
    stretch is entered and left within one call, so the stack is restored
    by every exit, an exception included."""

    __slots__ = ("label", "metrics", "_ann", "_t0")

    def __init__(self, label: str, metrics):
        self.label = label
        self.metrics = metrics
        self._ann = None

    def _start(self) -> None:
        self._ann = TraceAnnotation(self.label)
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def _stop(self) -> None:
        if self._ann is None:
            return
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(None, None, None)
        self._ann = None
        timers = self.metrics.timers
        timers["self_s"] = timers.get("self_s", 0.0) + dt

    def __enter__(self) -> "stretch":
        owners = getattr(_TLS, "owners", None)
        if owners is None:
            owners = _TLS.owners = []
        elif owners:
            owners[-1]._stop()
        owners.append(self)
        if getattr(_TLS, "in_phase", None) is None:
            self._start()
        return self

    def __exit__(self, *exc) -> bool:
        self._stop()
        owners = _TLS.owners
        owners.pop()
        if owners and getattr(_TLS, "in_phase", None) is None:
            owners[-1]._start()
        return False


def owners() -> list[str]:
    """The labels of this thread's owner stack, innermost last (tests)."""
    return [s.label for s in getattr(_TLS, "owners", None) or ()]


# ---------------------------------------------------------------------------
# wire conversion (SpanP)
# ---------------------------------------------------------------------------


def span_to_proto(s: Span):
    from ballista_tpu.proto import pb

    return pb.SpanP(
        trace_id=s.trace_id,
        span_id=s.span_id,
        parent_id=s.parent_id,
        name=s.name,
        start_s=s.start_s,
        end_s=s.end_s,
        status=s.outcome,
        attrs=[
            pb.KeyValuePair(key=k, value=str(v))
            for k, v in sorted(s.attrs.items())
        ],
    )


def span_from_proto(p) -> Span:
    return Span(
        trace_id=p.trace_id,
        span_id=p.span_id,
        parent_id=p.parent_id,
        name=p.name,
        start_s=p.start_s,
        end_s=p.end_s,
        outcome=p.status or "ok",
        attrs={kv.key: kv.value for kv in p.attrs},
    )
