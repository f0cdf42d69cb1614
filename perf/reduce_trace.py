"""From the profiler's ``.xplane.pb`` to the device numbers of a traced
window: busy seconds, the operations and programs that took most time, and
the idle gaps named by what the host was doing in them.

What a v5e trace holds (looked at by hand, PR 24): a plane ``/device:TPU:<n>``
per chip with the lines ``XLA Modules`` (one event per program run, named
``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event per HLO operation,
named by its HLO text; events nest inside loops, so busy time is the union
of their intervals and not their sum); a plane ``/host:CPU`` with one line per
thread, where ``python`` lines hold ``PjitFunction(<fn>)`` dispatches and
``np.asarray(jax.Array)`` device-to-host reads and ``pjrt-tpu-tasks`` lines the
runtime's (de)linearisation of transfers. All lines share one clock that starts
near the profiler's start. Reads the file with nothing but JAX's own reader.
"""

from __future__ import annotations

import re
from collections import Counter

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# threads whose events say what the host was doing for the device
HOST_LINES = re.compile(r"^(python|pjrt-tpu-tasks|tfrt-|EventFDAsyncWorker)")
NAME_CHARS = 100
TOP = 10


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    """The complement of merged ``busy`` inside [lo, hi]."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


# idle gaps shorter than this are the spaces between operations of one
# program, not the host's doing: lumped, not looked up
SHORT_GAP_NS = 20_000
BETWEEN_OPS = "between operations of a program (gaps under 20 us)"
UNTRACED = "no traced host event (Python, RPC, polling)"


def label_gaps(gap_list, host_events) -> Counter:
    """Idle nanoseconds by what the host was doing: each gap goes to the
    host event that covers most of it, if one covers a fifth of it; otherwise
    nothing traced ran, which on this host is the program's own Python
    (planning, RPC, polling sleeps). One sweep over both lists in time order."""
    idle: Counter = Counter()
    events = sorted(host_events)
    active: list = []
    at = 0
    for g0, g1 in sorted(gap_list):
        if g1 - g0 < SHORT_GAP_NS:
            idle[BETWEEN_OPS] += g1 - g0
            continue
        while at < len(events) and events[at][0] < g1:
            active.append(events[at])
            at += 1
        active = [ev for ev in active if ev[1] > g0]
        cover: Counter = Counter()
        for s, e, name in active:
            cover[name] += min(e, g1) - max(s, g0)
        label = UNTRACED
        if cover:
            name, ns = cover.most_common(1)[0]
            if ns >= (g1 - g0) / 5:
                label = name
        idle[label] += g1 - g0
    return idle


def short(name: str) -> str:
    return name[:NAME_CHARS]


def reduce_planes(planes, window_s: float) -> dict:
    """``planes``, as ``read_planes`` gives them: [(plane name, [(line name,
    [(start_ns, duration_ns, event name), ...]), ...]), ...], the host plane
    cut to the threads that work for the device."""
    devices = []
    host_events = []
    for pname, lines in planes:
        if DEVICE_PLANE.match(pname):
            dev = {"ops": [], "modules": []}
            for lname, events in lines:
                if lname == "XLA Ops":
                    dev["ops"] = events
                elif lname == "XLA Modules":
                    dev["modules"] = events
            devices.append(dev)
        elif pname == "/host:CPU":
            for _, events in lines:
                host_events.extend(
                    (s, s + d, short(n)) for s, d, n in events if d > 0
                )
    devices = [d for d in devices if d["ops"]]
    if not devices:
        return {"window_s": window_s, "busy_s": None, "breakdown": None,
                "sort_s": None, "devices": 0}
    busy_s, sort_s = [], []
    ops: Counter = Counter()
    programs: Counter = Counter()
    idle: Counter = Counter()
    for dev in devices:
        merged = union((s, s + d) for s, d, _ in dev["ops"])
        busy_s.append(sum(e - s for s, e in merged) / 1e9)
        sort_s.append(sum(d for _, d, n in dev["ops"]
                          if re.match(r"%sort[.\d]* = ", n)) / 1e9)
        for _, d, n in dev["ops"]:
            ops[short(n)] += d
        for _, d, n in dev["modules"]:
            programs["program " + re.sub(r"\(\d+\)$", "", n)] += d
        idle.update(label_gaps(gaps(merged, merged[0][0], merged[-1][1]),
                               host_events))
    n = len(devices)
    half = TOP // 2
    device_ops = programs.most_common(half) + ops.most_common(TOP - half)
    return {
        "window_s": window_s,
        "busy_s": sum(busy_s) / n,
        "sort_s": sum(sort_s) / n,
        "devices": n,
        "breakdown": {
            "device_ops": [[k, v / 1e9 / n] for k, v in device_ops],
            "idle_gaps": [[k, v / 1e9 / n] for k, v in idle.most_common(TOP)],
        },
    }


def read_planes(path: str):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [
        (plane.name, [
            (line.name, [(int(e.start_ns), int(e.duration_ns), e.name)
                         for e in line.events])
            for line in plane.lines if _wanted(plane.name, line.name)
        ])
        for plane in data.planes
        if DEVICE_PLANE.match(plane.name) or plane.name == "/host:CPU"
    ]


def _wanted(plane_name: str, line_name: str) -> bool:
    if plane_name == "/host:CPU":
        return bool(HOST_LINES.match(line_name))
    return line_name in ("XLA Ops", "XLA Modules")


def reduce(path: str, t0: float, t1: float, queries) -> dict:
    """The traced window ran from ``t0`` to ``t1`` on the host's clock;
    ``queries`` are the window's records, of which those that ended inside
    it are what the device's time is set against."""
    out = reduce_planes(read_planes(path), t1 - t0)
    out["queries"] = [r for r in queries
                      if r["error"] is None and t0 <= r["t1"] <= t1]
    return out
