#!/usr/bin/env python3
"""``python -m ballista_tpu.executor``, unchanged, with a profiler window that
the harness opens with SIGUSR1 and closes with SIGUSR2.

Only the process that owns the chip can trace it, and in the ``daemons``
deployment that is the executor. The signals only set events; a thread of
this file starts and stops ``jax.profiler`` and leaves ``started`` and
``stopped`` in ``--trace-dir`` for the harness to see.
"""

from __future__ import annotations

import os
import pathlib
import runpy
import signal
import sys
import threading

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "perf")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> None:
    if sys.argv[1] != "--trace-dir":
        raise SystemExit("usage: executor_traced.py --trace-dir DIR "
                         "<executor arguments>")
    trace_dir = sys.argv[2]
    del sys.argv[1:3]
    os.makedirs(trace_dir, exist_ok=True)
    start, stop = threading.Event(), threading.Event()
    signal.signal(signal.SIGUSR1, lambda *_: start.set())
    signal.signal(signal.SIGUSR2, lambda *_: stop.set())

    def profiler_window() -> None:
        import jax

        from deployments import profiler_options

        start.wait()
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=profiler_options())
        pathlib.Path(trace_dir, "started").touch()
        stop.wait()
        jax.profiler.stop_trace()
        pathlib.Path(trace_dir, "stopped").touch()

    threading.Thread(target=profiler_window, daemon=True).start()
    sys.argv[0] = "ballista_tpu.executor"
    runpy.run_module("ballista_tpu.executor", run_name="__main__")


if __name__ == "__main__":
    main()
