"""The two ways a configuration deploys the system under test.

``standalone``: one process; ``BallistaContext.standalone()`` over tables
registered in memory. This process owns the chip and traces it itself.

``daemons``: the README quick start: a scheduler process, an executor
process, and a remote client here over one parquet file per table. Only the
executor owns the chip; this process never initialises a JAX backend, and
every child is reaped on every exit path.

Both give the harness the same few things: a client, the device as JAX
reports it, compile counters, the scheduler's history, a profiler window and
the device's peak memory. Both open the client's session under the
configuration's ``session_settings``.
"""

from __future__ import annotations

import glob
import json
import os
import pathlib
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = pathlib.Path(__file__).resolve().parent.parent


def fresh_hints(tmp: str) -> None:
    """The program persists what it learns about plans (join strategies,
    capacities, AQE's per-class strategies) in ``plan_hints.json`` beside the
    compile cache, and reads it back in the next process: a run's speed then
    hangs on which runs shared that directory before it (PR 24 read 5.3, 4.2
    and 2.9 queries/s for one cell as the file aged). Every run starts the
    file anew, in its own temporary directory, through the program's own
    ``BALLISTA_TPU_HINT_CACHE``: the first session of a fresh deployment.
    Children inherit it."""
    hints = os.path.join(tmp, "hints")
    os.makedirs(hints, exist_ok=True)
    os.environ["BALLISTA_TPU_HINT_CACHE"] = hints


def session_config(cfg: dict):
    """The configuration's ``session_settings`` (the program's keys, as
    ``docs/config.md`` lists them) as the ``BallistaConfig`` the
    client's session is opened with; ``None``, the program's defaults, for
    ``{}``. A key the program does not know, or a value it cannot parse,
    ends the run here, before any work."""
    settings = cfg.get("session_settings")
    if not settings:
        return None
    from ballista_tpu.config import BallistaConfig, ConfigError

    try:
        return BallistaConfig({
            k: str(v).lower() if isinstance(v, bool) else str(v)
            for k, v in settings.items()
        })
    except ConfigError as e:
        raise SystemExit(f"configuration: session_settings: {e}")


class NoChip(Exception):
    """JAX found no TPU, or another number of chips than the cell asks for."""


def check_device(device: dict, chips: int, rehearse: bool) -> None:
    if rehearse:
        return
    if device["platform"] != "tpu":
        raise NoChip(f"JAX found platform {device['platform']!r}, not a TPU")
    if device["count"] != chips:
        raise NoChip(f"JAX sees {device['count']} chips, the cell asks {chips}")


def newest_xplane(trace_dir: str) -> str | None:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def profiler_options():
    """Device and host tracing, no Python call stacks: a window of tens of
    seconds has to stay readable inside the run's time limit."""
    from jax.profiler import ProfileOptions

    po = ProfileOptions()
    po.python_tracer_level = 0
    po.host_tracer_level = 2
    return po


class Standalone:
    def __init__(self, cfg: dict, chips: int, rehearse: bool, trace: bool):
        self.cfg, self.chips, self.rehearse = cfg, chips, rehearse
        self.session = session_config(cfg)
        self.ctx = None
        self.device: dict = {}
        self.trace_dir = None
        self._tmp = tempfile.mkdtemp(prefix="perf-standalone-")
        fresh_hints(self._tmp)

    def start(self) -> None:
        import jax

        devs = jax.devices()
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        check_device(self.device, self.chips, self.rehearse)

    def load(self, tables: dict) -> None:
        from ballista_tpu.client.context import BallistaContext

        self.ctx = BallistaContext.standalone(
            config=self.session,
            concurrent_tasks=self.cfg["concurrent_tasks"],
        )
        for name, t in tables.items():
            self.ctx.register_table(name, t)

    def counters(self) -> dict:
        from ballista_tpu.compilecache import metrics

        return metrics.snapshot()

    def history(self, table: str) -> list[dict]:
        return self.ctx._system_table_rows(table)

    def trace_start(self) -> None:
        import jax

        self.trace_dir = os.path.join(self._tmp, "trace")
        jax.profiler.start_trace(self.trace_dir,
                                 profiler_options=profiler_options())

    def trace_stop(self) -> str | None:
        import jax

        jax.profiler.stop_trace()
        return newest_xplane(self.trace_dir)

    def stop(self) -> None:
        if self.ctx is not None:
            self.ctx.close()
            self.ctx = None

    def peak_bytes(self) -> int | None:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()]
        return max((p for p in peaks if p is not None), default=None)

    def cleanup(self) -> None:
        self.stop()
        shutil.rmtree(self._tmp, ignore_errors=True)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Daemons:
    def __init__(self, cfg: dict, chips: int, rehearse: bool, trace: bool):
        self.cfg, self.chips, self.rehearse = cfg, chips, rehearse
        self.trace = trace
        self.session = session_config(cfg)
        self.ctx = None
        self.device: dict = {}
        self.procs: list[subprocess.Popen] = []
        self._tmp = tempfile.mkdtemp(prefix="perf-daemons-")
        fresh_hints(self._tmp)
        self.trace_dir = os.path.join(self._tmp, "trace")
        self._logs: dict[str, str] = {}
        self._executor = None

    # -- children -------------------------------------------------------
    def _spawn(self, name: str, argv: list[str], env: dict):
        log = os.path.join(self._tmp, f"{name}.log")
        self._logs[name] = log
        with open(log, "w") as fh:
            # its own session, so the whole group dies with it
            p = subprocess.Popen(
                argv, env=env, stdout=fh, stderr=subprocess.STDOUT,
                start_new_session=True, cwd=str(ROOT),
            )
        self.procs.append(p)
        return p

    def _tail(self, name: str) -> str:
        try:
            return pathlib.Path(self._logs[name]).read_text()[-3000:]
        except OSError:
            return ""

    def _wait(self, what: str, probe, timeout: float) -> None:
        deadline = time.time() + timeout
        while True:
            for name, p in zip(self._logs, self.procs):
                if p.poll() is not None:
                    raise RuntimeError(
                        f"{name} exited {p.returncode} while waiting for "
                        f"{what}:\n{self._tail(name)}"
                    )
            if probe():
                return
            if time.time() > deadline:
                raise RuntimeError(f"timed out waiting for {what}")
            time.sleep(0.2)

    def _state(self) -> dict:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{self.rest_port}/api/state", timeout=5
        ) as r:
            return json.load(r)

    def start(self) -> None:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [str(ROOT)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH")
                           else [])
        )
        # scheduler and client never touch a backend (PR 21); should one
        # ever try, it must not be the chip it takes from the executor
        off_chip = dict(env, JAX_PLATFORMS="cpu")
        os.environ["JAX_PLATFORMS"] = "cpu"
        self.sched_port, self.rest_port = _free_port(), _free_port()
        self._spawn("scheduler", [
            sys.executable, "-m", "ballista_tpu.scheduler",
            "--bind-host", "127.0.0.1", "--bind-port", str(self.sched_port),
            "--rest-port", str(self.rest_port),
        ], off_chip)

        def rest_up() -> bool:
            try:
                self._state()
                return True
            except OSError:
                return False

        self._wait("the scheduler's REST port", rest_up, 60)
        work = os.path.join(self._tmp, "work")
        os.makedirs(work)
        entry = ["-m", "ballista_tpu.executor"]
        if self.trace:
            entry = [str(ROOT / "perf" / "executor_traced.py"),
                     "--trace-dir", self.trace_dir]
        self._executor = self._spawn("executor", [
            sys.executable, *entry,
            "--bind-host", "127.0.0.1", "--external-host", "127.0.0.1",
            "--bind-port", str(_free_port()),
            "--bind-grpc-port", str(_free_port()),
            "--scheduler-host", "127.0.0.1",
            "--scheduler-port", str(self.sched_port),
            "--work-dir", work,
            "--concurrent-tasks", str(self.cfg["concurrent_tasks"]),
            "--task-scheduling-policy", self.cfg["task_scheduling_policy"],
        ], env)

    def load(self, tables: dict) -> None:
        import pyarrow.parquet as papq

        data = os.path.join(self._tmp, "data")
        os.makedirs(data)
        for name, t in tables.items():
            papq.write_table(t, os.path.join(data, f"{name}.parquet"))
        self._wait("the executor to register",
                   lambda: len(self._state()["executors"]) == 1, 180)
        m = re.search(r" devices: platform=(\S+) count=(\d+) kind=(.*)",
                      pathlib.Path(self._logs["executor"]).read_text())
        if m is None:
            raise RuntimeError("the executor's log names no device:\n"
                               + self._tail("executor"))
        self.device = {"platform": m[1], "kind": m[3].strip(),
                       "count": int(m[2])}
        check_device(self.device, self.chips, self.rehearse)
        from ballista_tpu.client.context import BallistaContext

        self.ctx = BallistaContext.remote("127.0.0.1", self.sched_port,
                                          self.session)
        for name in tables:
            self.ctx.register_parquet(
                name, os.path.join(data, f"{name}.parquet")
            )

    def counters(self) -> dict:
        time.sleep(0.5)  # the counters ride the executor's 0.1 s poll
        compile_ = self._state()["executors"][0].get("compile") or {}
        return {k: float(v) for k, v in compile_.items()}

    def history(self, table: str) -> list[dict]:
        return self.ctx._system_table_rows(table)

    # -- the profiler window, opened and closed in the executor ----------
    def _signal_and_wait(self, sig, marker: str) -> None:
        path = os.path.join(self.trace_dir, marker)
        os.kill(self._executor.pid, sig)
        self._wait(f"the executor's profiler ({marker})",
                   lambda: os.path.exists(path), 120)

    def trace_start(self) -> None:
        self._signal_and_wait(signal.SIGUSR1, "started")

    def trace_stop(self) -> str | None:
        self._signal_and_wait(signal.SIGUSR2, "stopped")
        return newest_xplane(self.trace_dir)

    def stop(self) -> None:
        if self.ctx is not None:
            self.ctx.close()
            self.ctx = None
        for p in reversed(self.procs):  # SIGTERM: the executor logs its peak
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGTERM)
                except ProcessLookupError:
                    pass
                try:
                    p.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    pass
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            p.wait()

    def peak_bytes(self) -> int | None:
        try:
            log = pathlib.Path(self._logs["executor"]).read_text()
        except (KeyError, OSError):
            return None
        m = re.search(r" device peak_bytes_in_use=(\d+)", log)
        return int(m[1]) if m else None

    def cleanup(self) -> None:
        self.stop()
        shutil.rmtree(self._tmp, ignore_errors=True)


KINDS = {"standalone": Standalone, "daemons": Daemons}
