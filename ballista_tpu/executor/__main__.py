"""Executor process entrypoint: ``python -m ballista_tpu.executor``.

ref ballista/rust/executor/src/main.rs:64-296 — parse the flag/env config
tier, start the Flight (data-plane) server, connect to the scheduler in
pull- or push-staged mode, and run the shuffle-data TTL cleanup loop until
interrupted.

Flags mirror the reference's executor config spec (executor_config_spec.toml);
every flag also reads a ``BALLISTA_EXECUTOR_<NAME>`` environment default, the
reference's configure_me behavior.
"""

from __future__ import annotations

import argparse
import logging
import os
import signal
import tempfile
import threading

from ballista_tpu.config import TaskSchedulingPolicy
from ballista_tpu.executor.cleanup import start_cleanup_loop
from ballista_tpu.executor.executor import Executor, PollLoop, new_executor_id
from ballista_tpu.executor.flight_service import start_flight_server

log = logging.getLogger("ballista_tpu.executor")


def _env(name: str, default):
    return os.environ.get(f"BALLISTA_EXECUTOR_{name.upper()}", default)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m ballista_tpu.executor",
        description="ballista-tpu executor process",
    )
    p.add_argument("--bind-host", default=_env("bind_host", "0.0.0.0"))
    p.add_argument(
        "--external-host",
        default=_env("external_host", "localhost"),
        help="host advertised to the scheduler/clients for Flight fetches",
    )
    p.add_argument(
        "--bind-port", type=int, default=int(_env("bind_port", 50051)),
        help="Flight data-plane port",
    )
    p.add_argument(
        "--bind-grpc-port", type=int, default=int(_env("bind_grpc_port", 50053)),
        help="push-mode control port (LaunchTask); 50052 is the "
        "scheduler's conventional REST port, so default past it",
    )
    p.add_argument("--scheduler-host", default=_env("scheduler_host", "localhost"))
    p.add_argument(
        "--scheduler-port", type=int, default=int(_env("scheduler_port", 50050))
    )
    p.add_argument(
        "--work-dir", default=_env("work_dir", ""),
        help="shuffle spill directory (default: a fresh temp dir)",
    )
    p.add_argument(
        "--concurrent-tasks", type=int, default=int(_env("concurrent_tasks", 4))
    )
    p.add_argument(
        "--task-scheduling-policy",
        default=_env("task_scheduling_policy", "pull-staged"),
        choices=["pull-staged", "push-staged"],
    )
    p.add_argument(
        "--job-data-ttl-seconds",
        type=float,
        default=float(_env("job_data_ttl_seconds", 604800)),
    )
    p.add_argument(
        "--job-data-clean-up-interval-seconds",
        type=float,
        default=float(_env("job_data_clean_up_interval_seconds", 0)),
        help="0 disables the cleanup loop (ref main.rs:188-203)",
    )
    p.add_argument(
        "--prewarm",
        default=_env("prewarm", os.environ.get("BALLISTA_TPU_PREWARM", "off")),
        choices=["off", "on", "background"],
        help="AOT-compile the kernel vocabulary at start "
        "(docs/compile_cache.md): 'on' blocks until warm, 'background' "
        "compiles while serving",
    )
    p.add_argument(
        "--metrics-port",
        type=int,
        default=int(_env("metrics_port", 0)),
        help="serve Prometheus text metrics (GET /api/metrics) on this "
        "port — the executor-side scrape surface (docs/observability.md); "
        "0 disables",
    )
    p.add_argument("--log-level", default=_env("log_level", "INFO"))
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    logging.basicConfig(
        level=getattr(logging, args.log_level.upper(), logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
    )
    from ballista_tpu.config import warn_unknown_env

    warn_unknown_env()  # typo'd BALLISTA_* knobs must be loud (config.md)
    # re-log the import-time cache decision now that a handler exists
    import ballista_tpu

    log.info(
        "jax persistent compilation cache: %s",
        ballista_tpu.jax_cache_dir or "disabled",
    )
    # the executor is the process that owns the chip: say which one, before
    # anything is served (a missing or busy device fails here, at start)
    import jax

    devices = jax.devices()
    log.info(
        "devices: platform=%s count=%d kind=%s",
        devices[0].platform, len(devices), devices[0].device_kind,
    )
    work_dir = args.work_dir or tempfile.mkdtemp(prefix="ballista-executor-")
    os.makedirs(work_dir, exist_ok=True)
    policy = TaskSchedulingPolicy.parse(args.task_scheduling_policy)
    executor_id = new_executor_id()
    executor = Executor(executor_id=executor_id, work_dir=work_dir)

    _svc, flight_port, _t = start_flight_server(
        args.bind_host, args.bind_port, work_dir
    )
    log.info(
        "executor %s: Flight on %s:%d, work_dir=%s, policy=%s",
        executor_id, args.bind_host, flight_port, work_dir, policy.value,
    )

    scheduler_addr = f"{args.scheduler_host}:{args.scheduler_port}"
    if policy == TaskSchedulingPolicy.PUSH_STAGED:
        from ballista_tpu.executor.executor_server import ExecutorServer

        server = ExecutorServer(
            executor,
            scheduler_addr,
            args.external_host,
            flight_port,
            task_slots=args.concurrent_tasks,
            prewarm=args.prewarm,
        )
        grpc_port = server.startup(args.bind_host, args.bind_grpc_port)
        log.info("push-mode ExecutorGrpc on %s:%d", args.bind_host, grpc_port)
        worker = server
    else:
        loop = PollLoop(
            executor,
            scheduler_addr,
            args.external_host,
            flight_port,
            task_slots=args.concurrent_tasks,
            prewarm=args.prewarm,
        )
        loop.start()
        worker = loop

    if args.job_data_clean_up_interval_seconds > 0:
        start_cleanup_loop(
            work_dir,
            args.job_data_ttl_seconds,
            args.job_data_clean_up_interval_seconds,
        )

    metrics_httpd = None
    if args.metrics_port:
        from ballista_tpu.obs import prometheus as prom

        metrics_httpd, mport = prom.start_metrics_server(
            prom.executor_families, args.bind_host, args.metrics_port
        )
        log.info("metrics on %s:%d/api/metrics", args.bind_host, mport)

    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    stop.wait()
    log.info("shutting down")
    if metrics_httpd is not None:
        from ballista_tpu.obs.prometheus import stop_metrics_server

        stop_metrics_server(metrics_httpd)
    worker.stop()
    log.info(
        "device peak_bytes_in_use=%s",
        (devices[0].memory_stats() or {}).get("peak_bytes_in_use"),
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
