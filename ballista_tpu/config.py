"""Validated, typed, string-keyed session configuration.

Mirrors the reference's ``BallistaConfig`` (reference:
ballista/rust/core/src/config.rs:30-281): a map of string settings with
per-key validation and typed getters, plus the task scheduling policy enum
(config.rs:264). These settings travel with every query (serialized as
key-value pairs in ExecuteQuery — ref proto ballista.proto:844-853) and are
rebuilt into the executor's task context.

TPU-specific keys added beyond the reference: target batch capacity rounding
(XLA static shapes), device placement policy, and aggregate/join table
capacities (XLA needs static output bounds).
"""

from __future__ import annotations

import dataclasses
from enum import Enum
from typing import Callable

from ballista_tpu.errors import ConfigError

# Reference key names kept verbatim where they exist (config.rs:30-40) so that
# configs written for the reference work unchanged.
BALLISTA_JOB_NAME = "ballista.job.name"
BALLISTA_DEFAULT_SHUFFLE_PARTITIONS = "ballista.shuffle.partitions"
BALLISTA_DEFAULT_BATCH_SIZE = "ballista.batch.size"
BALLISTA_REPARTITION_JOINS = "ballista.repartition.joins"
BALLISTA_REPARTITION_AGGREGATIONS = "ballista.repartition.aggregations"
BALLISTA_REPARTITION_WINDOWS = "ballista.repartition.windows"
BALLISTA_PARQUET_PRUNING = "ballista.parquet.pruning"
BALLISTA_WITH_INFORMATION_SCHEMA = "ballista.with_information_schema"
BALLISTA_PLUGIN_DIR = "ballista.plugin_dir"

# TPU-native extensions.
BALLISTA_DEVICE = "ballista.tpu.device"  # "tpu" | "cpu" | "auto"
BALLISTA_AGG_CAPACITY = "ballista.tpu.agg_capacity"  # max distinct groups per kernel
BALLISTA_TPU_BATCH_ROWS = "ballista.tpu.batch_rows"  # device-batch row budget
BALLISTA_PROFILE_DIR = "ballista.tpu.profile_dir"  # XLA profiler trace output
BALLISTA_BUILD_CACHE_MB = "ballista.tpu.build_cache_mb"  # join build-table HBM cache
BALLISTA_COLLECTIVE_SHUFFLE = "ballista.tpu.collective_shuffle"  # on-pod all_to_all
BALLISTA_SCAN_STREAM_MB = "ballista.tpu.scan_stream_mb"  # parquet streaming threshold
BALLISTA_HBM_BUDGET_MB = "ballista.tpu.hbm_budget_mb"  # grace-hash trigger
BALLISTA_SPILL_BUDGET_MB = "ballista.tpu.spill_budget_mb"  # host spill ceiling
BALLISTA_SPILL_DIR = "ballista.tpu.spill_dir"  # grace-hash spill location
BALLISTA_PREFETCH_DEPTH = "ballista.tpu.prefetch_depth"  # streamed-scan overlap
BALLISTA_VERIFY_PLANS = "ballista.tpu.verify_plans"  # static plan verification
BALLISTA_TASK_MAX_ATTEMPTS = "ballista.tpu.task_max_attempts"  # bounded task retries
BALLISTA_FETCH_RETRIES = "ballista.tpu.fetch_retries"  # Flight fetch attempts
BALLISTA_FETCH_BACKOFF_MS = "ballista.tpu.fetch_backoff_ms"  # base fetch backoff
BALLISTA_FETCH_TIMEOUT_S = "ballista.tpu.fetch_timeout_s"  # per-attempt deadline
BALLISTA_SHUFFLE_FETCH_CONCURRENCY = (
    "ballista.tpu.shuffle_fetch_concurrency"  # overlapped shuffle fetch
)
BALLISTA_SHUFFLE_COMPRESSION = (
    "ballista.tpu.shuffle_compression"  # IPC codec: none|lz4|zstd
)
BALLISTA_EAGER_SHUFFLE = "ballista.tpu.eager_shuffle"  # pre-barrier consumption
BALLISTA_PUSH_SHUFFLE = "ballista.tpu.push_shuffle"  # in-memory DoExchange fast path
BALLISTA_PUSH_SHUFFLE_WINDOW_MB = (
    "ballista.tpu.push_shuffle_window_mb"  # in-flight push window before spill
)
BALLISTA_SHUFFLE_TARGET_BATCH_MB = (
    "ballista.tpu.shuffle_target_batch_mb"  # coalesce tiny batches up to this
)
BALLISTA_EAGER_POLL_MS = "ballista.tpu.eager_poll_ms"  # location poll cadence
BALLISTA_EAGER_WAIT_S = "ballista.tpu.eager_wait_s"  # unpublished-location deadline
BALLISTA_CAPACITY_BUCKETS = (
    "ballista.tpu.capacity_buckets"  # static-shape bucket ladder
)
BALLISTA_PREWARM = "ballista.tpu.prewarm"  # AOT kernel prewarm: off|on|background
BALLISTA_TRACE = "ballista.tpu.trace"  # distributed tracing: off|on|<jsonl path>
BALLISTA_METRICS_COLLECTOR = (
    "ballista.tpu.metrics_collector"  # executor metrics sink: shipping|logging
)
# fleet-level observability (docs/observability.md): straggler/skew
# detection thresholds + the composite autoscale target
BALLISTA_STRAGGLER_FACTOR = (
    "ballista.tpu.straggler_factor"  # flag tasks > k x stage median
)
BALLISTA_STRAGGLER_MIN_S = (
    "ballista.tpu.straggler_min_s"  # noise floor for straggler flags
)
BALLISTA_SKEW_RATIO = (
    "ballista.tpu.skew_ratio"  # flag partitions > k x stage median rows
)
BALLISTA_SKEW_MIN_ROWS = (
    "ballista.tpu.skew_min_rows"  # noise floor for skew flags
)
BALLISTA_SCALER_QUEUE_WAIT_TARGET_S = (
    "ballista.tpu.scaler_queue_wait_target_s"  # KEDA pressure target
)
# adaptive query execution (docs/aqe.md)
BALLISTA_AQE = "ballista.tpu.aqe"  # runtime re-planning policy
BALLISTA_AQE_BROADCAST_THRESHOLD_MB = (
    "ballista.tpu.aqe_broadcast_threshold_mb"  # small-build broadcast cutoff
)
BALLISTA_AQE_TARGET_PARTITION_MB = (
    "ballista.tpu.aqe_target_partition_mb"  # coalesce-toward bucket size
)
# queryable history + cost accounting (docs/observability.md)
BALLISTA_COST_ACCOUNTING = (
    "ballista.tpu.cost_accounting"  # per-attempt resource cost vectors
)
BALLISTA_HISTORY_RETENTION_JOBS = (
    "ballista.tpu.history_retention_jobs"  # persistent query-log bound
)
# serving fast path (docs/serving.md)
BALLISTA_RESULT_CACHE_MB = (
    "ballista.tpu.result_cache_mb"  # scheduler-side result cache (0 = off)
)
BALLISTA_SINGLE_STAGE_BYPASS = (
    "ballista.tpu.single_stage_bypass"  # skip stage machinery for 1-task jobs
)
BALLISTA_TASK_GRANT_BATCH = (
    "ballista.tpu.task_grant_batch"  # tasks per PollWork round-trip
)

METRICS_COLLECTORS = ("shipping", "logging")


def _parse_metrics_collector(s: str) -> str:
    v = s.lower()
    if v not in METRICS_COLLECTORS:
        raise ValueError(
            f"not a metrics collector (shipping|logging): {s!r}"
        )
    return v


def _parse_trace(s: str) -> str:
    # "off" | "on" (case-insensitive, like every other enum entry) | a
    # JSONL export path — path-like values are accepted as-is (the tracer
    # treats unwritable paths as ring-only, never fails a query on it).
    # Without the lowercasing, "OFF" would read as an export path and
    # silently turn tracing ON plus create a file named OFF.
    v = s.strip()
    if v.lower() in ("off", "on"):
        return v.lower()
    return v or "off"

SHUFFLE_COMPRESSION_CODECS = ("none", "lz4", "zstd", "auto")

PREWARM_MODES = ("off", "on", "background")


def _parse_prewarm(s: str) -> str:
    v = s.lower()
    if v not in PREWARM_MODES:
        raise ValueError(f"not a prewarm mode (off|on|background): {s!r}")
    return v


def _parse_capacity_buckets(s: str) -> str:
    from ballista_tpu.columnar.batch import CapacityLadder

    CapacityLadder.parse(s)  # raises on malformed specs
    return s


def _parse_shuffle_compression(s: str) -> str:
    v = s.lower()
    if v not in SHUFFLE_COMPRESSION_CODECS:
        raise ValueError(
            f"not a shuffle codec (none|lz4|zstd|auto): {s!r}"
        )
    return v

# Task-scoped keys the scheduler stamps onto TaskDefinition props for the
# executor (attempt number for fault keying / logging). NOT session config:
# executors strip this prefix before building BallistaConfig.
BALLISTA_INTERNAL_PREFIX = "ballista.internal."
BALLISTA_INTERNAL_TASK_ATTEMPT = "ballista.internal.task_attempt"
# distributed tracing (docs/observability.md): trace id minted at job
# submission + the parent span id (the stage's span) for the task attempt
BALLISTA_INTERNAL_TRACE_ID = "ballista.internal.trace_id"
BALLISTA_INTERNAL_SPAN_PARENT = "ballista.internal.span_parent"
# fleet observability (docs/observability.md): the job's query-class
# token rides every task so the executor's task-run histogram aggregates
# by the same label the scheduler's job-latency series uses
BALLISTA_INTERNAL_QUERY_CLASS = "ballista.internal.query_class"


@dataclasses.dataclass(frozen=True)
class EnvEntry:
    """One declared ``BALLISTA_*`` environment variable. Process-scoped
    knobs (daemons have no session config at start; debug witnesses must
    not ride query settings) live HERE; everything query-scoped is a
    ``ConfigEntry`` above. The lifelint config-registry analyzer
    (analysis/configlint.py) proves every env read site in the tree
    resolves to exactly one of these entries, and docs/config.md is
    generated from both tables. A trailing ``*`` declares a prefix family
    (per-flag daemon overrides)."""

    name: str
    kind: str  # value shape shown in docs ("0|1", "path|off", ...)
    default: str
    description: str
    doc: str  # owning doc page


ENV_REGISTRY: tuple[EnvEntry, ...] = (
    EnvEntry(
        "BALLISTA_FAULTS", "JSON list", "",
        "Deterministic fault-injection rules installed at import "
        "(testing/faults.py); chaos tests set it in SUBPROCESS envs only",
        "docs/fault_tolerance.md",
    ),
    EnvEntry(
        "BALLISTA_FAULTS_SEED", "int", "0",
        "Seed for probabilistic fault rules (p < 1)",
        "docs/fault_tolerance.md",
    ),
    EnvEntry(
        "BALLISTA_LOCK_WITNESS", "0|1", "0",
        "Runtime lock-order witness: control-plane locks record per-"
        "thread acquisition order and flag inversions live "
        "(analysis/witness.py)",
        "docs/analysis.md",
    ),
    EnvEntry(
        "BALLISTA_RESOURCE_WITNESS", "0|1", "0",
        "Runtime resource witness: channels/pools/files/spill sets "
        "register on acquire and must drain to zero at shutdown "
        "(analysis/reswitness.py)",
        "docs/analysis.md",
    ),
    EnvEntry(
        "BALLISTA_REPLAY_WITNESS", "0|1", "0",
        "Runtime replay witness: committed shuffle outputs and final "
        "result partitions record canonical content hashes; retries, "
        "lineage recomputes, and certified rewrites must re-record "
        "identical hashes (analysis/replay.py)",
        "docs/fault_tolerance.md",
    ),
    EnvEntry(
        "BALLISTA_CACHE_WITNESS", "0|1", "0",
        "Runtime cache-staleness witness: sampled cache hits are "
        "re-derived fresh and must hash-match what was served; a "
        "mismatch is a recorded stale hit (analysis/stalewitness.py)",
        "docs/analysis.md",
    ),
    EnvEntry(
        "BALLISTA_CACHE_WITNESS_SAMPLE", "float 0..1", "1",
        "Fraction of cache hits the staleness witness re-derives "
        "(deterministic per-cache stride, no RNG); 1 checks every hit, "
        "0.25 every fourth",
        "docs/analysis.md",
    ),
    EnvEntry(
        "BALLISTA_DUR_WITNESS", "0|1", "0",
        "Runtime durability witness: a restarted scheduler's recovered "
        "state is diffed against the declared durability classes — "
        "persisted fields round-trip, rebuilt fields converge, "
        "ephemeral fields start empty (analysis/durwitness.py)",
        "docs/analysis.md",
    ),
    EnvEntry(
        "BALLISTA_RPC_TIMEOUT_S", "seconds", "30",
        "Default per-call deadline for scheduler-side gRPC/etcd client "
        "calls (scheduler/rpc.py stubs, etcd lease/lock); 0 disables "
        "the default deadline",
        "docs/deployment.md",
    ),
    EnvEntry(
        "BALLISTA_AQE", "0|1", "",
        "Process-wide adaptive-query-execution override: 0/off forces "
        "the AQE policy off regardless of session config (the ops "
        "kill-switch), 1/on forces it on; unset defers to "
        "ballista.tpu.aqe",
        "docs/aqe.md",
    ),
    EnvEntry(
        "BALLISTA_TPU_JAX_CACHE", "off", "",
        "'off' disables the persistent XLA compilation cache machinery "
        "entirely. The directory is not set here: JAX's own "
        "JAX_COMPILATION_CACHE_DIR places it, and unset it is "
        "<checkout>/.jax_cache",
        "docs/compile_cache.md",
    ),
    EnvEntry(
        "BALLISTA_TPU_HINT_CACHE", "path|off", "(rides the XLA cache dir)",
        "Persisted plan-shape hints (join strategies, learned "
        "capacities) location override",
        "docs/compile_cache.md",
    ),
    EnvEntry(
        "BALLISTA_TPU_PREWARM", "off|on|background", "off",
        "AOT kernel prewarm mode for executor processes (no session "
        "config at start); an explicit --prewarm flag wins",
        "docs/compile_cache.md",
    ),
    EnvEntry(
        "BALLISTA_TPU_PREWARM_BUCKETS", "csv ints", "",
        "Bounds the prewarm ladder enumeration (tests / constrained "
        "hosts)",
        "docs/compile_cache.md",
    ),
    EnvEntry(
        "BALLISTA_TPU_CAPACITY_BUCKETS", "ladder spec", "",
        "Capacity-bucket ladder for server prewarm on non-default "
        "deployments (session config arrives only with the first task)",
        "docs/compile_cache.md",
    ),
    EnvEntry(
        "BALLISTA_TPU_NO_FUSE", "set|unset", "",
        "Debug: disable Filter/Projection chain fusion (per-operator "
        "dispatch, for isolating a fused-kernel miscompare)",
        "docs/analysis.md",
    ),
    EnvEntry(
        "BALLISTA_PLUGIN_DIR", "path", "",
        "UDF plugin directory consulted alongside ballista.plugin_dir",
        "docs/client-api.md",
    ),
    EnvEntry(
        "BALLISTA_SCHEDULER_*", "per-flag", "",
        "Scheduler daemon CLI-flag defaults "
        "(BALLISTA_SCHEDULER_<FLAG>=v; scheduler/__main__.py)",
        "docs/deployment.md",
    ),
    EnvEntry(
        "BALLISTA_EXECUTOR_*", "per-flag", "",
        "Executor daemon CLI-flag defaults (executor/__main__.py)",
        "docs/deployment.md",
    ),
    EnvEntry(
        "BALLISTA_TEST_TIME_LIMIT_S", "seconds", "300",
        "Tier-1 per-test wall-clock guard (tests/conftest.py); 0 "
        "disables",
        "docs/analysis.md",
    ),
)


def env_entry_for(name: str) -> EnvEntry | None:
    """The registry entry covering env var ``name`` (exact or prefix
    family), or None — the runtime side of the configlint closure."""
    for e in ENV_REGISTRY:
        if e.name.endswith("*"):
            if name.startswith(e.name[:-1]):
                return e
        elif e.name == name:
            return e
    return None


_ENV_WARNED = False


def warn_unknown_env() -> list[str]:
    """Warn (once per process) about ``BALLISTA_*`` environment variables
    no registry entry covers — a typo'd knob silently doing nothing is
    the env-var analogue of the unknown-config-key ConfigError. Returns
    the offending names (for tests)."""
    import logging
    import os

    global _ENV_WARNED
    unknown = sorted(
        k for k in os.environ
        if k.startswith("BALLISTA_") and env_entry_for(k) is None
    )
    if unknown and not _ENV_WARNED:
        logging.getLogger(__name__).warning(
            "unrecognized BALLISTA_* environment variables (typo? see "
            "docs/config.md): %s", ", ".join(unknown),
        )
    _ENV_WARNED = True
    return unknown


class TaskSchedulingPolicy(Enum):
    """Pull vs push task dispatch (ref config.rs:264-281)."""

    PULL_STAGED = "pull-staged"
    PUSH_STAGED = "push-staged"

    @classmethod
    def parse(cls, s: str) -> "TaskSchedulingPolicy":
        for p in cls:
            if p.value == s.lower():
                return p
        raise ConfigError(f"invalid task scheduling policy: {s!r}")


def _parse_bool(s: str) -> bool:
    if s.lower() in ("true", "1", "yes"):
        return True
    if s.lower() in ("false", "0", "no"):
        return False
    raise ValueError(f"not a boolean: {s!r}")


@dataclasses.dataclass(frozen=True)
class ConfigEntry:
    """One valid setting: name, description, validator (ref config.rs:60-92)."""

    name: str
    description: str
    default: str
    parse: Callable[[str], object]


def _entries() -> dict[str, ConfigEntry]:
    """The closed set of valid settings (ref config.rs valid_entries :156-187)."""
    ents = [
        ConfigEntry(BALLISTA_JOB_NAME, "Job name shown in the UI", "", str),
        ConfigEntry(
            BALLISTA_DEFAULT_SHUFFLE_PARTITIONS,
            "Shuffle (exchange) output partition count",
            "2",
            int,
        ),
        ConfigEntry(
            BALLISTA_DEFAULT_BATCH_SIZE, "Rows per record batch", "8192", int
        ),
        ConfigEntry(
            BALLISTA_REPARTITION_JOINS,
            "Repartition inputs of joins for parallelism",
            "true",
            _parse_bool,
        ),
        ConfigEntry(
            BALLISTA_REPARTITION_AGGREGATIONS,
            "Repartition inputs of aggregations for parallelism",
            "true",
            _parse_bool,
        ),
        ConfigEntry(
            BALLISTA_REPARTITION_WINDOWS,
            "Repartition inputs of window functions and percentiles by "
            "their keys",
            "true",
            _parse_bool,
        ),
        ConfigEntry(
            BALLISTA_PARQUET_PRUNING,
            "Prune parquet row groups by statistics",
            "true",
            _parse_bool,
        ),
        ConfigEntry(
            BALLISTA_WITH_INFORMATION_SCHEMA,
            "Expose information_schema tables (needed for SHOW)",
            "false",
            _parse_bool,
        ),
        ConfigEntry(BALLISTA_PLUGIN_DIR, "UDF plugin directory", "", str),
        ConfigEntry(
            BALLISTA_PROFILE_DIR,
            "When set, wrap task execution in jax.profiler.trace writing "
            "TensorBoard-compatible device traces here (SURVEY §5 tracing: "
            "the XLA profiler hook beside per-op host metrics)",
            "",
            str,
        ),
        ConfigEntry(BALLISTA_DEVICE, "Execution device: tpu|cpu|auto", "auto", str),
        ConfigEntry(
            BALLISTA_AGG_CAPACITY,
            "Static capacity (max distinct groups) of device hash aggregates",
            str(1 << 16),
            int,
        ),
        ConfigEntry(
            BALLISTA_BUILD_CACHE_MB,
            "HBM budget (MB) for caching join build tables across queries "
            "on the same registered data. A warm TPC-H suite re-collects "
            "and re-sorts each dimension/build side every run otherwise. "
            "0 disables.",
            "2048",
            int,
        ),
        ConfigEntry(
            BALLISTA_TPU_BATCH_ROWS,
            "Rows per DeviceBatch cut from a scan (the device-side analogue "
            "of ballista.batch.size; larger batches amortize per-dispatch "
            "and per-batch aggregate costs, smaller ones bound HBM use). "
            "The default, 2M, is what every cell of BENCHMARK.json runs "
            "(PERF.md section 4); no other value is on the ledger",
            str(1 << 21),
            int,
        ),
        ConfigEntry(
            BALLISTA_COLLECTIVE_SHUFFLE,
            "Use jax.lax.all_to_all over ICI for on-pod shuffles",
            "true",
            _parse_bool,
        ),
        ConfigEntry(
            BALLISTA_SCAN_STREAM_MB,
            "Projected (post-pruning, post-projection) host-byte size above "
            "which a parquet scan streams row-group slices through the "
            "device instead of materializing + caching the whole table. "
            "Keeps tables far larger than HBM (TPC-H SF=100) runnable on "
            "one chip; 0 disables streaming. Materialized residency is "
            "faster when the working set fits, so the threshold should stay "
            "a healthy fraction of HBM. Also bounds the device bytes an "
            "executor's scan store keeps resident across queries (0: no "
            "bound).",
            "4096",
            int,
        ),
        ConfigEntry(
            BALLISTA_HBM_BUDGET_MB,
            "Device-memory budget (MB) an operator's resident working set "
            "may use before it switches to grace-hash partitioned passes: "
            "a join build side or a final-aggregate state set larger than "
            "this is hash-split into K ranges, spilled to host Arrow IPC "
            "files, and processed range-by-range through the same kernels "
            "(docs/memory.md). 0 disables — every pipeline must then fit "
            "in HBM at once.",
            "0",
            int,
        ),
        ConfigEntry(
            BALLISTA_SPILL_BUDGET_MB,
            "Host-disk budget (MB) for grace-hash spill files per task "
            "attempt; exceeding it fails the task rather than filling the "
            "disk. 0 = unlimited.",
            str(1 << 16),
            int,
        ),
        ConfigEntry(
            BALLISTA_SPILL_DIR,
            "Directory for grace-hash spill files. Empty = the task's "
            "work_dir (distributed executors — files then share the "
            "shuffle TTL sweep) or the system temp dir (local contexts).",
            "",
            str,
        ),
        ConfigEntry(
            BALLISTA_PREFETCH_DEPTH,
            "Row-group slices a streamed parquet scan reads/converts and "
            "stages ahead of the slice currently computing (a background "
            "host thread overlaps parquet decode + host->device transfer "
            "with device time). 0 disables the overlap; 1 (double "
            "buffering) is usually enough to hide decode on scan-bound "
            "queries.",
            "1",
            int,
        ),
        ConfigEntry(
            BALLISTA_VERIFY_PLANS,
            "Statically verify plans before execution/submission "
            "(ballista_tpu/analysis/verifier.py): schema agreement, column "
            "resolution, TPU dtype legality, shuffle partition-count "
            "consistency, stage-DAG well-formedness. Errors surface as "
            "PlanVerificationError at submission time instead of failing "
            "on an executor mid-query. On by default; off trades the "
            "(sub-ms) walk for zero submission-path checking.",
            "true",
            _parse_bool,
        ),
        ConfigEntry(
            BALLISTA_TASK_MAX_ATTEMPTS,
            "Max execution attempts per task before the job fails. On a "
            "retryable failure the scheduler requeues the task "
            "(FAILED -> PENDING) preferring an executor the task has not "
            "failed on; deterministic errors (PlanVerificationError and "
            "the rest of errors.NON_RETRYABLE_ERROR_TYPES) short-circuit "
            "straight to JobFailed. Also bounds lost-shuffle recompute "
            "rounds per producing stage (docs/fault_tolerance.md). 1 "
            "disables retries.",
            "3",
            int,
        ),
        ConfigEntry(
            BALLISTA_FETCH_RETRIES,
            "Attempts per shuffle-partition Flight fetch before the fetch "
            "escalates to a ShuffleFetchError (scheduler-level recompute). "
            "Only transient transport errors (unavailable/timeout) are "
            "retried; data corruption escalates immediately.",
            "3",
            int,
        ),
        ConfigEntry(
            BALLISTA_FETCH_BACKOFF_MS,
            "Base backoff (ms) between fetch attempts; grows exponentially "
            "per attempt with +-25% deterministic jitter, capped at 100x "
            "the base.",
            "50",
            int,
        ),
        ConfigEntry(
            BALLISTA_FETCH_TIMEOUT_S,
            "Per-attempt deadline (seconds) on a shuffle fetch Flight call "
            "— a blackholed executor must fail the attempt, not wedge the "
            "reading task forever. Generous by default: it bounds a whole "
            "partition stream, not one batch. 0 disables.",
            "300",
            float,
        ),
        ConfigEntry(
            BALLISTA_SHUFFLE_FETCH_CONCURRENCY,
            "Upstream shuffle locations a ShuffleReaderExec pulls "
            "CONCURRENTLY (each into a small bounded batch queue) while "
            "the device consumes earlier ones in order — network/disk "
            "overlapped with compute, yield order (and therefore results) "
            "identical to the sequential pull. <= 1 restores the "
            "sequential fetch loop (the A/B baseline).",
            "4",
            int,
        ),
        ConfigEntry(
            BALLISTA_SHUFFLE_COMPRESSION,
            "IPC buffer compression for shuffle files and Flight shuffle "
            "streams: none|lz4|zstd|auto. Applied by ShuffleWriterExec "
            "via pa.ipc.IpcWriteOptions and requested from the serving "
            "executor per Flight ticket; readers auto-detect per file, so "
            "mixed codecs within one consumed partition (rolling "
            "upgrades) are fine. 'auto' (default) negotiates per "
            "(producer, consumer) link: 'none' when the pair is "
            "colocated (same host, shared filesystem, or one ICI mesh: "
            "there is no wire to save, only codec CPU to pay) and 'lz4' "
            "when shuffle bytes genuinely cross a "
            "NIC; files are written uncompressed under auto since the "
            "wire codec is re-negotiated per fetch anyway. Explicit lz4/"
            "zstd force that codec everywhere; none disables it.",
            "auto",
            _parse_shuffle_compression,
        ),
        ConfigEntry(
            BALLISTA_EAGER_SHUFFLE,
            "Publish completed map-task shuffle locations to scheduled "
            "consumer tasks BEFORE the producing stage fully completes "
            "(docs/shuffle.md): consumers of a pending stage whose "
            "producers are all in flight with some output already "
            "committed start fetching early, overlapping upstream "
            "compute with downstream fetch. Stage promotion remains the "
            "commit point, so lineage recovery and the stage verifier "
            "are unchanged. Off restores strictly barriered consumption.",
            "true",
            _parse_bool,
        ),
        ConfigEntry(
            BALLISTA_PUSH_SHUFFLE,
            "Push-shuffle fast path (docs/shuffle.md): ShuffleWriterExec "
            "holds committed shuffle partitions IN MEMORY on the "
            "producing executor and consumers stream them over a Flight "
            "DoExchange call (or straight out of the in-process registry "
            "when colocated) — zero disk I/O on the hot path. The disk "
            "file remains the recovery substrate: when the in-flight "
            "window (ballista.tpu.push_shuffle_window_mb) overflows or a "
            "consumer lags, streams spill to the ordinary shuffle path "
            "and consumers fall back to the pull data plane; a producer "
            "lost mid-push recovers through the normal lineage-recompute "
            "machinery. Requires eager shuffle and a scheduler-connected "
            "executor; anything else silently keeps the pull path.",
            "true",
            _parse_bool,
        ),
        ConfigEntry(
            BALLISTA_PUSH_SHUFFLE_WINDOW_MB,
            "Bound (MB) on in-memory push-shuffle bytes held per executor "
            "process (the producer->consumer in-flight window). When an "
            "append would exceed it, sealed streams whose consumers lag "
            "spill to their shuffle-file path first (oldest first), then "
            "the appending stream itself converts to disk writing — "
            "backpressure degrades push to the pull path instead of "
            "growing host memory. <= 0 disables push buffering entirely "
            "(every stream goes straight to disk).",
            "256",
            int,
        ),
        ConfigEntry(
            BALLISTA_SHUFFLE_TARGET_BATCH_MB,
            "Target size (MB) shuffle batches are coalesced up to before "
            "hitting the wire/disk: post-partition slices of a hash "
            "shuffle are tiny (batch bytes / fan-out), and per-batch "
            "fixed costs (IPC framing, Flight chunk round-trips, queue "
            "handoffs, device-upload dispatch) are paid per batch, not "
            "per byte. Writers concatenate "
            "sub-target batches before write/stream; readers concatenate "
            "sub-target batches before device upload. 0 disables "
            "coalescing (every partition slice ships as-is).",
            "8",
            int,
        ),
        ConfigEntry(
            BALLISTA_EAGER_POLL_MS,
            "Cadence (ms) at which an eager shuffle reader re-polls the "
            "scheduler for newly published upstream locations. The poll "
            "is one small unary RPC; a short cadence matters because a "
            "blocked reader's completion latency quantizes to it (one "
            "stage boundary per query stage) while the scheduler-side "
            "cost stays trivial.",
            "10",
            int,
        ),
        ConfigEntry(
            BALLISTA_CAPACITY_BUCKETS,
            "Static-shape capacity-bucket ladder (docs/compile_cache.md): "
            "every padded row capacity rounds UP through this ladder so "
            "unrelated queries share compiled programs. '<min>:<ratio>' "
            "is geometric (default 2048:2, the historical power-of-two "
            "rounding); an explicit 'b0,b1,...' list is extended "
            "geometrically past its top. Coarser ladders shrink the "
            "compile vocabulary (fewer distinct signatures to trace, "
            "compile, and prewarm) at the cost of up to ratio-1 x padding "
            "on intermediate results.",
            "2048:2",
            _parse_capacity_buckets,
        ),
        ConfigEntry(
            BALLISTA_PREWARM,
            "AOT-compile the closed kernel vocabulary (ops/: sort, "
            "gather, compact primitives per capacity bucket and dtype — "
            "ballista_tpu/compilecache/registry.py) at context/executor "
            "start, populating the jit and persistent XLA caches before "
            "the first query: 'on' blocks startup until warm, "
            "'background' compiles on a small thread pool joined at "
            "shutdown, 'off' (default) pays compiles lazily on the first "
            "query that needs each kernel.",
            "off",
            _parse_prewarm,
        ),
        ConfigEntry(
            BALLISTA_TRACE,
            "Distributed query tracing (docs/observability.md): 'off' "
            "(default — zero overhead, no trace context is ever minted), "
            "'on' (spans recorded to the bounded in-process ring and "
            "shipped executor->scheduler for the per-job span tree), or a "
            "filesystem path (ring + shipping plus JSONL export, one span "
            "per line, appended). Spans cover plan/verify, stage "
            "lifecycle, task attempts (incl. retries and lineage "
            "recompute), per-location shuffle fetch, spill passes, and "
            "trace-cache misses. The JSONL sink is PROCESS-wide: when "
            "concurrent sessions configure different paths, the most "
            "recently submitted session's sink wins for spans recorded "
            "after it (the ring and shipped spans are unaffected).",
            "off",
            _parse_trace,
        ),
        ConfigEntry(
            BALLISTA_METRICS_COLLECTOR,
            "Executor metrics sink (docs/observability.md): 'shipping' "
            "(default) meters every operator of a stage fragment and "
            "serializes per-operator counters/timers into the completed "
            "TaskStatus — the scheduler aggregates them per (job, stage, "
            "partition) for /api/job/<id>, /api/metrics, and the AQE "
            "stats substrate; 'logging' restores the reference's "
            "LoggingMetricsCollector (annotated plan into the executor "
            "log, nothing shipped).",
            "shipping",
            _parse_metrics_collector,
        ),
        ConfigEntry(
            BALLISTA_STRAGGLER_FACTOR,
            "Straggler monitor (docs/observability.md): a completed task "
            "whose duration exceeds this factor times the median of its "
            "stage's completed task durations (with at least 3 "
            "completions to form a median) is flagged — a `straggler` "
            "trace event, the ballista_stragglers_total counter, and the "
            "/api/job/<id>/timeline straggler bit. <= 0 disables.",
            "3",
            float,
        ),
        ConfigEntry(
            BALLISTA_STRAGGLER_MIN_S,
            "Noise floor for the straggler monitor: tasks faster than "
            "this are never flagged regardless of the ratio (sub-second "
            "scheduling jitter would otherwise flag trivial stages).",
            "1",
            float,
        ),
        ConfigEntry(
            BALLISTA_SKEW_RATIO,
            "Skew monitor (docs/observability.md): when a stage "
            "completes, a (stage, partition) whose processed rows exceed "
            "this ratio over the stage's median partition is flagged — a "
            "`skew` trace event, the ballista_skew_partitions_total "
            "counter, and /api/job/<id> skew list. This is the signal "
            "the AQE split/coalesce policy consumes. <= 0 disables.",
            "4",
            float,
        ),
        ConfigEntry(
            BALLISTA_SKEW_MIN_ROWS,
            "Noise floor for the skew monitor: partitions smaller than "
            "this many rows are never flagged (splitting tiny partitions "
            "cannot help anyone).",
            "4096",
            int,
        ),
        ConfigEntry(
            BALLISTA_SCALER_QUEUE_WAIT_TARGET_S,
            "Declared queue-wait target for the KEDA ExternalScaler's "
            "composite pressure signal (docs/observability.md): when the "
            "p90 of recent job queue waits (submit -> first task "
            "assignment) exceeds this, the reported desired-executor "
            "count scales up proportionally (capped at 4x) on top of the "
            "inflight-task demand. <= 0 disables the queue-wait term.",
            "2",
            float,
        ),
        ConfigEntry(
            BALLISTA_AQE,
            "Adaptive query execution (docs/aqe.md): the scheduler's "
            "runtime re-planning policy reads completed producers' "
            "shuffle stats + the skew monitor at StageFinished, decides "
            "which certified rewrite to apply (build-side flip, "
            "small-side broadcast, coalesce/split of shuffle buckets), "
            "applies every adaptation through "
            "SchedulerServer.apply_certified_rewrite (a failing "
            "certificate clause rejects it and the job proceeds on the "
            "pristine plan), and persists learned per-query-class "
            "strategies through the plan-hint seam so a fresh process "
            "plans adaptively from submission. Off (default) records "
            "and applies nothing. The BALLISTA_AQE env var overrides "
            "this process-wide.",
            "false",
            _parse_bool,
        ),
        ConfigEntry(
            BALLISTA_AQE_BROADCAST_THRESHOLD_MB,
            "AQE broadcast cutoff (docs/aqe.md): a partitioned join "
            "whose build side measured under this many MB of shuffle "
            "output is re-planned as a collect (broadcast-build) join "
            "on the next submission of its query class. <= 0 disables "
            "the broadcast rule.",
            "32",
            int,
        ),
        ConfigEntry(
            BALLISTA_AQE_TARGET_PARTITION_MB,
            "AQE coalesce target (docs/aqe.md): when a consumer's "
            "observed input buckets would all fit in fewer buckets of "
            "this size, the bucket count is coalesced down to that "
            "ideal on the next submission of its query class (fuller "
            "buckets amortize per-task costs). Skewed inputs instead "
            "split, governed by ballista.tpu.skew_ratio/skew_min_rows. "
            "<= 0 disables the coalesce rule.",
            "16",
            int,
        ),
        ConfigEntry(
            BALLISTA_COST_ACCOUNTING,
            "Per-attempt resource cost accounting "
            "(docs/observability.md): executors measure a cost vector "
            "(wall seconds, CPU thread-time, shuffle bytes read/"
            "written, pushed bytes, spill bytes, claimed compile "
            "seconds) around every task attempt — failed attempts too — "
            "and ship it home on the task status. The scheduler "
            "aggregates per job (JobInfo.cost), rolls up per query "
            "class (the ballista_job_cost_total Prometheus counters), "
            "and persists it with the job's history record — the "
            "attribution substrate multi-tenant charging and fair-share "
            "need. Off skips the measurement and ships no cost.",
            "true",
            _parse_bool,
        ),
        ConfigEntry(
            BALLISTA_HISTORY_RETENTION_JOBS,
            "Jobs retained in the persistent query-history log "
            "(docs/observability.md): the append-only submit/complete/"
            "fail records (plus per-attempt cost records) written "
            "through the scheduler's state backend and served by "
            "GET /api/history and the system.queries / "
            "system.task_attempts SQL tables. Beyond this many jobs the "
            "OLDEST jobs' records are deleted on the next submission — "
            "compaction keeps the store bounded on every backend "
            "(memory, sqlite, etcd).",
            "512",
            int,
        ),
        ConfigEntry(
            BALLISTA_RESULT_CACHE_MB,
            "Scheduler-side result cache budget in MB (docs/serving.md): "
            "a bounded LRU keyed by the canonical optimized-plan "
            "fingerprint composed with the registered tables' data "
            "versions. A repeated identical query over unchanged data is "
            "served straight from the scheduler — no stages, no "
            "executor round-trip — with the hit/miss/bytes counters on "
            "/api/metrics and a `cache` event in the job trace. "
            "Re-registering or appending to a table changes its data "
            "version and naturally misses; system.* tables are never "
            "cached. 0 (default) disables the cache entirely.",
            "0",
            int,
        ),
        ConfigEntry(
            BALLISTA_SINGLE_STAGE_BYPASS,
            "Single-stage orchestration bypass (docs/serving.md): when "
            "stage splitting yields exactly one stage with one input "
            "partition, skip the stage state machine and hand the plan "
            "out as ONE direct task grant; the result streams back "
            "through the normal Flight path. JobInfo, history, cost "
            "accounting, queue-wait metering, and traces see bypassed "
            "jobs identically (a `bypass` trace event marks them). "
            "Failed grants retry bounded by task_max_attempts, exactly "
            "like staged tasks.",
            "true",
            _parse_bool,
        ),
        ConfigEntry(
            BALLISTA_TASK_GRANT_BATCH,
            "Max tasks one PollWork round-trip may grant "
            "(docs/serving.md): executors advertise their free slots on "
            "each poll and the scheduler fills up to "
            "min(free_slots, this) task definitions into the reply, "
            "collapsing per-task RPC chatter at high QPS. 1 restores "
            "the one-task-per-poll reference behavior. Read from the "
            "SCHEDULER's config (PollWork has no session).",
            "4",
            int,
        ),
        ConfigEntry(
            BALLISTA_EAGER_WAIT_S,
            "Deadline (seconds) an eager reader waits for a "
            "not-yet-published upstream location before failing the task "
            "back to the scheduler (bounded retry) — distinguishes "
            "'not yet published' (wait) from a wedged producer. 0 "
            "disables the deadline.",
            "60",
            float,
        ),
    ]
    return {e.name: e for e in ents}


_VALID = _entries()


class BallistaConfig:
    """Validated session config (ref config.rs:94-259).

    Construct via :meth:`builder` / :meth:`with_setting` or ``from_settings``.
    Unknown keys and unparsable values raise :class:`ConfigError` — the same
    contract the reference enforces in ``BallistaConfigBuilder::build``.
    """

    def __init__(self, settings: dict[str, str] | None = None):
        self._settings: dict[str, str] = {}
        for k, v in (settings or {}).items():
            self._validate(k, v)
            self._settings[k] = v

    @staticmethod
    def _validate(key: str, value: str) -> None:
        entry = _VALID.get(key)
        if entry is None:
            raise ConfigError(f"unknown configuration key: {key!r}")
        try:
            entry.parse(value)
        except Exception as e:
            raise ConfigError(
                f"invalid value {value!r} for {key!r}: {e}"
            ) from e

    @classmethod
    def builder(cls) -> "BallistaConfig":
        return cls()

    def with_setting(self, key: str, value: str) -> "BallistaConfig":
        new = dict(self._settings)
        self._validate(key, value)
        new[key] = value
        return BallistaConfig(new)

    def settings(self) -> dict[str, str]:
        return dict(self._settings)

    def _get(self, key: str):
        entry = _VALID[key]
        raw = self._settings.get(key, entry.default)
        return entry.parse(raw)

    # Typed getters (ref config.rs:193-258).
    def default_shuffle_partitions(self) -> int:
        return self._get(BALLISTA_DEFAULT_SHUFFLE_PARTITIONS)

    def default_batch_size(self) -> int:
        return self._get(BALLISTA_DEFAULT_BATCH_SIZE)

    def repartition_joins(self) -> bool:
        return self._get(BALLISTA_REPARTITION_JOINS)

    def repartition_aggregations(self) -> bool:
        return self._get(BALLISTA_REPARTITION_AGGREGATIONS)

    def repartition_windows(self) -> bool:
        return self._get(BALLISTA_REPARTITION_WINDOWS)

    def parquet_pruning(self) -> bool:
        return self._get(BALLISTA_PARQUET_PRUNING)

    def with_information_schema(self) -> bool:
        return self._get(BALLISTA_WITH_INFORMATION_SCHEMA)

    def plugin_dir(self) -> str:
        return self._get(BALLISTA_PLUGIN_DIR)

    def device(self) -> str:
        return self._get(BALLISTA_DEVICE)

    def tpu_batch_rows(self) -> int:
        return self._get(BALLISTA_TPU_BATCH_ROWS)

    def agg_capacity(self) -> int:
        return self._get(BALLISTA_AGG_CAPACITY)

    def profile_dir(self) -> str:
        return self._get(BALLISTA_PROFILE_DIR)

    def build_cache_mb(self) -> int:
        return self._get(BALLISTA_BUILD_CACHE_MB)

    def scan_stream_mb(self) -> int:
        return self._get(BALLISTA_SCAN_STREAM_MB)

    def hbm_budget_mb(self) -> int:
        return self._get(BALLISTA_HBM_BUDGET_MB)

    def spill_budget_mb(self) -> int:
        return self._get(BALLISTA_SPILL_BUDGET_MB)

    def spill_dir(self) -> str:
        return self._get(BALLISTA_SPILL_DIR)

    def prefetch_depth(self) -> int:
        return self._get(BALLISTA_PREFETCH_DEPTH)

    def collective_shuffle(self) -> bool:
        return self._get(BALLISTA_COLLECTIVE_SHUFFLE)

    def verify_plans(self) -> bool:
        return self._get(BALLISTA_VERIFY_PLANS)

    def task_max_attempts(self) -> int:
        return max(1, self._get(BALLISTA_TASK_MAX_ATTEMPTS))

    def fetch_retries(self) -> int:
        return max(1, self._get(BALLISTA_FETCH_RETRIES))

    def fetch_backoff_ms(self) -> int:
        return max(0, self._get(BALLISTA_FETCH_BACKOFF_MS))

    def fetch_timeout_s(self) -> float:
        return max(0.0, self._get(BALLISTA_FETCH_TIMEOUT_S))

    def shuffle_fetch_concurrency(self) -> int:
        return max(0, self._get(BALLISTA_SHUFFLE_FETCH_CONCURRENCY))

    def shuffle_compression(self) -> str:
        return self._get(BALLISTA_SHUFFLE_COMPRESSION)

    def eager_shuffle(self) -> bool:
        return self._get(BALLISTA_EAGER_SHUFFLE)

    def push_shuffle(self) -> bool:
        return self._get(BALLISTA_PUSH_SHUFFLE)

    def push_shuffle_window_mb(self) -> int:
        return self._get(BALLISTA_PUSH_SHUFFLE_WINDOW_MB)

    def shuffle_target_batch_mb(self) -> int:
        return max(0, self._get(BALLISTA_SHUFFLE_TARGET_BATCH_MB))

    def eager_poll_ms(self) -> int:
        return max(1, self._get(BALLISTA_EAGER_POLL_MS))

    def eager_wait_s(self) -> float:
        return max(0.0, self._get(BALLISTA_EAGER_WAIT_S))

    def capacity_buckets(self) -> str:
        return self._get(BALLISTA_CAPACITY_BUCKETS)

    def prewarm(self) -> str:
        return self._get(BALLISTA_PREWARM)

    def trace(self) -> str:
        return self._get(BALLISTA_TRACE)

    def metrics_collector(self) -> str:
        return self._get(BALLISTA_METRICS_COLLECTOR)

    def straggler_factor(self) -> float:
        return self._get(BALLISTA_STRAGGLER_FACTOR)

    def straggler_min_s(self) -> float:
        return max(0.0, self._get(BALLISTA_STRAGGLER_MIN_S))

    def skew_ratio(self) -> float:
        return self._get(BALLISTA_SKEW_RATIO)

    def skew_min_rows(self) -> int:
        return max(0, self._get(BALLISTA_SKEW_MIN_ROWS))

    def scaler_queue_wait_target_s(self) -> float:
        return self._get(BALLISTA_SCALER_QUEUE_WAIT_TARGET_S)

    def aqe(self) -> bool:
        return self._get(BALLISTA_AQE)

    def aqe_broadcast_threshold_mb(self) -> int:
        return self._get(BALLISTA_AQE_BROADCAST_THRESHOLD_MB)

    def aqe_target_partition_mb(self) -> int:
        return self._get(BALLISTA_AQE_TARGET_PARTITION_MB)

    def cost_accounting(self) -> bool:
        return self._get(BALLISTA_COST_ACCOUNTING)

    def history_retention_jobs(self) -> int:
        return max(1, self._get(BALLISTA_HISTORY_RETENTION_JOBS))

    def result_cache_mb(self) -> int:
        return max(0, self._get(BALLISTA_RESULT_CACHE_MB))

    def single_stage_bypass(self) -> bool:
        return self._get(BALLISTA_SINGLE_STAGE_BYPASS)

    def task_grant_batch(self) -> int:
        return max(1, self._get(BALLISTA_TASK_GRANT_BATCH))

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, BallistaConfig) and other._settings == self._settings
        )

    def __repr__(self) -> str:
        return f"BallistaConfig({self._settings!r})"
