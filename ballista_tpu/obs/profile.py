"""Per-operator runtime profiling: the EXPLAIN ANALYZE substrate.

:func:`instrument_plan` walks a physical plan tree and wraps every
operator's ``execute`` (as an instance attribute shadowing the class
method — parents call ``child.execute(...)``, so the wrapper sees every
batch) to meter, per operator:

- ``output_rows`` — valid rows produced. Recorded as LAZY device scalars
  (``batch.valid.sum()``), exactly the discipline
  :meth:`~ballista_tpu.exec.base.Metrics.summary` documents: nothing
  syncs on the hot path; the single resolution happens at report time.
- ``output_batches`` / ``output_bytes`` — batch count and the device
  residency of what was produced (capacity x dtype widths, host
  arithmetic — no sync).
- ``self_s`` (timer) — the operator's own stretches of the thread's
  time (``obs.trace.stretch``): from entering its iterator's ``next()``
  to the batch coming back, without its inputs' ``next()`` and without
  any host phase, the metering of the batch included. Each stretch is a
  ``ballista/op.<Operator>`` annotation on the profiler's clock. On the
  served path nothing syncs, so this is host time: Python, tracing and
  the dispatch of the operator's programs, not the time they run.
- ``elapsed`` (timer, ``EXPLAIN ANALYZE`` only) — wall seconds spent
  INSIDE this operator's iterator until the batch it yields is COMPLETE
  on the device (the timer ends in ``block_until_ready``), cumulative
  over the operator and its inputs (the Spark UI convention).

The same counters feed three consumers: ``EXPLAIN ANALYZE`` renders
:func:`annotated_display`; the executor's ShippingMetricsCollector
serializes :func:`operator_metrics` into ``CompletedTask`` so the
scheduler aggregates per (job, stage, partition); and the AQE roadmap
item re-plans from exactly these per-partition row/byte stats.
"""

from __future__ import annotations

import time

import jax

from ballista_tpu.datatypes import DataType
from ballista_tpu.obs import trace as obs_trace

_DONE = object()  # next()'s default: the input is exhausted

# device-resident width per column dtype (bytes/row at capacity) — host
# arithmetic only, mirroring columnar/batch.py's storage choices
_DTYPE_BYTES = {
    DataType.BOOL: 1,
    DataType.INT32: 4,
    DataType.INT64: 8,
    DataType.FLOAT32: 4,
    DataType.FLOAT64: 8,
    DataType.DATE32: 4,
    DataType.TIMESTAMP_US: 8,
    DataType.STRING: 4,  # dictionary codes
}


def batch_nbytes(batch) -> int:
    """Approximate device bytes of one DeviceBatch (capacity-padded), from
    schema dtypes — no device sync."""
    cap = int(batch.valid.shape[0]) if batch.valid is not None else 0
    per_row = sum(_DTYPE_BYTES.get(f.dtype, 8) for f in batch.schema)
    return cap * (per_row + 1)  # +1 for the valid mask


def instrument_plan(plan, sync: bool = False) -> None:
    """Wrap every node's ``execute`` with the metering shim (idempotent:
    re-instrumenting an already-wrapped node is a no-op, so cached plan
    instances survive repeated EXPLAIN ANALYZE runs), and a task root's
    ``execute_shuffle_write`` with its stretch. ``sync`` (EXPLAIN ANALYZE)
    also times each step to ``block_until_ready`` on the batch, cumulative
    over the inputs, as ``elapsed``."""

    def wrap(node) -> None:
        if getattr(node, "_obs_metered", False):
            return
        orig = node.execute
        label = f"ballista/op.{type(node).__name__}"

        def metered(partition, ctx, _orig=orig, _node=node):
            m = _node.metrics
            own = obs_trace.stretch(label, m)
            with own:
                it = iter(_orig(partition, ctx))
            try:
                while True:
                    with own:
                        t0 = time.perf_counter() if sync else 0.0
                        batch = next(it, _DONE)
                        if sync:
                            if batch is not _DONE:
                                jax.block_until_ready(batch)
                            m.timers["elapsed"] = m.timers.get(
                                "elapsed", 0.0
                            ) + (time.perf_counter() - t0)
                        if batch is _DONE:
                            break
                        m.add("output_batches")
                        if batch.valid is not None:
                            # lazy device scalar; Metrics.summary resolves it
                            m.add("output_rows", batch.valid.sum())
                            m.add("output_bytes", batch_nbytes(batch))
                    # no stretch is held across the yield
                    yield batch
            finally:
                close = getattr(it, "close", None)
                if close is not None:
                    with own:
                        close()

        node.execute = metered
        write = getattr(node, "execute_shuffle_write", None)
        if write is not None:

            def metered_write(partition, ctx, _orig=write, _node=node):
                with obs_trace.stretch(label, _node.metrics):
                    return _orig(partition, ctx)

            node.execute_shuffle_write = metered_write
        node._obs_metered = True
        for c in node.children():
            wrap(c)

    wrap(plan)


def reset_plan_metrics(plan) -> None:
    """Clear every node's counters/timers. Called at the top of each task
    ATTEMPT (run_with_capacity_retry re-invokes its fn on CapacityError/
    SpeculationMiss with the same plan instance): without the reset, the
    shipped metrics would sum the aborted partial attempt into the final
    one — inflated rows/bytes/elapsed poisoning exactly the stats
    substrate AQE re-plans from."""
    for _path, node in walk_paths(plan):
        node.metrics.reset()


def walk_paths(plan):
    """Yield ``(path, node)`` in display (pre-)order; path is the
    dot-joined child-index chain ("0", "0.0", "0.1", ...) — a stable
    operator identity across serialization (proto carries no object
    ids)."""

    def rec(node, path):
        yield path, node
        for i, c in enumerate(node.children()):
            yield from rec(c, f"{path}.{i}")

    yield from rec(plan, "0")


def operator_metrics(plan) -> list[dict]:
    """Per-operator metric records for one executed plan tree — the
    payload the ShippingMetricsCollector sends home. Device-scalar
    counters resolve here (one sync, at report time). ``subquery`` marks
    the operators of an aggregate that decorrelates a scalar subquery, and
    ``reduction`` the semi join below one; the executor reads them and
    they do not ship."""
    out = []
    for path, node in walk_paths(plan):
        out.append(
            {
                "path": path,
                "operator": type(node).__name__,
                "describe": node.describe(),
                "counters": node.metrics.summary(),
                "subquery": getattr(node, "subquery", False),
                "reduction": getattr(node, "reduction", False),
            }
        )
    return out


def merge_counter_maps(maps) -> dict:
    """Sum stringly-typed counter maps (cross-partition aggregation)."""
    out: dict = {}
    for m in maps:
        for k, v in m.items():
            out[k] = out.get(k, 0) + v
    return {k: round(v, 6) if isinstance(v, float) else v
            for k, v in sorted(out.items())}


def annotated_display(plan, extra: dict | None = None) -> str:
    """The physical plan display re-printed with measured
    rows/bytes/elapsed/self time per operator (the EXPLAIN ANALYZE body;
    records shipped from executors carry ``self`` without ``elapsed``).
    ``extra``: {path: counter-map} merged in (e.g. scheduler-side
    aggregates for operators that ran remotely)."""
    lines = []
    for path, node in walk_paths(plan):
        d = path.count(".")
        counters = dict(node.metrics.summary())
        if extra and path in extra:
            counters = merge_counter_maps([counters, extra[path]])
        rows = counters.pop("output_rows", None)
        nbytes = counters.pop("output_bytes", None)
        elapsed = counters.pop("elapsed", None)
        own = counters.pop("self_s", None)
        parts = []
        if rows is not None:
            parts.append(f"rows={int(rows)}")
        if nbytes is not None:
            parts.append(f"bytes={int(nbytes)}")
        if elapsed is not None:
            parts.append(f"elapsed={float(elapsed):.6f}s")
        if own is not None:
            parts.append(f"self={float(own):.6f}s")
        parts += [f"{k}={v}" for k, v in sorted(counters.items())]
        line = "  " * d + node.describe()
        if parts:
            line += "  [" + ", ".join(parts) + "]"
        lines.append(line)
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# wire conversion (OperatorMetricP)
# ---------------------------------------------------------------------------


def metrics_to_proto(records: list[dict]):
    from ballista_tpu.proto import pb

    out = []
    for r in records:
        out.append(
            pb.OperatorMetricP(
                path=r["path"],
                operator=r["operator"],
                describe=r.get("describe", ""),
                counters=[
                    pb.KeyValuePair(key=k, value=repr(v))
                    for k, v in sorted(r["counters"].items())
                ],
            )
        )
    return out


def _num(s: str):
    try:
        return int(s)
    except ValueError:
        try:
            return float(s)
        except ValueError:
            return 0


def metrics_from_proto(protos) -> list[dict]:
    return [
        {
            "path": p.path,
            "operator": p.operator,
            "describe": p.describe,
            "counters": {kv.key: _num(kv.value) for kv in p.counters},
        }
        for p in protos
    ]
