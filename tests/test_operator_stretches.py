"""Operator stretches (``obs/trace.py stretch``, ``obs/profile.py
instrument_plan``): a task thread's time belongs to exactly one owner at
every moment, the innermost running operator or an open phase. Each
operator's own time is its ``self_s`` timer and a ``ballista/op.<Operator>``
annotation; the executor sums ``self_s`` into ``op.<family>.self_seconds``
once a task."""

import pathlib
import sys
import threading
import time
import types

import pytest

from ballista_tpu.compilecache import metrics
from ballista_tpu.errors import CapacityError
from ballista_tpu.exec.base import ExecutionPlan
from ballista_tpu.obs import profile as obs_profile
from ballista_tpu.obs import trace as obs_trace

ROOT = pathlib.Path(__file__).resolve().parent.parent
PERF = ROOT / "perf"


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps each thread's
    open ``ballista/`` labels and every label opened, and fails the moment
    a thread would hold two."""

    open: dict = {}
    seen: list = []
    lock = threading.Lock()

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        with self.lock:
            held = self.open.setdefault(threading.get_ident(), [])
            assert not held, f"{self.name} opened inside {held}"
            held.append(self.name)
            self.seen.append(self.name)
        return self

    def __exit__(self, *exc):
        with self.lock:
            assert self.open[threading.get_ident()] == [self.name]
            self.open[threading.get_ident()].pop()
        return False


@pytest.fixture
def recorder(monkeypatch):
    Recorder.open, Recorder.seen = {}, []
    monkeypatch.setattr(obs_trace, "TraceAnnotation", Recorder)
    yield Recorder
    assert all(not held for held in Recorder.open.values())


BATCH = types.SimpleNamespace(valid=None)


class Op(ExecutionPlan):
    """A test operator: ``work`` seconds of its own per batch, then its
    input's batches (or ``n`` of its own), optionally a phase inside its
    step or an error at its second batch."""

    def __init__(self, input=None, n=3, work=0.0, phase=None, raises=None,
                 take=None):
        super().__init__()
        self.input, self.n, self.work = input, n, work
        self.phase, self.raises, self.take = phase, raises, take

    def children(self):
        return [self.input] if self.input is not None else []

    def execute(self, partition, ctx):
        source = (self.input.execute(partition, ctx) if self.input
                  else iter([BATCH] * self.n))
        for i, batch in enumerate(source):
            if self.raises is not None and i == 1:
                raise self.raises
            time.sleep(self.work)
            if self.phase:
                with obs_trace.phase(self.phase, site="test.read"):
                    time.sleep(self.work)
            yield batch
            if self.take is not None and i + 1 == self.take:
                # a LIMIT met: the input is closed before it is exhausted
                source.close()
                return


def named(cls_name, **kw):
    return type(cls_name, (Op,), {})(**kw)


def self_s(node) -> float:
    return node.metrics.timers.get("self_s", 0.0)


def test_a_child_sleeping_in_its_next_adds_nothing_to_its_parent(recorder):
    child = named("SleepyExec", n=3, work=0.05)
    parent = named("ParentExec", input=child)
    obs_profile.instrument_plan(parent)
    assert len(list(parent.execute(0, None))) == 3
    assert self_s(child) >= 0.15
    assert self_s(parent) < 0.02
    assert obs_trace.owners() == []
    assert set(recorder.seen) == {"ballista/op.SleepyExec",
                                  "ballista/op.ParentExec"}


def test_a_phase_suspends_the_operator_and_annotations_never_nest(recorder):
    """The child's d2h phase is neither its own time nor its parent's, and
    no thread ever holds two ``ballista/`` annotations (the recorder fails
    the first that would)."""
    child = named("ReaderExec", n=2, work=0.03, phase="task.d2h")
    parent = named("ParentExec", input=child)
    obs_profile.instrument_plan(parent)
    before = metrics.snapshot().get("phase.task.d2h.seconds", 0.0)
    list(parent.execute(0, None))
    phased = metrics.snapshot()["phase.task.d2h.seconds"] - before
    assert phased >= 0.06
    assert 0.06 <= self_s(child) < 0.06 + 0.03
    assert self_s(parent) < 0.02
    assert "ballista/task.d2h:test.read" in recorder.seen


def test_a_stretch_entered_inside_a_phase_opens_no_annotation(recorder):
    """An operator driven from inside a phase (an iterator closed or
    collected there) owns nothing until the phase ends."""
    m = types.SimpleNamespace(timers={})
    with obs_trace.stretch("ballista/op.OuterExec", m):
        with obs_trace.phase("task.h2d"):
            with obs_trace.stretch("ballista/op.InnerExec", m):
                assert obs_trace.owners() == ["ballista/op.OuterExec",
                                              "ballista/op.InnerExec"]
        assert recorder.open[threading.get_ident()] == [
            "ballista/op.OuterExec"]
    assert "ballista/op.InnerExec" not in recorder.seen
    assert obs_trace.owners() == []


def test_a_capacity_error_two_levels_down_empties_the_stack(recorder):
    leaf = named("LeafExec", n=3, raises=CapacityError("full", required=9))
    root = named("RootExec", input=named("MidExec", input=leaf))
    obs_profile.instrument_plan(root)
    with pytest.raises(CapacityError):
        list(root.execute(0, None))
    assert obs_trace.owners() == []
    # the thread is nobody's: a new stretch starts from an empty stack
    with obs_trace.stretch("ballista/op.NextExec", leaf.metrics):
        assert obs_trace.owners() == ["ballista/op.NextExec"]


def test_a_limit_closing_its_input_early_empties_the_stack(recorder):
    scan = named("ScanExec", n=5)
    limit = named("GlobalLimitExec", input=scan, take=2)
    obs_profile.instrument_plan(limit)
    assert len(list(limit.execute(0, None))) == 2
    assert scan.metrics.counters["output_batches"] == 2
    assert obs_trace.owners() == []


def test_an_abandoned_generator_holds_no_stretch(recorder):
    """No stretch is held across a ``yield``: an iterator dropped after its
    first batch, or closed from another thread, leaves every stack empty."""
    root = named("RootExec", input=named("ScanExec", n=5))
    obs_profile.instrument_plan(root)
    it = root.execute(0, None)
    next(it)
    assert obs_trace.owners() == []
    closer = threading.Thread(target=it.close)
    closer.start()
    closer.join()
    del it
    assert obs_trace.owners() == []


def test_the_shuffle_writer_root_has_a_stretch(recorder, tmp_path):
    import pyarrow as pa

    from ballista_tpu.columnar.arrow_interop import schema_from_arrow
    from ballista_tpu.exec.base import TaskContext
    from ballista_tpu.exec.scan import MemoryScanExec
    from ballista_tpu.executor.shuffle import ShuffleWriterExec

    table = pa.table({"k": list(range(100))})
    scan = MemoryScanExec(table, schema_from_arrow(table.schema))
    writer = ShuffleWriterExec("job", 1, scan, [], 1)
    obs_profile.instrument_plan(writer)
    ctx = TaskContext(work_dir=str(tmp_path))
    metas = writer.execute_shuffle_write(0, ctx)
    assert sum(m.num_rows for m in metas) == 100
    assert obs_trace.owners() == []
    assert self_s(writer) > 0 and self_s(scan) > 0
    assert {"ballista/op.ShuffleWriterExec", "ballista/op.MemoryScanExec",
            "ballista/task.shuffle_write"} <= set(recorder.seen)
    records = obs_profile.operator_metrics(writer)
    assert records[0]["counters"]["self_s"] > 0
    assert "self=" in obs_profile.annotated_display(writer)
    assert "dispatch" not in obs_profile.annotated_display(writer)


def test_every_family_is_declared_at_zero():
    snap = metrics.snapshot()
    assert set(metrics.OP_FAMILIES) == {"scan", "pipeline", "aggregate",
                                        "join", "holistic", "exchange",
                                        "other"}
    for family in metrics.OP_FAMILIES:
        assert f"op.{family}.self_seconds" in snap


@pytest.mark.parametrize("operator,family", [
    ("MemoryScanExec", "scan"), ("ParquetScanExec", "scan"),
    ("FilterExec", "pipeline"), ("CoalescePartitionsExec", "pipeline"),
    ("HashAggregateExec", "aggregate"), ("MeshAggregateExec", "aggregate"),
    ("HashJoinExec", "join"), ("CrossJoinExec", "join"),
    ("SortExec", "holistic"), ("WindowExec", "holistic"),
    ("PercentileExec", "holistic"), ("HashRepartitionExec", "exchange"),
    ("ShuffleReaderExec", "exchange"), ("ShuffleWriterExec", "exchange"),
    ("SomethingNewExec", "other"),
])
def test_operator_classes_fall_in_their_family(operator, family):
    assert metrics.op_counter(operator) == f"op.{family}.self_seconds"


def test_every_listed_class_is_an_operator():
    """A family lists real class names: a renamed operator would fall into
    ``other`` silently."""
    import ballista_tpu.exec as exec_pkg  # noqa: F401
    from ballista_tpu.exec import (aggregate, joins, mesh, percentile,  # noqa
                                   pipeline, repartition, scan, sort, window)
    from ballista_tpu.executor import reader, shuffle  # noqa: F401

    def subclasses(cls):
        for sub in cls.__subclasses__():
            yield sub.__name__
            yield from subclasses(sub)

    known = set(subclasses(ExecutionPlan))
    listed = {c for classes in metrics.OP_FAMILIES.values() for c in classes}
    assert listed - known == set()


# -- served tasks ------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """q1 and q3 at SF 0.01 through ``BallistaContext.standalone`` with one
    task slot and the trace on, each run twice (the second warm): the
    counters' move over the second runs, and each attempt's cost, operator
    records and ``task_attempt`` span."""
    for p in (str(ROOT), str(PERF)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import datagen
    import traffic

    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.config import BallistaConfig

    tables = datagen.gen_all(0.01, 3_800_000_011)
    ctx = BallistaContext.standalone(
        BallistaConfig({"ballista.tpu.trace": "on"}), concurrent_tasks=1)
    try:
        for name in ("customer", "orders", "lineitem"):
            ctx.register_table(name, tables[name])
        moved = {}
        for name, mod in traffic.load_templates(["q1", "q3"]).items():
            sql = mod.SQL.format(**mod.VALIDATION)
            ctx.sql(sql).collect()
            before = metrics.snapshot()
            ctx.sql(sql).collect()
            after = metrics.snapshot()
            moved[name] = {k: after[k] - before.get(k, 0) for k in after}
        attempts = ctx._system_table_rows("system.task_attempts")
        jobs = ctx._standalone_cluster.scheduler.jobs
        records = {(job_id, stage, part): recs
                   for job_id, job in jobs.items()
                   for (stage, part), recs in job.op_metrics.items()}
        spans = {(s.attrs["job_id"], int(s.attrs["stage_id"]),
                  int(s.attrs["partition"])): s
                 for s in obs_trace.snapshot() if s.name == "task_attempt"}
    finally:
        ctx.close()
    return moved, attempts, records, spans


def test_the_executor_adds_family_counters_once_a_task(served):
    moved, attempts, records, _ = served
    for name in ("q1", "q3"):
        own = {k: v for k, v in moved[name].items()
               if k.startswith("op.") and v}
        assert own, name
        assert set(own) <= set(metrics.OP_COUNTERS)
        assert own["op.scan.self_seconds"] > 0
        assert own["op.aggregate.self_seconds"] > 0
        assert own["op.exchange.self_seconds"] > 0
    assert moved["q3"]["op.join.self_seconds"] > 0
    assert moved["q1"]["op.join.self_seconds"] == 0
    # the counters are the records' self_s summed by family, nothing more
    total = sum(r["counters"].get("self_s", 0.0)
                for recs in records.values() for r in recs)
    counted = sum(metrics.snapshot()[k] for k in metrics.OP_COUNTERS)
    assert counted >= total - 1e-3


def test_self_time_and_phases_fit_inside_a_served_task(served):
    """Per attempt: every operator's ``self_s`` plus the phases on its
    ``task_attempt`` span is at most its ``wall_seconds``; what is left is
    the runner's own code, the metrics' resolution and the report."""
    _, attempts, records, spans = served
    checked = 0
    for a in attempts:
        key = (a["job_id"], a["stage_id"], a["partition"])
        if key not in records or key not in spans:
            continue
        own = sum(r["counters"].get("self_s", 0.0) for r in records[key])
        phases = sum(float(v) for k, v in spans[key].attrs.items()
                     if k.startswith("phase."))
        wall = a["cost"]["wall_seconds"]
        assert own > 0, key
        assert own + phases <= wall + 1e-5, (key, own, phases, wall)
        checked += 1
    assert checked >= 8
