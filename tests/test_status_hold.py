"""The client waits for its job's end, not for 0.1 s (docs/serving.md, PR 34).

Scheduler end: ``GetJobStatus`` with a ``wait_ms`` is held until the job is
completed or failed, within ``POLL_HOLD_S`` and the budget of held calls it
shares with ``PollWork``. Client end: ``collect_logical`` sends the wait with
every ask and sleeps only what a call left of ``POLL_INTERVAL``. As in
``test_poll_blocking.py``, whose helpers these cases use, the bounds are
patched to ``BOUND`` seconds wherever an arrival ``WELL_INSIDE`` it has to
have been an event's doing; no case measures a speed.
"""

import concurrent.futures
import sys
import threading
import time

import grpc
import pyarrow as pa
import pytest

from ballista_tpu.compilecache import metrics
from ballista_tpu.proto import pb
from test_poll_blocking import (
    BOUND, FILTER, GROUP_BY, WELL_INSIDE, Poll, _completed, _counters, _held,
    _moved, _request, _scheduler, _until,
)

LONG_MS = 60_000  # a caller in no hurry: the scheduler's bound decides


@pytest.fixture
def bounds(monkeypatch):
    from ballista_tpu.scheduler import server as server_mod

    monkeypatch.setattr(server_mod, "POLL_HOLD_S", BOUND)


class Ask:
    """One GetJobStatus on a thread of its own, as the gRPC pool runs it."""

    def __init__(self, sched, job_id, wait_ms=LONG_MS):
        from ballista_tpu.scheduler.server import SchedulerGrpcServicer

        self.status = None
        self.seconds = None
        params = pb.GetJobStatusParams(job_id=job_id)
        if wait_ms is not None:
            params.wait_ms = wait_ms

        def call():
            t0 = time.monotonic()
            self.status = SchedulerGrpcServicer(sched).GetJobStatus(
                params, None).status
            self.seconds = time.monotonic() - t0

        self._thread = threading.Thread(target=call, daemon=True)
        self._thread.start()

    def done(self, timeout=WELL_INSIDE):
        self._thread.join(timeout=timeout)
        assert not self._thread.is_alive(), "the status call is still held"
        return self.status.WhichOneof("status")


def _asked(sched, n=1):
    _until(lambda: sched._held_status == n, f"{n} held status call(s)")


def _threads_in(function_name):
    """The threads with a frame of that function on their stack."""
    found = []
    for ident, frame in sys._current_frames().items():
        while frame is not None:
            if frame.f_code.co_name == function_name:
                found.append(ident)
                break
            frame = frame.f_back
    return found


def _running_job(ctx, sched, sql=FILTER, executor_id="e1"):
    """A submitted job with its first tasks granted: (job id, tasks)."""
    job_id = sched.submit_logical(ctx.sql_to_logical(sql), "s")
    sched.event_loop.drain()
    granted = Poll(sched, _request(executor_id=executor_id)).done()
    assert granted.tasks and sched._get_job(job_id).status == "running"
    return job_id, list(granted.tasks)


def _report(sched, statuses, executor_id="e1"):
    # a poll with a task still out: answered at once, not held
    return Poll(sched, _request(
        executor_id=executor_id, free_slots=3, statuses=statuses)).done()


# ---------------------------------------------------------------------------
# scheduler end: the servicer, called as gRPC would
# ---------------------------------------------------------------------------


def test_held_status_returns_the_completion_of_a_job_that_ends_meanwhile(
    bounds,
):
    ctx, sched = _scheduler("1")
    try:
        job_id, tasks = _running_job(ctx, sched)
        before = _counters()
        ask = Ask(sched, job_id)
        _asked(sched)
        sent = time.monotonic()
        _report(sched, [_completed(tasks[0], "e1", n_out=1)])
        assert ask.done() == "completed"
        assert time.monotonic() - sent < WELL_INSIDE
        locations = ask.status.completed.partition_location
        assert [l.path for l in locations] == ["/nowhere/0"]
        assert _moved(before, "status.rpcs") == 1
        assert _moved(before, "status.holds") == 1
        assert _moved(before, "status.holds_ended_by_status") == 1
        assert _moved(before, "status.holds_timed_out") == 0
        assert sched._held_status == 0
    finally:
        sched.shutdown()


def test_a_multi_stage_job_is_held_through_running_to_its_end(bounds):
    """Asked while ``queued``: planning makes it ``running`` and wakes
    nobody; the last stage's last status ends the hold."""
    ctx, sched = _scheduler(**{"ballista.tpu.eager_shuffle": "false"})
    try:
        job_id = sched.submit_logical(ctx.sql_to_logical(GROUP_BY), "s")
        ask = Ask(sched, job_id)
        first = Poll(sched, _request()).done()
        assert [t.task_id.stage_id for t in first.tasks] == [1, 1]
        second = Poll(sched, _request(
            statuses=[_completed(t, "e1") for t in first.tasks])).done()
        assert {t.task_id.stage_id for t in second.tasks} == {2}
        assert sched._held_status == 1  # two stages on, and still held
        before = _counters()
        _report(sched, [_completed(t, "e1", n_out=1) for t in second.tasks])
        assert ask.done() == "completed"
        assert len(ask.status.completed.partition_location) == 2
        assert _moved(before, "status.holds_ended_by_status") == 1
    finally:
        sched.shutdown()


@pytest.mark.parametrize("held", [True, False], ids=["held", "over_budget"])
def test_a_caller_that_offers_a_wait_is_told_once_the_end_is_recorded(
    bounds, monkeypatch, held
):
    """The history record follows the status (a state backend's write lies
    between them): a client that reads ``system.queries`` right after its
    answer finds its row. A call that offers no wait reads the status, as
    before."""
    from ballista_tpu.scheduler import server as server_mod

    if not held:
        monkeypatch.setattr(server_mod, "MAX_HELD_CALLS", 0)
    ctx, sched = _scheduler("1")
    recording, go_on = threading.Event(), threading.Event()
    record = sched._job_terminal_history

    def slow_record(job, status):
        recording.set()
        assert go_on.wait(WELL_INSIDE)
        record(job, status)

    monkeypatch.setattr(sched, "_job_terminal_history", slow_record)
    try:
        job_id, tasks = _running_job(ctx, sched)
        ask = Ask(sched, job_id) if held else None
        if held:
            _asked(sched)
        report = Poll(sched, _request(
            free_slots=3, statuses=[_completed(tasks[0], "e1", n_out=1)]))
        assert recording.wait(WELL_INSIDE)
        # the status is set, the record is not written yet
        assert Ask(sched, job_id, wait_ms=None).done() == "completed"
        assert not [r for r in sched.history.jobs()
                    if r["job_id"] == job_id and r["status"] == "completed"]
        if held:
            assert sched._held_status == 1
        else:
            assert Ask(sched, job_id).done() == "running"
        go_on.set()
        report.done()
        if held:
            assert ask.done() == "completed"
        else:
            assert Ask(sched, job_id).done() == "completed"
        assert [r["status"] for r in sched.history.jobs()
                if r["job_id"] == job_id] == ["completed"]
        assert sched._ending == set()
    finally:
        go_on.set()
        sched.shutdown()


def test_a_failed_job_ends_the_hold_with_its_error(bounds):
    ctx, sched = _scheduler("1")
    try:
        job_id, tasks = _running_job(ctx, sched)
        before = _counters()
        ask = Ask(sched, job_id)
        _asked(sched)
        _report(sched, [pb.TaskStatus(
            task_id=tasks[0].task_id,
            failed=pb.FailedTask(error="PlanError: no such column"),
        )])
        assert ask.done() == "failed"
        assert "PlanError: no such column" in ask.status.failed.error
        assert _moved(before, "status.holds_ended_by_status") == 1
    finally:
        sched.shutdown()


@pytest.mark.parametrize("job", ["unknown", "completed", "failed"])
def test_a_job_that_is_not_going_to_change_is_answered_at_once(bounds, job):
    """Unknown (a scheduler restarted without state), or ended before the
    ask, as a job recovered completed is: nothing to wait for."""
    ctx, sched = _scheduler("1")
    try:
        job_id = "nosuchj"
        if job != "unknown":
            job_id, tasks = _running_job(ctx, sched)
            _report(sched, [
                _completed(tasks[0], "e1", n_out=1) if job == "completed"
                else pb.TaskStatus(
                    task_id=tasks[0].task_id,
                    failed=pb.FailedTask(error="PlanError: boom")),
            ])
        before = _counters()
        ask = Ask(sched, job_id)
        assert ask.done() == ("failed" if job == "unknown" else job)
        assert ask.seconds < WELL_INSIDE
        if job == "unknown":
            assert ask.status.failed.error == "unknown job"
        assert _moved(before, "status.rpcs") == 1
        assert _moved(before, "status.holds") == 0
    finally:
        sched.shutdown()


@pytest.mark.parametrize("wait_ms", [None, 0], ids=["absent", "zero"])
def test_a_request_without_the_wait_is_answered_at_once(bounds, wait_ms):
    """The reference's clients and the REST surface: as before the change,
    byte for byte what ``job_status_proto`` says."""
    ctx, sched = _scheduler("1")
    try:
        job_id, _ = _running_job(ctx, sched)
        before = _counters()
        ask = Ask(sched, job_id, wait_ms=wait_ms)
        assert ask.done() == "running"
        assert ask.seconds < WELL_INSIDE
        assert ask.status == sched.job_status_proto(job_id)
        assert _moved(before, "status.rpcs") == 1
        assert _moved(before, "status.holds") == 0
        # and on the wire: a request serialized without the field is one
        old = pb.GetJobStatusParams.FromString(
            pb.GetJobStatusParams(job_id=job_id).SerializeToString())
        assert old.wait_ms == 0
    finally:
        sched.shutdown()


@pytest.mark.parametrize(
    "hold_s,wait_ms", [(0.3, LONG_MS), (BOUND, 300)],
    ids=["the_schedulers_bound", "the_callers_wait"],
)
def test_hold_ends_with_the_job_still_running_at_the_shorter_bound(
    monkeypatch, hold_s, wait_ms
):
    from ballista_tpu.scheduler import server as server_mod

    monkeypatch.setattr(server_mod, "POLL_HOLD_S", hold_s)
    ctx, sched = _scheduler("1")
    try:
        job_id, _ = _running_job(ctx, sched)
        before = _counters()
        ask = Ask(sched, job_id, wait_ms=wait_ms)
        assert ask.done() == "running"
        assert 0.3 <= ask.seconds < WELL_INSIDE
        assert _moved(before, "status.holds") == 1
        assert _moved(before, "status.holds_timed_out") == 1
        assert _moved(before, "status.holds_ended_by_status") == 0
        assert sched._held_status == 0
    finally:
        sched.shutdown()


def test_scheduler_stop_releases_a_held_status(bounds):
    ctx, sched = _scheduler("1")
    job_id, _ = _running_job(ctx, sched)
    before = _counters()
    ask = Ask(sched, job_id)
    _asked(sched)
    sched.shutdown()
    assert ask.done() == "running"
    assert sched._held_status == 0
    assert _moved(before, "status.holds_timed_out") == 0
    # an ask that comes during the stop is not held either
    assert Ask(sched, job_id).done() == "running"
    assert _moved(before, "status.holds") == 1
    assert _moved(before, "status.holds_over_budget") == 0


def test_a_cancelled_rpc_releases_its_hold_and_leaks_no_thread(bounds):
    """Over real gRPC: the client goes away under the held GetJobStatus;
    the handler sees the RPC end and gives its worker back to the pool."""
    from ballista_tpu.scheduler.rpc import SCHEDULER_SERVICE
    from ballista_tpu.scheduler.server import start_scheduler_grpc

    ctx, sched = _scheduler("1")
    gs, port = start_scheduler_grpc(sched, "127.0.0.1", 0)
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    try:
        job_id, _ = _running_job(ctx, sched)
        call = channel.unary_unary(
            f"/{SCHEDULER_SERVICE}/GetJobStatus",
            request_serializer=lambda r: r.SerializeToString(),
            response_deserializer=pb.GetJobStatusResult.FromString,
        )
        future = call.future(
            pb.GetJobStatusParams(job_id=job_id, wait_ms=LONG_MS))
        _asked(sched)
        assert future.cancel()
        _until(lambda: sched._held_status == 0, "the handler to let go")
        _until(lambda: not _threads_in("job_status_held"),
               "the pool's worker to leave the handler")
        assert sched._get_job(job_id).status == "running"
    finally:
        channel.close()
        sched.shutdown()
        ev = gs.stop(grace=None)
        if ev is not None:
            ev.wait(timeout=5)


def test_four_callers_and_an_executor_are_all_held(bounds):
    """The two load cells' shape, within the default budget."""
    from ballista_tpu.scheduler import server as server_mod

    assert 4 + 1 <= server_mod.MAX_HELD_CALLS < server_mod.GRPC_WORKERS
    ctx, sched = _scheduler("1")
    try:
        jobs = [_running_job(ctx, sched, executor_id=f"e{i}")
                for i in range(4)]
        before = _counters()
        asks = [Ask(sched, job_id) for job_id, _ in jobs]
        _asked(sched, 4)
        poll = Poll(sched, _request(executor_id="e-idle"))
        _held(sched)
        assert _moved(before, "status.holds") == 4
        assert _moved(before, "status.holds_over_budget") == 0
        for i, (job_id, tasks) in enumerate(jobs):
            _report(sched, [_completed(tasks[0], f"e{i}", n_out=1)],
                    executor_id=f"e{i}")
        assert [a.done() for a in asks] == ["completed"] * 4
        assert _moved(before, "status.holds_ended_by_status") == 4
    finally:
        sched.shutdown()
        poll.done()


def test_with_the_budget_full_the_other_calls_are_still_served(
    bounds, monkeypatch
):
    """Held polls and held status calls draw on one budget. Past it a
    status call is answered at once and counted, a poll is answered at
    once, and what ends a hold (ExecuteQuery, a PollWork that brings a
    status) is served as ever."""
    from ballista_tpu.scheduler import server as server_mod
    from ballista_tpu.serde import logical_to_proto

    monkeypatch.setattr(server_mod, "MAX_HELD_CALLS", 2)
    ctx, sched = _scheduler("1")
    try:
        job_id, tasks = _running_job(ctx, sched)
        poll = Poll(sched, _request(executor_id="e-idle"))
        _held(sched)
        ask = Ask(sched, job_id)
        _asked(sched)
        before = _counters()
        # one more of each kind: no room
        extra = Ask(sched, job_id)
        assert extra.done() == "running"
        assert extra.seconds < WELL_INSIDE
        assert _moved(before, "status.holds_over_budget") == 1
        assert _moved(before, "status.holds") == 0
        assert not Poll(sched, _request(executor_id="e-idle-2")).done().tasks
        assert _moved(before, "poll.holds") == 0
        assert (sched._held_polls, sched._held_status) == (1, 1)
        # a query is taken, and the held poll gets its task
        servicer = server_mod.SchedulerGrpcServicer(sched)
        plan = logical_to_proto(ctx.sql_to_logical(FILTER))
        second = servicer.ExecuteQuery(pb.ExecuteQueryParams(
            logical_plan=plan.SerializeToString(), session_id="s"), None)
        assert {t.task_id.job_id for t in poll.done().tasks} == {
            second.job_id}
        # and the status that ends the held call is taken
        _report(sched, [_completed(tasks[0], "e1", n_out=1)])
        assert ask.done() == "completed"
    finally:
        sched.shutdown()


def test_a_held_poll_makes_room_for_a_status_call_when_it_ends(
    monkeypatch,
):
    from ballista_tpu.scheduler import server as server_mod

    monkeypatch.setattr(server_mod, "MAX_HELD_CALLS", 1)
    monkeypatch.setattr(server_mod, "POLL_HOLD_S", 0.3)
    ctx, sched = _scheduler("1")
    try:
        job_id, _ = _running_job(ctx, sched)
        poll = Poll(sched, _request(executor_id="e-idle"))
        _held(sched)
        before = _counters()
        assert Ask(sched, job_id).done() == "running"
        assert _moved(before, "status.holds_over_budget") == 1
        poll.done()
        assert Ask(sched, job_id).done() == "running"
        assert _moved(before, "status.holds") == 1
    finally:
        sched.shutdown()


def test_many_callers_at_once_leave_the_budget_where_it_was(bounds):
    """More callers than cores and than the budget, under a short switch
    interval: every held call is told of the end, every other is answered
    at once, and the count of held calls comes back to 0 (a lost update
    would leave it off, and the budget with it)."""
    from ballista_tpu.scheduler import server as server_mod

    callers = 3 * server_mod.MAX_HELD_CALLS
    ctx, sched = _scheduler("1")
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        job_id, tasks = _running_job(ctx, sched)
        before = _counters()
        asks = [Ask(sched, job_id) for _ in range(callers)]
        _until(lambda: _moved(before, "status.rpcs") == callers
               and sched._held_status == server_mod.MAX_HELD_CALLS,
               "every caller to have asked")
        _report(sched, [_completed(tasks[0], "e1", n_out=1)])
        answers = [a.done() for a in asks]
    finally:
        sys.setswitchinterval(interval)
        sched.shutdown()
    held = server_mod.MAX_HELD_CALLS
    assert sorted(answers) == ["completed"] * held + ["running"] * (
        callers - held)
    assert sched._held_status == 0
    assert _moved(before, "status.holds") == held
    assert _moved(before, "status.holds_ended_by_status") == held
    assert _moved(before, "status.holds_over_budget") == callers - held


def test_the_counters_are_declared_at_zero():
    """A reader tells "no call yet" from "a program without the counter"
    (perf/layers/status_polls_per_query.py)."""
    assert set(metrics.STATUS_COUNTERS) == {
        "status.rpcs", "status.holds", "status.holds_ended_by_status",
        "status.holds_timed_out", "status.holds_over_budget",
    }
    assert set(metrics.STATUS_COUNTERS) <= set(metrics.snapshot())


def test_every_change_of_a_jobs_status_goes_through_the_setter():
    """One place sets ``job.status``, so one place wakes the held calls."""
    import inspect
    import re

    from ballista_tpu.scheduler import server as server_mod

    source = inspect.getsource(server_mod)
    assert len(re.findall(r"\b(job|j)\.status = ", source)) == 1
    assert "job.status = status" in inspect.getsource(
        server_mod.SchedulerServer._set_job_status)


# ---------------------------------------------------------------------------
# client end: collect_logical against a fake scheduler over real gRPC
# ---------------------------------------------------------------------------


class FakeScheduler:
    """Takes any query as job ``fake``; answers ``running`` to the first
    ``running`` asks, each after ``hold_s`` seconds (0: a scheduler that
    does not hold), then ``failed: boom``. Records the wait each ask
    offered."""

    def __init__(self, running, hold_s=0.0):
        self.asks: list[int] = []
        self._running = running
        self._hold_s = hold_s
        self._stop = threading.Event()

    def ExecuteQuery(self, request, context):
        return pb.ExecuteQueryResult(job_id="fake", session_id="s")

    def GetJobStatus(self, request, context):
        if self._hold_s:
            self._stop.wait(self._hold_s)
        self.asks.append(request.wait_ms)
        if len(self.asks) <= self._running:
            status = pb.JobStatus(running=pb.RunningJob())
        else:
            status = pb.JobStatus(failed=pb.FailedJob(error="boom"))
        return pb.GetJobStatusResult(status=status)

    def __getattr__(self, name):
        def unimplemented(request, context):
            context.abort(grpc.StatusCode.UNIMPLEMENTED, name)

        return unimplemented


@pytest.fixture
def fake_scheduler():
    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.scheduler.rpc import (
        SCHEDULER_METHODS, SCHEDULER_SERVICE, add_service,
    )

    started = []

    def start(fake):
        gs = grpc.server(concurrent.futures.ThreadPoolExecutor(max_workers=2))
        add_service(gs, SCHEDULER_SERVICE, SCHEDULER_METHODS, fake)
        port = gs.add_insecure_port("127.0.0.1:0")
        gs.start()
        ctx = BallistaContext.remote("127.0.0.1", port)
        ctx.register_table("t", pa.table({"k": [1, 3], "v": [1.0, 2.0]}))
        started.append((gs, ctx, fake))
        return ctx

    yield start
    for gs, ctx, fake in started:
        fake._stop.set()
        ctx.close()
        gs.stop(grace=None).wait(timeout=5)


@pytest.fixture
def sleeps(monkeypatch):
    """What ``collect_logical`` sleeps between two asks, with each sleep
    cut short: the test is about how long it meant to."""
    from ballista_tpu.client import context as context_mod

    asked_for = []

    class Clock:
        monotonic = staticmethod(time.monotonic)
        time = staticmethod(time.time)

        @staticmethod
        def sleep(seconds):
            asked_for.append(seconds)
            time.sleep(min(seconds, 0.01))

    monkeypatch.setattr(context_mod, "time", Clock)
    return asked_for


def test_client_sends_the_wait_and_acts_on_the_end_at_once(
    fake_scheduler, sleeps
):
    from ballista_tpu.client import context as context_mod
    from ballista_tpu.errors import BallistaError

    fake = FakeScheduler(running=0)
    ctx = fake_scheduler(fake)
    with pytest.raises(BallistaError, match="job fake failed: boom"):
        ctx.sql(FILTER).collect()
    assert fake.asks == [context_mod.STATUS_WAIT_MS]
    assert context_mod.STATUS_WAIT_MS > 0
    assert sleeps == []


def test_after_a_hold_that_ran_out_the_client_asks_again_without_sleeping(
    fake_scheduler, sleeps, monkeypatch
):
    from ballista_tpu.client import context as context_mod
    from ballista_tpu.errors import BallistaError

    monkeypatch.setattr(context_mod, "POLL_INTERVAL", 0.05)
    fake = FakeScheduler(running=3, hold_s=0.1)  # held past the interval
    ctx = fake_scheduler(fake)
    with pytest.raises(BallistaError, match="boom"):
        ctx.sql(FILTER).collect()
    assert len(fake.asks) == 4
    assert sleeps == []


def test_a_scheduler_that_never_holds_is_asked_at_the_interval(
    fake_scheduler, sleeps, monkeypatch
):
    """The fallback: a scheduler from before the change, or one over its
    budget, answers at once, and the client waits out what the call left
    of POLL_INTERVAL before each further ask. It does not spin."""
    from ballista_tpu.client import context as context_mod
    from ballista_tpu.errors import BallistaError

    monkeypatch.setattr(context_mod, "POLL_INTERVAL", BOUND)
    fake = FakeScheduler(running=3)
    ctx = fake_scheduler(fake)
    with pytest.raises(BallistaError, match="boom"):
        ctx.sql(FILTER).collect()
    assert len(fake.asks) == 4
    assert len(sleeps) == 3  # one after each answer that was no end
    assert all(BOUND - WELL_INSIDE < s <= BOUND for s in sleeps), sleeps


def test_the_interval_is_counted_from_when_the_ask_was_sent(
    fake_scheduler, sleeps, monkeypatch
):
    from ballista_tpu.client import context as context_mod
    from ballista_tpu.errors import BallistaError

    monkeypatch.setattr(context_mod, "POLL_INTERVAL", BOUND)
    fake = FakeScheduler(running=1, hold_s=0.5)
    ctx = fake_scheduler(fake)
    with pytest.raises(BallistaError, match="boom"):
        ctx.sql(FILTER).collect()
    assert len(sleeps) == 1 and sleeps[0] <= BOUND - 0.5


# ---------------------------------------------------------------------------
# both ends: a served query
# ---------------------------------------------------------------------------


def test_a_standalone_querys_completion_ends_its_hold(monkeypatch):
    """Client's interval and scheduler's bound both at 20 s: the answer
    came by the job's end waking the held call."""
    from ballista_tpu.client import context as context_mod
    from ballista_tpu.client.context import BallistaContext
    from ballista_tpu.scheduler import server as server_mod

    monkeypatch.setattr(context_mod, "POLL_INTERVAL", BOUND)
    monkeypatch.setattr(context_mod, "STATUS_WAIT_MS", int(BOUND * 1e3))
    monkeypatch.setattr(server_mod, "POLL_HOLD_S", BOUND)
    ctx = BallistaContext.standalone(concurrent_tasks=2)
    try:
        ctx.register_table("t", pa.table({
            "k": [i % 7 for i in range(2000)],
            "v": [float(i) for i in range(2000)],
        }))
        before = _counters()
        started = time.monotonic()
        out = ctx.sql(GROUP_BY).collect()
        took = time.monotonic() - started
    finally:
        ctx.close()
    assert sorted(out.column("k").to_pylist()) == list(range(7))
    assert took < BOUND
    assert _moved(before, "status.rpcs") == 1
    assert _moved(before, "status.holds_ended_by_status") == 1
    assert _moved(before, "status.holds_timed_out") == 0
