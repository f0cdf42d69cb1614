"""The pull loop waits for events, not for 0.1 s (docs/serving.md, PR 30).

Executor end: a finished task's status wakes ``PollLoop``'s wait between
two polls. Scheduler end: ``PollWork`` holds the poll of an executor that
reports no running task until a task is grantable for it. Every case here
runs with ``POLL_INTERVAL`` and the hold's bound patched to ``BOUND``
seconds, so that whatever arrives ``WELL_INSIDE`` it arrived by an event and
not by a timer; no case measures a speed.
"""

import tempfile
import threading
import time

import grpc
import pyarrow as pa
import pytest

from ballista_tpu.compilecache import metrics
from ballista_tpu.config import BallistaConfig
from ballista_tpu.proto import pb

BOUND = 20.0
WELL_INSIDE = 8.0

GROUP_BY = "select k, sum(v) as s from t group by k"  # two stages
FILTER = "select v from t where k = 3"  # one stage, one task: the bypass


@pytest.fixture
def bounds(monkeypatch):
    from ballista_tpu.executor import executor as executor_mod
    from ballista_tpu.scheduler import server as server_mod

    monkeypatch.setattr(executor_mod, "POLL_INTERVAL", BOUND)
    monkeypatch.setattr(server_mod, "POLL_HOLD_S", BOUND)


def _counters() -> dict:
    return metrics.snapshot()


def _moved(before: dict, key: str) -> float:
    return _counters().get(key, 0) - before.get(key, 0)


# ---------------------------------------------------------------------------
# executor end: PollLoop against a fake scheduler stub
# ---------------------------------------------------------------------------


class FakeStub:
    """A scheduler that grants ``grants`` on the first poll and never
    holds; records every request with the time it came."""

    def __init__(self, grants=(), fail_first_with_status=False):
        self.calls: list[tuple[float, pb.PollWorkParams]] = []
        self._grants = list(grants)
        self._fail = fail_first_with_status

    def PollWork(self, request):
        self.calls.append((time.monotonic(), request))
        if self._fail and request.task_status:
            self._fail = False
            raise grpc.RpcError("injected")
        result = pb.PollWorkResult()
        if self._grants:
            result.tasks.extend(self._grants)
            result.task.CopyFrom(self._grants[0])
            self._grants = []
        return result

    def statuses(self):
        return [(t, st) for t, req in self.calls for st in req.task_status]


def _task(partition=0):
    return pb.TaskDefinition(
        task_id=pb.PartitionId(
            job_id="job", stage_id=1, partition_id=partition
        ),
        plan=b"", session_id="s",
    )


def _loop(executor_id="ex", task_slots=2, run=lambda task: [],
          scheduler_addr="127.0.0.1:1"):
    from ballista_tpu.executor.executor import Executor, PollLoop

    wd = tempfile.mkdtemp(prefix="poll-blocking-")
    executor = Executor(executor_id=executor_id, work_dir=wd)
    executor.execute_shuffle_write = run
    return PollLoop(executor, scheduler_addr, "127.0.0.1", 0,
                    task_slots=task_slots)


def _run_poll(loop, stub):
    t = threading.Thread(target=loop._poll, args=(stub,), daemon=True)
    t.start()
    return t


def _stop_poll(loop, thread):
    loop._stop.set()
    loop._wake.set()
    thread.join(timeout=WELL_INSIDE)
    assert not thread.is_alive()


def _until(cond, what, timeout=WELL_INSIDE):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(f"{what}: not within {timeout} s")


def test_finished_task_status_leaves_well_inside_the_bound(bounds):
    """(a) The loop is waiting out POLL_INTERVAL (20 s here) after a poll
    that granted nothing; the running task's end wakes it, and the next
    PollWork carries the status."""

    def run(task):
        time.sleep(0.3)  # the empty second poll comes first
        return []

    before = _counters()
    loop, stub = _loop(run=run), FakeStub(grants=[_task()])
    started = time.monotonic()
    thread = _run_poll(loop, stub)
    try:
        _until(stub.statuses, "the finished task's status")
        at, status = stub.statuses()[0]
        assert at - started < WELL_INSIDE
        assert status.WhichOneof("status") == "completed"
        assert _moved(before, "poll.wakes_by_status") >= 1
        assert _moved(before, "phase.executor.status_wait.count") == 1
        assert 0 <= _moved(before, "phase.executor.status_wait.seconds") < 1
        assert _moved(before, "poll.rpcs") == len(stub.calls)
    finally:
        _stop_poll(loop, thread)


def test_a_poll_with_every_slot_free_carries_every_status(bounds):
    """What lets the scheduler hold such a poll: a task queues its status
    BEFORE it frees its slot, and the loop counts the slots BEFORE it
    drains the statuses."""
    loop = _loop(task_slots=2)
    stub = FakeStub(grants=[_task(0), _task(1)])
    thread = _run_poll(loop, stub)
    try:
        _until(lambda: len(stub.statuses()) == 2, "both statuses")
    finally:
        _stop_poll(loop, thread)
    done = 0
    for _, req in stub.calls:
        done += len(req.task_status)
        if req.free_slots == 2:
            # all free: nothing is running, so nothing is still to come
            assert done in (0, 2), [
                (r.free_slots, len(r.task_status)) for _, r in stub.calls]


def test_a_scheduler_that_never_holds_is_polled_at_the_interval(monkeypatch):
    """The reverse compat case: against a pre-change scheduler (every poll
    answered at once) the loop waits out POLL_INTERVAL between polls as it
    always did; it does not spin."""
    from ballista_tpu.executor import executor as executor_mod

    monkeypatch.setattr(executor_mod, "POLL_INTERVAL", 0.05)
    loop, stub = _loop(), FakeStub()
    started = time.monotonic()
    thread = _run_poll(loop, stub)
    time.sleep(0.5)
    _stop_poll(loop, thread)
    took = time.monotonic() - started
    assert 2 <= len(stub.calls) <= took / 0.05 + 2, (len(stub.calls), took)


def test_statuses_of_a_failed_poll_are_sent_again_exactly_once(bounds):
    loop = _loop()
    stub = FakeStub(grants=[_task()], fail_first_with_status=True)
    thread = _run_poll(loop, stub)
    try:
        _until(lambda: len(stub.statuses()) == 2, "the status sent again")
        time.sleep(0.2)
    finally:
        _stop_poll(loop, thread)
    sent = stub.statuses()
    assert len(sent) == 2  # the poll that failed, then the one that did not
    assert sent[0][1] == sent[1][1]
    assert loop._statuses.empty()


def test_heartbeat_blackout_still_silences_the_executor(bounds):
    """(f) The injected blackout skips PollWork itself, the held kind
    too: the scheduler's expiry sweep sees the executor die."""
    from ballista_tpu.testing import faults

    faults.install([{"point": "heartbeat_blackout", "executor": "ex-dark*"}])
    try:
        loop, stub = _loop(executor_id="ex-dark-1"), FakeStub()
        thread = _run_poll(loop, stub)
        time.sleep(0.3)
        _stop_poll(loop, thread)
        assert stub.calls == []
    finally:
        faults.install(None)


# ---------------------------------------------------------------------------
# scheduler end: the servicer, called as gRPC would
# ---------------------------------------------------------------------------


def _scheduler(partitions="2", **settings):
    from ballista_tpu.exec.context import TpuContext
    from ballista_tpu.scheduler.server import SchedulerServer

    ctx = TpuContext()
    ctx.register_table("t", pa.table({
        "k": [i % 7 for i in range(2000)],
        "v": [float(i) for i in range(2000)],
    }))
    cfg = BallistaConfig().with_setting(
        "ballista.shuffle.partitions", partitions
    )
    for key, value in settings.items():
        cfg = cfg.with_setting(key, value)
    return ctx, SchedulerServer(provider=ctx, config=cfg)


def _request(executor_id="e1", free_slots=4, task_slots=4, statuses=(),
             can_accept=True):
    return pb.PollWorkParams(
        metadata=pb.ExecutorMetadata(
            id=executor_id, host="localhost", port=1, grpc_port=2,
            specification=pb.ExecutorSpecification(
                task_slots=task_slots, n_devices=1
            ),
        ),
        can_accept_task=can_accept,
        free_slots=free_slots,
        task_status=list(statuses),
        metrics=[pb.KeyValuePair(key="traces", value="7")],
    )


class Poll:
    """One PollWork on a thread of its own, as the gRPC pool runs it."""

    def __init__(self, sched, request):
        from ballista_tpu.scheduler.server import SchedulerGrpcServicer

        self.result = None
        self.seconds = None

        def call():
            t0 = time.monotonic()
            self.result = SchedulerGrpcServicer(sched).PollWork(request, None)
            self.seconds = time.monotonic() - t0

        self._thread = threading.Thread(target=call, daemon=True)
        self._thread.start()

    def done(self, timeout=WELL_INSIDE):
        self._thread.join(timeout=timeout)
        assert not self._thread.is_alive(), "the poll is still held"
        return self.result


def _held(sched, n=1):
    _until(lambda: sched._held_polls == n, f"{n} held poll(s)")


def _completed(task, executor_id, n_out=2):
    return pb.TaskStatus(
        task_id=task.task_id,
        completed=pb.CompletedTask(
            executor_id=executor_id,
            partitions=[
                pb.ShuffleWritePartition(
                    partition_id=p, path=f"/nowhere/{p}", num_batches=1,
                    num_rows=1, num_bytes=8,
                )
                for p in range(n_out)
            ],
        ),
    )


@pytest.mark.parametrize("sql,partitions", [(GROUP_BY, "2"), (FILTER, "1")],
                         ids=["stage_task", "bypass_task"])
def test_held_poll_returns_the_task_of_a_job_submitted_meanwhile(
    bounds, sql, partitions
):
    """(b) An idle executor's poll is held; a job is submitted; the event
    loop plans it and wakes the poll, which returns its first tasks."""
    ctx, sched = _scheduler(partitions)
    try:
        before = _counters()
        poll = Poll(sched, _request())
        _held(sched)
        job_id = sched.submit_logical(ctx.sql_to_logical(sql), "s")
        result = poll.done()
        assert result.tasks
        assert {t.task_id.job_id for t in result.tasks} == {job_id}
        assert result.task == result.tasks[0]
        assert sched._get_job(job_id).bypass == (sql is FILTER)
        assert _moved(before, "poll.holds") == 1
        assert _moved(before, "poll.holds_granted") == 1
        assert _moved(before, "poll.holds_timed_out") == 0
        assert _moved(before, "phase.scheduler.grant_wait.count") == len(
            result.tasks)
        assert 0 <= _moved(before, "phase.scheduler.grant_wait.seconds") < 1
        assert sched._held_polls == 0
    finally:
        sched.shutdown()


def test_held_poll_returns_the_task_of_a_stage_promoted_meanwhile(bounds):
    """(b) The poll that brings a stage's last status finds nothing to
    grant (the promotion is the event loop's) and, with every slot free,
    is held: the statuses were applied BEFORE the hold, so the promotion
    they set off ends it. The shape of a pre-change executor's poll too."""
    ctx, sched = _scheduler(**{"ballista.tpu.eager_shuffle": "false"})
    try:
        job_id = sched.submit_logical(ctx.sql_to_logical(GROUP_BY), "s")
        first = Poll(sched, _request()).done()
        assert [t.task_id.stage_id for t in first.tasks] == [1, 1]
        before = _counters()
        poll = Poll(sched, _request(
            statuses=[_completed(t, "e1") for t in first.tasks]))
        result = poll.done()
        assert {t.task_id.stage_id for t in result.tasks} == {2}
        assert {t.task_id.job_id for t in result.tasks} == {job_id}
        assert _moved(before, "poll.holds") <= 1  # 0: promoted before the look
        assert _moved(before, "poll.holds_granted") == _moved(
            before, "poll.holds")
    finally:
        sched.shutdown()


def test_hold_ends_empty_at_its_bound(monkeypatch):
    """(c)"""
    from ballista_tpu.scheduler import server as server_mod

    monkeypatch.setattr(server_mod, "POLL_HOLD_S", 0.3)
    _, sched = _scheduler()
    try:
        before = _counters()
        poll = Poll(sched, _request())
        result = poll.done()
        assert not result.tasks and not result.HasField("task")
        assert poll.seconds >= 0.3
        assert _moved(before, "poll.holds") == 1
        assert _moved(before, "poll.holds_timed_out") == 1
        assert _moved(before, "poll.holds_granted") == 0
        assert sched._held_polls == 0
    finally:
        sched.shutdown()


@pytest.mark.parametrize(
    "request_",
    [dict(free_slots=3), dict(free_slots=0), dict(free_slots=0,
                                                  can_accept=False)],
    ids=["a_task_is_running", "pre_batching_executor", "no_free_slot"],
)
def test_a_poll_that_may_bring_a_status_is_not_held(bounds, request_):
    """(d) Only a poll that reports every slot free is held: with a task
    running, the status it ends with must find the loop free to send it."""
    _, sched = _scheduler()
    try:
        before = _counters()
        poll = Poll(sched, _request(**request_))
        assert not poll.done().tasks
        assert poll.seconds < WELL_INSIDE
        assert _moved(before, "poll.holds") == 0
    finally:
        sched.shutdown()


def test_scheduler_stop_releases_a_held_poll(bounds):
    """(e)"""
    _, sched = _scheduler()
    poll = Poll(sched, _request())
    _held(sched)
    sched.shutdown()
    assert not poll.done().tasks
    assert sched._held_polls == 0
    # a poll that comes during the stop is not held either
    assert not Poll(sched, _request()).done().tasks


def test_executor_stop_releases_its_held_poll_and_leaks_no_thread(bounds):
    """(e) Over real gRPC: stop() closes the channel under the held
    PollWork; the loop's thread ends, and the scheduler's handler sees the
    RPC end and gives its worker back."""
    from ballista_tpu.scheduler.server import start_scheduler_grpc

    _, sched = _scheduler()
    gs, port = start_scheduler_grpc(sched, "127.0.0.1", 0)
    try:
        loop = _loop(executor_id="ex-stop",
                     scheduler_addr=f"127.0.0.1:{port}")
        loop.start()
        _held(sched)
        assert "ex-stop" in sched.executor_manager.get_alive_executors(60)
        loop.stop()
        assert not loop._thread.is_alive()
        _until(lambda: sched._held_polls == 0, "the handler to let go")
        assert not [t for t in threading.enumerate()
                    if t.name == "executor-poll-loop" and t.is_alive()]
    finally:
        sched.shutdown()
        ev = gs.stop(grace=None)
        if ev is not None:
            ev.wait(timeout=5)


def test_heartbeat_and_metrics_are_saved_before_the_hold(bounds):
    """(f) A held poll has already done what a poll is also for."""
    _, sched = _scheduler()
    try:
        poll = Poll(sched, _request(executor_id="e-beat"))
        _held(sched)
        em = sched.executor_manager
        assert "e-beat" in em.get_alive_executors(60)
        assert em.get_executor_data("e-beat") is not None
        assert em.get_executor_metrics("e-beat") == {"traces": 7.0}
    finally:
        sched.shutdown()
        poll.done()


def test_an_executor_silent_after_a_held_poll_expires(monkeypatch):
    """(f) The hold does not keep an executor alive: its heartbeat is the
    poll's arrival, and the sweep counts from there."""
    from ballista_tpu.scheduler import server as server_mod

    monkeypatch.setattr(server_mod, "POLL_HOLD_S", 0.2)
    _, sched = _scheduler()
    try:
        sched.executor_timeout_s = 0.3
        Poll(sched, _request(executor_id="e-gone")).done()
        time.sleep(0.2)
        assert sched.check_expired_executors() == ["e-gone"]
    finally:
        sched.shutdown()


def test_two_held_executors_each_get_their_grant(bounds):
    """(g) Every waiter is woken and next_tasks decides: with one task a
    poll (task_grant_batch 1), two jobs go to the two held polls."""
    ctx, sched = _scheduler("1", **{"ballista.tpu.task_grant_batch": "1"})
    try:
        polls = [Poll(sched, _request(executor_id=e)) for e in ("e1", "e2")]
        _held(sched, 2)
        jobs = {sched.submit_logical(ctx.sql_to_logical(FILTER), "s")
                for _ in range(2)}
        got = [p.done().tasks for p in polls]
        assert [len(g) for g in got] == [1, 1]
        assert {g[0].task_id.job_id for g in got} == jobs
    finally:
        sched.shutdown()


def test_held_polls_are_bounded_below_the_grpc_pool(bounds, monkeypatch):
    """A held poll occupies a worker of the scheduler's gRPC pool: past
    the bound (the one budget of held calls, test_status_hold.py) a poll
    is answered at once, as before the change."""
    from ballista_tpu.scheduler import server as server_mod

    assert 0 < server_mod.MAX_HELD_CALLS < server_mod.GRPC_WORKERS
    monkeypatch.setattr(server_mod, "MAX_HELD_CALLS", 1)
    _, sched = _scheduler()
    try:
        first = Poll(sched, _request(executor_id="e1"))
        _held(sched)
        before = _counters()
        second = Poll(sched, _request(executor_id="e2"))
        assert not second.done().tasks
        assert _moved(before, "poll.holds") == 0
        assert sched._held_polls == 1
    finally:
        sched.shutdown()
        first.done()


def test_a_cancelled_poll_is_not_granted_into(bounds):
    """The RPC ended under the hold (its executor stopped): the handler
    must not pick tasks for it, or they would sit RUNNING until the
    executor expires."""
    ctx, sched = _scheduler("1")
    try:
        alive = [True]
        got = []
        t = threading.Thread(
            target=lambda: got.append(sched.next_tasks_held(
                "e1", 4, True, lambda: alive[0])),
            daemon=True,
        )
        t.start()
        _held(sched)
        alive[0] = False
        sched.notify_grantable()  # what context.add_callback does
        t.join(timeout=WELL_INSIDE)
        assert got == [[]]
        job_id = sched.submit_logical(ctx.sql_to_logical(FILTER), "s")
        sched.event_loop.drain()
        assert [x.task_id.job_id for x in sched.next_tasks("e2", 4)] == [
            job_id]
    finally:
        sched.shutdown()


def test_grant_wait_counts_the_hand_off_and_not_the_wait_for_a_slot(
    monkeypatch,
):
    """``scheduler.grant_wait`` runs from the later of two instants: the
    task became grantable, the executor told of a free slot. A task that
    sat behind a busy slot did not wait for the hand-off; one that sat
    while its executor polled with a slot free did."""
    from ballista_tpu.scheduler import server as server_mod

    monkeypatch.setattr(server_mod, "POLL_HOLD_S", 0.05)
    ctx, sched = _scheduler("2")
    seconds = "phase.scheduler.grant_wait.seconds"
    try:
        # one slot, two tasks: the second waits 0.3 s for the slot
        sched.submit_logical(ctx.sql_to_logical(GROUP_BY), "s")
        sched.event_loop.drain()
        one = dict(executor_id="e-one", free_slots=1, task_slots=1)
        first = Poll(sched, _request(**one)).done()
        assert len(first.tasks) == 1
        time.sleep(0.3)
        before = _counters()
        second = Poll(sched, _request(
            statuses=[_completed(first.tasks[0], "e-one")], **one)).done()
        assert [t.task_id.stage_id for t in second.tasks] == [1]
        assert _moved(before, "phase.scheduler.grant_wait.count") == 1
        assert _moved(before, seconds) < 0.1
        # a free slot, reported before the task was there: 0.3 s of hand-off
        Poll(sched, _request(executor_id="e-free")).done()
        job_id = sched.submit_logical(ctx.sql_to_logical(GROUP_BY), "s")
        sched.event_loop.drain()
        time.sleep(0.3)
        before = _counters()
        got = Poll(sched, _request(executor_id="e-free")).done()
        assert {t.task_id.job_id for t in got.tasks} == {job_id}
        assert _moved(before, seconds) >= 0.25 * len(got.tasks)
    finally:
        sched.shutdown()


# ---------------------------------------------------------------------------
# both ends: a served query
# ---------------------------------------------------------------------------


def test_multi_stage_query_is_driven_by_events(bounds):
    """(h) Standalone, pull-staged, both bounds at 20 s: every hand-off of
    a three-stage query happens by a wake or a released hold."""
    import numpy as np

    from ballista_tpu.client.context import BallistaContext

    n = 4000
    table = pa.table({
        "a": pa.array(np.arange(n) % 11, type=pa.int64()),
        "b": pa.array(np.arange(n, dtype="float64")),
    })
    ctx = BallistaContext.standalone(concurrent_tasks=4)
    try:
        ctx.register_table("t", table)
        before = _counters()
        for _ in range(2):
            out = ctx.sql(
                "SELECT a, SUM(b) s, COUNT(*) c FROM t GROUP BY a ORDER BY a"
            ).collect()
    finally:
        ctx.close()
    assert out.column("a").to_pylist() == list(range(11))
    want = [float(sum(range(r, n, 11))) for r in range(11)]
    assert out.column("s").to_pylist() == want
    assert sum(out.column("c").to_pylist()) == n
    assert _moved(before, "poll.wakes_by_status") >= 1
    assert _moved(before, "poll.holds_granted") >= 1
    assert _moved(before, "phase.executor.status_wait.count") >= 2
    assert _moved(before, "phase.scheduler.grant_wait.count") >= 2
