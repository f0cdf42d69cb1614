"""The reader of the scheduler's status-call counter (PR 34): the counter
pair gives the calls per completed query, a program without the counter
gives ``None``, and so does a window in which no query completed."""

import pytest

from layers import status_polls_per_query


def obs(before, after, errors=(None, None, "boom")):
    return {
        "queries": [{"error": e, "t0": 5.0 + i, "t1": 6.0 + i}
                    for i, e in enumerate(errors)],
        "counters_before": before, "counters_after": after,
    }


def test_reads_the_window_delta_per_completed_query():
    before = {"status.rpcs": 40, "status.holds": 40}
    after = {"status.rpcs": 47, "status.holds": 46}
    assert status_polls_per_query.read(obs(before, after)) == \
        pytest.approx(3.5)
    # declared at 0 by the program: a window without a call reads 0
    idle = {"status.rpcs": 0}
    assert status_polls_per_query.read(obs(idle, idle)) == 0.0


@pytest.mark.parametrize("counters", [
    {"backend_compiles": 3.0, "poll.rpcs": 12}, None])
def test_a_program_without_the_counter_gives_none(counters):
    assert status_polls_per_query.read(obs(counters, counters)) is None


@pytest.mark.parametrize("errors", [(), ("boom", "boom")])
def test_a_window_without_completed_queries_gives_none(errors):
    counters = {"status.rpcs": 3}
    assert status_polls_per_query.read(
        obs(counters, counters, errors)) is None
