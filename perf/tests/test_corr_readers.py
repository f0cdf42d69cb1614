"""The readers of the metrics that the cell ``tpch-sf1-corr-mem.correlated``
brought: a number where the program's ``subquery.*`` counters are there, 0
where they are declared and did not move (every other cell), ``None`` where
the program has no such counter (a parent commit), so that the metric is
left out of the line."""

import pytest

from layers import (
    subquery_agg_groups_per_query,
    subquery_agg_rows_per_query,
    subquery_agg_self_ms_per_query,
)

QUERIES = [{"error": None, "template": t, "t0": 10.0 + i, "t1": 11.0 + i}
           for i, t in enumerate(["q17", "q20", "q20", "q17"])]
FAILED = {"error": "Boom", "template": "q17", "t0": 19.0, "t1": 19.5}
JOBS = [{"status": "completed", "session_id": "s", "submitted_s": 10.0 + i}
        for i in range(4)]
COUNTERS = {
    subquery_agg_rows_per_query: "subquery.agg_rows",
    subquery_agg_groups_per_query: "subquery.agg_groups",
}


def obs(before, after, queries=QUERIES + [FAILED]):
    return {"queries": queries, "counters_before": before,
            "counters_after": after, "jobs": JOBS, "session_id": "s",
            "window_t0": 10.0, "window_t1": 20.0}


@pytest.mark.parametrize("reader", COUNTERS, ids=lambda m: m.__name__)
def test_a_counter_per_completed_query(reader):
    key = COUNTERS[reader]
    # four queries completed, the failed one does not count
    assert reader.read(obs({key: 100}, {key: 900})) == pytest.approx(200.0)
    # declared at 0 by the program and unmoved: a cell without such a query
    assert reader.read(obs({key: 0}, {key: 0})) == 0.0
    # a parent's program has no such counter: the metric is left out
    old = {"agg.sort_passes": 4, "holistic.tasks": 0}
    assert reader.read(obs(old, old)) is None
    assert reader.read(obs(None, None)) is None
    assert reader.read(obs({key: 0}, {key: 5}, [FAILED])) is None


def test_the_own_time_in_milliseconds_per_window_job():
    key = "subquery.agg_self_seconds"
    read = subquery_agg_self_ms_per_query.read
    assert read(obs({key: 1.0}, {key: 1.5})) == pytest.approx(125.0)
    assert read(obs({key: 2.0}, {key: 2.0})) == 0.0
    old = {"op.aggregate.self_seconds": 1.0}
    assert read(obs(old, old)) is None
    # the history does not hold exactly the window's queries: nothing
    assert read(dict(obs({key: 0}, {key: 1}), jobs=JOBS[:3])) is None
