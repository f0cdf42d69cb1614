"""The readers of the metrics that the cell ``tpch-sf1-subq-mem.subquery``
brought (PR 36): a number where the program's counters are there, 0 where
they are declared and did not move (the older cells), ``None`` where the
program has no such counter (a parent commit), so that the metric is left
out of the line."""

import pytest

from layers import (
    dict_predicate_entries_per_query,
    dict_predicate_ms_per_query,
    noninner_join_probe_rows_per_query,
    noninner_join_tasks_per_query,
)

QUERIES = [{"error": None, "template": t, "t0": i, "t1": i + 1.0}
           for i, t in enumerate(["q13", "q4", "q16", "q13"])]
QUERIES.append({"error": "Boom", "template": "q4", "t0": 9.0, "t1": 9.5})
READERS = {
    dict_predicate_entries_per_query: "dict_predicate.entries",
    noninner_join_probe_rows_per_query: "join.noninner.probe_rows",
    noninner_join_tasks_per_query: "join.noninner.tasks",
}


def obs(before, after):
    return {"queries": QUERIES, "counters_before": before,
            "counters_after": after}


@pytest.mark.parametrize("reader", READERS, ids=lambda m: m.__name__)
def test_a_counter_per_completed_query(reader):
    key = READERS[reader]
    # four queries completed, the failed one does not count
    assert reader.read(obs({key: 100}, {key: 900})) == pytest.approx(200.0)
    # declared at 0 by the program and unmoved: a cell without such a query
    assert reader.read(obs({key: 0}, {key: 0})) == 0.0
    assert reader.read(obs({key: 7}, {key: 7})) == 0.0
    # a parent's program has no such counter: the metric is left out
    old = {"agg.sort_passes": 4, "holistic.tasks": 0}
    assert reader.read(obs(old, old)) is None
    assert reader.read(obs(None, None)) is None
    # no query completed: nothing to divide by
    none_done = dict(obs({key: 0}, {key: 5}), queries=QUERIES[-1:])
    assert reader.read(none_done) is None


def test_the_phase_in_milliseconds_per_query():
    key = "phase.task.dict_predicate.seconds"
    read = dict_predicate_ms_per_query.read
    assert read(obs({key: 1.0}, {key: 1.5})) == pytest.approx(125.0)
    # entered in warm-up, not in the window: 0, not nothing
    assert read(obs({key: 2.0}, {key: 2.0})) == 0.0
    # first entered inside the window: the counter is new after it
    assert read(obs({}, {key: 0.2})) == pytest.approx(50.0)
    # never entered, or a program without the phase: left out
    assert read(obs({"phase.task.d2h.seconds": 1.0},
                    {"phase.task.d2h.seconds": 2.0})) is None
