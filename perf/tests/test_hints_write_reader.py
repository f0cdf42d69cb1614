"""The reader of the hint writer's phase (PR 32), beside
``hints_save_ms_per_query``'s case in ``test_phase_labels.py``: a counter
pair gives the per-query value, a program without the counter gives
``None``."""

import pytest

from layers import hints_save_ms_per_query, hints_write_ms_per_query


def obs(before, after):
    return {
        "queries": [{"error": None, "t0": 5.0, "t1": 6.0},
                    {"error": None, "t0": 6.0, "t1": 7.0},
                    {"error": "boom", "t0": 7.0, "t1": 8.0}],
        "counters_before": before, "counters_after": after,
    }


def test_reads_the_window_delta_per_completed_query():
    before = {"phase.executor.hints_write.seconds": 0.5,
              "phase.task.hints_save.seconds": 0.01}
    after = {"phase.executor.hints_write.seconds": 0.52,
             "phase.task.hints_save.seconds": 0.013}
    assert hints_write_ms_per_query.read(obs(before, after)) == \
        pytest.approx(10.0)
    assert hints_save_ms_per_query.read(obs(before, after)) == \
        pytest.approx(1.5)
    # declared at 0 by the program: a window without a pass reads 0
    idle = {"phase.executor.hints_write.seconds": 0}
    assert hints_write_ms_per_query.read(obs(idle, idle)) == 0.0


@pytest.mark.parametrize("counters", [
    {"backend_compiles": 3.0, "phase.task.hints_save.seconds": 0.2}, None])
def test_a_program_without_the_counter_gives_none(counters):
    assert hints_write_ms_per_query.read(obs(counters, counters)) is None
