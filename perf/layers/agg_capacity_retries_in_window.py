"""Tasks the window ran a second time because a grouped aggregate found
more groups than its state had slots (``agg.capacity_retries``: +1 per
``CapacityError`` retry in ``exec/base.py run_with_capacity_retry``). The
executor remembers the capacity a task grew to, so warm-up pays for the
growth and this reads 0, like ``compiles_in_window``; a count, so 0 is a
reading. A program without the counter gives ``None``."""

from layers._phases import delta


def read(obs):
    return delta(obs, "agg.capacity_retries")
