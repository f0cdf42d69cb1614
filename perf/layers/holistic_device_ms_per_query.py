"""Device milliseconds a query of the traced window spent in the programs
of the window and percentile operators (``layers/_holistic.py``: their sort
passes, gathers, the ranking and the interpolation), of
``device_busy_ms_per_query``'s whole."""

from layers._holistic import device_seconds


def read(obs):
    s = device_seconds(obs)
    if s is None:
        return None
    return 1e3 * s / len(obs["trace"]["queries"])
