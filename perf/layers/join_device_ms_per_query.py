"""Device milliseconds a query of the traced window spent in the join's
programs (``layers/_join.py``: the probes, probe tables and the build's
finisher), of ``device_busy_ms_per_query``'s whole."""

from layers._join import device_seconds


def read(obs):
    s = device_seconds(obs)
    if s is None:
        return None
    return 1e3 * s / len(obs["trace"]["queries"])
