"""The program's host-phase counters over the window
(``ballista_tpu/obs/trace.py phase``: ``phase.<name>.seconds|count|bytes``
in the chip owner's counter store, read before and after the window like the
compile counters), per completed query. A program without the counter, as
every commit before PR 25, gives ``None`` and the metric is left out."""


def delta(obs, key):
    a, b = obs["counters_before"], obs["counters_after"]
    if a is None or b is None or key not in b:
        return None
    return float(b[key]) - float(a.get(key, 0.0))


def per_query(obs, keys, scale=1.0):
    """The window's summed deltas of ``keys`` (those the program has) over
    its completed queries, times ``scale``; ``None`` if it has none of them."""
    done = [r for r in obs["queries"] if r["error"] is None]
    found = [d for d in (delta(obs, k) for k in keys) if d is not None]
    if not found or not done:
        return None
    return scale * sum(found) / len(done)
