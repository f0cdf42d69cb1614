"""95th percentile of the client latency of all queries of the window. Four
closed-loop clients keep one Python process at capacity, and some 120 queries
a window leave six beyond the percentile: it spread by 11 % between runs of
one seed (PR 24), too wide to hold a bound, so it stands here beside
``queries_per_s`` and not among the end-to-end metrics."""


def read(obs):
    lat = sorted(r["t1"] - r["t0"] for r in obs["queries"]
                 if r["error"] is None)
    if len(lat) < 20:
        return None
    at = 0.95 * (len(lat) - 1)
    lo = int(at)
    hi = min(lo + 1, len(lat) - 1)
    return 1e3 * (lat[lo] + (lat[hi] - lat[lo]) * (at - lo))
