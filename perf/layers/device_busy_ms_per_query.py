"""Device-busy milliseconds per query completed in the traced window: what
the kernels cost a query, whatever the host adds around them."""


def read(obs):
    t = obs["trace"]
    if not t or not t["busy_s"] or not t["queries"]:
        return None
    return 1e3 * t["busy_s"] / len(t["queries"])
