"""Device milliseconds inside XLA sort operations per query completed in
the traced window."""


def read(obs):
    t = obs["trace"]
    if not t or t["sort_s"] is None or not t["queries"]:
        return None
    return 1e3 * t["sort_s"] / len(t["queries"])
