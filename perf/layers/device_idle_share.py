"""Share of the traced window in which no operation ran on the device."""


def read(obs):
    t = obs["trace"]
    if not t or t["busy_s"] is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
