"""Peak device memory of the chip's owner after the window, in GB."""


def read(obs):
    if not obs["peak_bytes"]:
        return None
    return obs["peak_bytes"] / 1e9
