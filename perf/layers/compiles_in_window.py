"""Backend compile requests counted by the chip's owner between the window's
start and its end (``compilecache/metrics.py``; the daemons' executor ships
them to the scheduler's ``/api/state``). Every query of the window was sent
once in warm-up, so this should read 0; a count, so 0 is a reading."""


def read(obs):
    a, b = obs["counters_before"], obs["counters_after"]
    if a is None or b is None:
        return None
    return float(b.get("backend_compiles", 0) - a.get("backend_compiles", 0))
