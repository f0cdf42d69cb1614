"""Milliseconds per query the executor's tasks spent decoding scans on the
host (``phase.task.scan_host.seconds``): parquet and CSV reads, and Arrow to
the numpy the device will hold, for scans that were not resident and for
shuffle partitions read back. A program before the phase counters gives
``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["phase.task.scan_host.seconds"], 1e3)
