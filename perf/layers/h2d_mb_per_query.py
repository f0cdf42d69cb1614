"""Megabytes per query the executor placed on the device from host memory
(``phase.task.h2d.bytes``): scans that were not resident and shuffle
partitions read back. A count of bytes, so 0 is a reading."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["phase.task.h2d.bytes"], 1e-6)
