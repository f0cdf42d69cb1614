"""Milliseconds per query in shuffle I/O on task threads: Arrow IPC encode
and file write after the device read (``phase.task.shuffle_write.seconds``)
plus the wait for upstream partitions (``phase.task.shuffle_fetch.seconds``)."""

from layers._phases import per_query


def read(obs):
    return per_query(
        obs,
        ["phase.task.shuffle_write.seconds",
         "phase.task.shuffle_fetch.seconds"],
        1e3,
    )
