"""Milliseconds per query that task threads spent blocked in device-to-host
reads (``phase.task.d2h.seconds``): waiting for the device to finish what
the value depends on, then the copy."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["phase.task.d2h.seconds"], 1e3)
