"""Milliseconds per query the scheduler spent planning: logical to physical
to stages (``phase.scheduler.plan.seconds``). Readable where the scheduler
shares the chip owner's counter store (standalone); the daemons' scheduler
keeps its counters to itself."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["phase.scheduler.plan.seconds"], 1e3)
