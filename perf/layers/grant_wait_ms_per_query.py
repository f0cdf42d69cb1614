"""Milliseconds per query that tasks were grantable, with a slot free for
them, before a poll took them (``phase.scheduler.grant_wait.seconds``): each
task's first grant counts from the later of two instants, its stage entering
the running set (or the bypass task being queued) and the polling executor
telling the scheduler of a free slot, so the wait for a slot is not in it;
summed over a query's tasks. Readable where the scheduler shares the chip
owner's counter store (standalone); the daemons' scheduler keeps its
counters to itself. A program from before PR 30 has no such counter:
``None``, the metric left out."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["phase.scheduler.grant_wait.seconds"], 1e3)
