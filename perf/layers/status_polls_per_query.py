"""``GetJobStatus`` calls the scheduler served per query (``status.rpcs``):
how often a client asked before it was told of its job's end. Since PR 34
the scheduler holds an ask until the job ends, within ``POLL_HOLD_S``, so a
query shorter than that is one call and a longer one a call per bound;
before, a call every 0.1 s of the job. Readable where the scheduler shares
the chip owner's counter store (standalone); the daemons' scheduler keeps
its counters to itself. A program from before PR 34 has no such counter:
``None``, the metric left out."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["status.rpcs"])
