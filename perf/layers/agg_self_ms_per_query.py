"""Milliseconds per query that task threads spent in the grouped aggregates'
own code (``HashAggregateExec``, ``MeshAggregateExec``:
``op.aggregate.self_seconds``), without their inputs and without any host
phase: one family of ``operator_self_ms_per_query``."""

from layers._operators import ms_per_query


def read(obs):
    return ms_per_query(obs, ("aggregate",))
