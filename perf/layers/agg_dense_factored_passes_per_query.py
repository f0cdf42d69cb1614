"""Dense aggregate passes a query takes whose counts and integer sums went
through the factorized one-hot on the MXU (``agg.dense_factored_passes``,
PR 37: every ``dense_group_aggregate`` past 2,048 slots, as
``ops/aggregate.py dense_factored`` says), counted on the host where
``exec/aggregate.py`` counts ``agg.dense_passes``. 0 in a cell whose dense
passes stay within the one-hot kernels' 2,048 slots; a program without the
counter gives ``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["agg.dense_factored_passes"])
