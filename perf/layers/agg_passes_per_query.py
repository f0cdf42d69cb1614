"""Device passes a query's grouped aggregates take: sort-based
(``agg.sort_passes``, one ``group_aggregate``: the sort programs, a gather
and the two-program segment reduction) and dense (``agg.dense_passes``, one
``dense_group_aggregate``: a scatter into one slot per key combination),
counted on the host where ``exec/aggregate.py`` dispatches them: a partial
pass per scanned batch, the folds, the final stage's merge."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["agg.sort_passes", "agg.dense_passes"])
