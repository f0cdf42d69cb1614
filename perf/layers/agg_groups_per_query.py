"""Rows a query's final aggregates emitted (``agg.groups_out``, summed from
the operator metrics a task fetches at its end anyway): that the cell
aggregates what it says, four groups in TPC-H q1 and 1e4 to 1e5 in the
group-by questions of h2oai's db-benchmark."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["agg.groups_out"])
