"""Tasks of a query that ran a window or a percentile operator
(``holistic.tasks``: +1 for every task whose plan holds a ``WindowExec`` or
``PercentileExec`` and ran it): 1 where every row goes through one task,
the exchange's partitions where the rows are divided by key. A program
without the counter gives ``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["holistic.tasks"])
