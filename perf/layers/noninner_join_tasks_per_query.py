"""Tasks of a query that ran a ``LEFT``, ``SEMI`` or ``ANTI`` join
(``join.noninner.tasks``: +1 a task): the partitions the preserved side was
divided into. 0 where every join is inner; a program without the counter
gives ``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["join.noninner.tasks"])
