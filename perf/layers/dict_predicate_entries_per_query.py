"""Dictionary entries a query's string predicates were evaluated over
(``dict_predicate.entries``: + the dictionary's length at every host-side
evaluation of a ``LIKE``, ``substr`` or string ``IN`` table). A table is
kept with the dictionary it was computed for, so once the window's
dictionaries have met their patterns this reads 0; a program that evaluates
per batch and per query reads the dictionaries' sizes times their batches.
0 where no query has such a predicate; a program without the counter gives
``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["dict_predicate.entries"])
