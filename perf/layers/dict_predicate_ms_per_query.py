"""Milliseconds per query of host-side string predicates over dictionaries
(``phase.task.dict_predicate.seconds``: every evaluation of a ``LIKE``,
``substr`` or string ``IN`` table over a dictionary's entries, and the
look-up of one already made). It lies inside a task's ``wall_seconds`` and
inside ``task_unnamed_ms_per_query``, whose reader does not take it out. A
phase's counters exist once it was entered, so only a cell whose queries
hold such a predicate reads it; a program without the phase gives
``None``."""

from layers._phases import per_query


def read(obs):
    return per_query(obs, ["phase.task.dict_predicate.seconds"], 1e3)
