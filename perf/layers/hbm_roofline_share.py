"""The least time the chip could take for the traced window's queries, over
the time it was busy: the bytes each completed query has to read at least
once (``least_bytes`` of its template, from the tables' row counts, whatever
implements the query) over the chip's HBM bandwidth, divided by the device's
busy seconds. Bound by bytes: these queries do a few operations per byte."""


def read(obs):
    t = obs["trace"]
    if not t or not t["busy_s"] or not t["queries"] or not obs["peaks"]:
        return None
    need = sum(obs["templates"][r["template"]].least_bytes(obs["rows"])
               for r in t["queries"])
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] / t["busy_s"]
