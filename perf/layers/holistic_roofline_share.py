"""The least time the chip could take for the sorts of the traced window's
window and percentile queries, over the device seconds their programs took
(``layers/_holistic.py``): each sorted row's key and value bytes and a 4-byte
position, read once and written once (``sort_least_bytes`` of the template,
beside its ``least_bytes``), over the chip's HBM bandwidth. Bound by bytes: a
comparison sort of n rows does log n operations a row against the bytes of
log n passes, and one pass is the least. It cannot pass 100 %."""

from layers._holistic import device_seconds


def read(obs):
    s = device_seconds(obs)
    if not s or not obs["peaks"]:
        return None
    need = sum(
        obs["templates"][r["template"]].sort_least_bytes(obs["rows"])
        for r in obs["trace"]["queries"]
        if hasattr(obs["templates"][r["template"]], "sort_least_bytes")
    )
    if not need:
        return None
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] / s
