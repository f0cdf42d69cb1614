"""The least time the chip could take for the joins of the traced window's
queries, over the device seconds their programs took (``layers/_join.py``):
each build row's key and payload written once, each probe row's key read
once, each output row's carried columns gathered once and written once
(``join_least_bytes`` of the template, beside its ``least_bytes``), over
the chip's HBM bandwidth. It cannot pass 100 %."""

from layers._join import device_seconds


def read(obs):
    s = device_seconds(obs)
    if not s or not obs["peaks"]:
        return None
    need = sum(
        obs["templates"][r["template"]].join_least_bytes(obs["rows"])
        for r in obs["trace"]["queries"]
        if hasattr(obs["templates"][r["template"]], "join_least_bytes")
    )
    if not need:
        return None
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] / s
