"""Few-round-trip device->host fetches.

Every device buffer fetched is a blocking host round trip (cost not measured
on the attached chip) — ``jax.device_get`` on a pytree fetches its leaves
serially, so a 20-column batch pays 20 round trips. Packing everything
into one
buffer via bitcast is NOT safe here: the TPU x64-rewrite pass stores 64-bit
element types in rewritten form and rejects (or truncates) bitcasts on
them. Instead, arrays are grouped BY DTYPE and concatenated on device (one
cached jitted concat per dtype-signature — dispatches are async and free),
so a fetch moves at most one buffer per distinct dtype (<=4-5 in practice)
rather than one per array. Exact ``device_get`` semantics are preserved:
values round-trip through the same dtype they were computed in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=None)
def _concat_program(dtype: str, lengths: tuple):
    # the program's name on the device trace says what it packs
    if len(lengths) == 1:

        def fetch_flat(x):
            return x.reshape(-1)

        fetch_flat.__name__ = f"fetch_flat_{dtype}"
        return jax.jit(fetch_flat)

    def fetch_concat(*xs):
        return jnp.concatenate([x.reshape(-1) for x in xs])

    fetch_concat.__name__ = f"fetch_concat_{dtype}"
    return jax.jit(fetch_concat)


@functools.lru_cache(maxsize=None)
def _f64_concat_program(sig: tuple):
    """sig: tuple of (dtype_str, length). One f64 buffer for everything."""

    def fetch_concat_f64(*xs):
        return jnp.concatenate(
            [x.reshape(-1).astype(jnp.float64) for x in xs]
        )

    return jax.jit(fetch_concat_f64)


# Above this total size, f64 widening of narrow columns costs more in
# transfer bytes than the saved per-dtype round trips (~0.1s each at
# ~10MB/s D2H).
_F64_FETCH_MAX_BYTES = 4 << 20

# dtypes that round-trip exactly through float64. int64 qualifies because
# the TPU x64-rewrite stores 64-bit integers in 32-bit physical form, so
# device values always fit float64's 2^53 integer range.
_F64_EXACT = {
    "bool", "int8", "uint8", "int16", "uint16", "int32", "uint32",
    "int64", "float32", "float64",
}


def read_array(x, site: str) -> np.ndarray:
    """``np.asarray`` of ONE device value, blocking, as the ``task.d2h``
    phase at ``site``: for the reads that are a single array already (a
    count, a flag, a mask), where packing through :func:`fetch_arrays`
    would only add a dispatch."""
    from ballista_tpu.obs import trace as obs_trace

    with obs_trace.phase("task.d2h", site=site) as ph:
        out = np.asarray(x)
        ph.nbytes = out.nbytes
    return out


def fetch_arrays(arrays: list, site: str = "fetch") -> list[np.ndarray]:
    """Fetch device arrays to host numpy in as few blocking round trips as
    possible: ONE for small batches (everything widened to a single f64
    buffer — value-preserving), one per distinct dtype otherwise. Returns
    arrays in input order with original shapes.

    ``site``: a short static string naming the caller. The blocking read
    is the ``task.d2h`` phase of docs/observability.md, counted per site:
    the list of round trips a query makes."""
    from ballista_tpu.obs import trace as obs_trace

    arrays = [jnp.asarray(a) for a in arrays]
    if not arrays:
        return []
    sig = tuple(
        (str(a.dtype), int(np.prod(a.shape)) if a.shape else 1)
        for a in arrays
    )
    total = sum(n for _, n in sig)
    dtypes = {dt for dt, _ in sig}
    if (
        len(dtypes) > 1
        and total * 8 <= _F64_FETCH_MAX_BYTES
        and dtypes <= _F64_EXACT
    ):
        packed = _f64_concat_program(sig)(*arrays)
        with obs_trace.phase("task.d2h", site=site) as ph:
            buf = np.asarray(jax.device_get(packed))
            ph.nbytes = buf.nbytes
        out = []
        off = 0
        for a, (dt, n) in zip(arrays, sig):
            v = buf[off : off + n].reshape(a.shape)
            # garbage under null masks may be NaN/Inf; the cast back to an
            # int dtype is still value-preserving for every LIVE lane
            with np.errstate(invalid="ignore"):
                out.append(v.astype(np.dtype(dt)))
            off += n
        return out
    groups: dict[str, list[int]] = {}
    for i, a in enumerate(arrays):
        groups.setdefault(str(a.dtype), []).append(i)
    packed = []
    for dt, idxs in groups.items():
        arrs = [arrays[i] for i in idxs]
        lengths = tuple(int(np.prod(a.shape)) if a.shape else 1 for a in arrs)
        packed.append(_concat_program(dt, lengths)(*arrs))
    with obs_trace.phase("task.d2h", site=site) as ph:
        host = [np.asarray(b) for b in jax.device_get(tuple(packed))]
        ph.nbytes = sum(b.nbytes for b in host)
    out: list[np.ndarray | None] = [None] * len(arrays)
    for buf, (dt, idxs) in zip(host, groups.items()):
        off = 0
        for i in idxs:
            shape = arrays[i].shape
            n = int(np.prod(shape)) if shape else 1
            out[i] = buf[off : off + n].reshape(shape)
            off += n
    return out
