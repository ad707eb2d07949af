"""Batched device->host transfers.

Every ``device_get`` leaf is a separate blocking device->host transfer, so
host logic that reads several small device arrays at once (grown-tree
flushes, per-level split decisions) coalesces them into ONE flat buffer
before pulling. What a leaf costs on the attached chip is not measured
(ROADMAP C4 decides whether the packing still pays). bool/int32 promote
losslessly; uint32 and float32 BITCAST to int32 so every value crosses
bit-exactly and is re-bitcast host-side.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def pack_for_host(arrs):
    """Coalesce a pytree of mixed-dtype arrays into ONE flat int32 buffer."""
    parts = []
    for a in jax.tree_util.tree_leaves(arrs):
        if a.dtype in (jnp.float32, jnp.uint32):
            a = jax.lax.bitcast_convert_type(a, jnp.int32)
        else:
            a = a.astype(jnp.int32)
        parts.append(a.reshape(-1))
    return jnp.concatenate(parts)


def fetch_packed(dicts: list) -> list:
    """list of device dicts -> list of host numpy dicts via ONE packed
    transfer for the whole flush."""
    buf = np.asarray(pack_for_host(dicts))
    out, off = [], 0
    for arrays in dicts:
        host_d = {}
        for k in sorted(arrays):  # tree_leaves of a dict is key-sorted
            a = arrays[k]
            n = int(np.prod(a.shape)) if a.ndim else 1
            flat = buf[off:off + n]
            off += n
            if a.dtype in (jnp.float32, jnp.uint32):
                host = flat.view(np.dtype(a.dtype.name))
            elif a.dtype == jnp.bool_:
                host = flat.astype(bool)
            else:
                host = flat.astype(np.dtype(a.dtype.name))
            host_d[k] = host.reshape(a.shape)
        out.append(host_d)
    return out


class _Host:
    """Plain-attribute view over a fetched dict (duck-types the source)."""

    __slots__ = ("_d",)

    def __init__(self, dd):
        self._d = dd

    def __getattr__(self, name):
        try:
            return self._d[name]
        except KeyError:
            raise AttributeError(name)


def fetch_struct(res):
    """One packed pull of a NamedTuple/dataclass of device arrays ->
    plain-attribute host object (duck-types the original for ``.field``
    reads). Non-array fields pass through untouched."""
    d = res._asdict() if hasattr(res, "_asdict") else dict(vars(res))
    arrays = {k: v for k, v in d.items() if isinstance(v, jnp.ndarray)}
    host = fetch_packed([arrays])[0] if arrays else {}
    merged = dict(d)
    merged.update(host)
    return _Host(merged)
