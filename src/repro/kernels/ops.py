"""Pytree adapter for the Pallas fedagg kernel.

The kernels compile natively for the TPU (Mosaic). Interpret mode (the
same kernel body, executed op by op on any backend) is never chosen for
the caller: pass ``interpret=True`` explicitly, as the CPU tests do. Off a
TPU, a call without it raises. Pure-jnp oracles live in ref.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fedagg import fedagg


def fedagg_pytree(stacked, w: jax.Array, *, interpret: bool = False):
    """Weighted-average a stacked client pytree through the fedagg kernel."""
    leaves, treedef = jax.tree.flatten(stacked)
    K = leaves[0].shape[0]
    flat = jnp.concatenate(
        [l.reshape(K, -1).astype(jnp.float32) for l in leaves], axis=1)
    out = fedagg(flat, w.astype(jnp.float32), interpret=interpret)
    segs = []
    off = 0
    for l in leaves:
        n = int(l[0].size)
        segs.append(out[off:off + n].reshape(l.shape[1:]).astype(l.dtype))
        off += n
    return treedef.unflatten(segs)


__all__ = ["fedagg_pytree"]
