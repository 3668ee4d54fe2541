"""FL client meshes: a "pod" axis for cluster-as-collective execution.

The production mapping (DESIGN.md section 3 / `launch.mesh`) gives every
orbital cluster its own pod of chips; on host backends (CPU smoke runs,
single-GPU dev boxes) there are fewer devices than clusters, so the pod
axis is laid over however many devices exist and each shard carries a
*block* of pods — the shard_map body vmaps its local block and the psum
still spans every pod (`repro.core.aggregation.masked_delta_allreduce`).
With one device this degenerates to the vmapped host computation expressed
through the collective, which is exactly what makes the mesh path testable
(and bit-comparable) on CI hardware.
"""
from __future__ import annotations

import jax


def client_mesh(n_clients: int, *, axis: str = "pod", devices=None):
    """1-D mesh whose `axis` carries FL client pods.

    Uses min(n_devices, n_clients) devices; callers pad their client batch
    to a multiple of the axis size (`pad_client_count`) with zero-weight
    slots, the collective equivalent of an out-of-contact satellite.
    The axis is `Auto`: outside the shard_map body the model it returns
    is an ordinary replicated array, which any later program (evaluation,
    a primitive with no explicit-sharding rule such as `ragged_dot`) takes.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = max(1, min(len(devices), int(n_clients)))
    return jax.make_mesh((n,), (axis,), devices=devices[:n],
                         axis_types=(jax.sharding.AxisType.Auto,))


def pad_client_count(n_clients: int, mesh, axis: str = "pod") -> int:
    """Smallest multiple of the mesh's `axis` size >= n_clients."""
    size = int(mesh.shape[axis])
    return ((max(1, int(n_clients)) + size - 1) // size) * size
