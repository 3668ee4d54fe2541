from repro.sharding.flmesh import client_mesh, pad_client_count
from repro.sharding.specs import (
    param_pspecs,
    batch_pspec,
    cache_pspecs,
    MeshAxes,
)

__all__ = ["param_pspecs", "batch_pspec", "cache_pspecs", "MeshAxes",
           "client_mesh", "pad_client_count"]
