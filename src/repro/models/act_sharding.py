"""Logical activation-sharding constraints.

Model code calls `constrain(x, "batch", None, "tp")` with *logical* axis
names; if an ambient mesh (jax.set_mesh) is present at trace time the logical
names resolve to whatever physical axes exist ('pod'/'data'/'model') and a
with_sharding_constraint is inserted; with no mesh (CPU smoke tests) it is a
no-op. This keeps the model single-source for 1-device tests and 512-chip
lowering.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

# logical -> candidate physical axes (first ones present in the mesh are used)
_LOGICAL = {
    "batch": ("pod", "data"),   # data-parallel batch shards
    "fsdp": ("data",),
    "tp": ("model",),           # tensor/vocab/head/expert parallel
    "seq": ("model",),          # sequence sharding (context parallel)
    "expert": ("model",),
    None: (),
}


def _resolve(logical, axis_names) -> Optional[Tuple[str, ...]]:
    if logical is None:
        return None
    axes = tuple(a for a in _LOGICAL[logical] if a in axis_names)
    return axes if axes else None


def constrain(x, *logical_spec):
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = tuple(_resolve(l, mesh.axis_names) for l in logical_spec)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))
