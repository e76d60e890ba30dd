"""Activation sharding constraints with logical axis names.

Model code calls ``constrain(x, "batch", None, "tp")`` — mesh-agnostic logical
names resolved against the ambient mesh (set by ``use_mesh``):

  * "batch" -> ("pod", "data") (whichever exist; divisibility-checked),
  * "tp"    -> "model",
  * "seq"   -> "data" (sequence parallelism),
  * None    -> replicated.

Outside a ``use_mesh`` context (CPU smoke tests) this is a no-op, so the same
model code runs everywhere.  GSPMD without these constraints reshards the 5-D
SSD/MoE intermediates pathologically (measured: 1.0 TB of collective-permute
per step on mamba2 train_4k — EXPERIMENTS.md §Perf iteration 1).
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

_ACTIVE: list = []

_LOGICAL = {
    "batch": ("pod", "data"),
    "tp": ("model",),
    "seq": ("data",),
    "expert": ("model",),
    "rows": ("pod", "data", "model"),  # tabular serving: rows over everything
}


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    _ACTIVE.append(mesh)
    try:
        yield
    finally:
        _ACTIVE.pop()


def current_mesh() -> Optional[Mesh]:
    return _ACTIVE[-1] if _ACTIVE else None


def constrain(x, *logical):
    """Apply with_sharding_constraint with logical names; no-op without mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = []
    for dim, item in zip(x.shape, logical):
        if item is None:
            spec.append(None)
            continue
        axes = []
        total = 1
        for ax in _LOGICAL.get(item, (item,)):
            size = mesh.shape.get(ax, 1)
            if size > 1 and dim % (total * size) == 0:
                axes.append(ax)
                total *= size
        spec.append(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))
    # trailing unlisted dims replicate
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
