"""Production mesh construction (function, not module constant — importing
this module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Single-device mesh for CPU smoke tests (axis sizes 1)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def elastic_mesh_shape(n_devices: int, *, model: int = 16):
    """Pick the largest (pod, data, model) grid for a degraded device count.

    Fault-tolerance path (DESIGN.md Sec. 5): after node failures the job
    restarts with whatever is healthy; ``model`` is kept fixed (weight layout
    stability) and the data axis absorbs the loss; leftover devices idle.
    """
    model = min(model, n_devices)
    while n_devices % model:
        model //= 2
    rest = n_devices // model
    # prefer a pod axis of 2 when even (cross-pod DP), else single pod
    if rest % 2 == 0 and rest >= 4:
        return (2, rest // 2, model), ("pod", "data", "model")
    return (rest, model), ("data", "model")
