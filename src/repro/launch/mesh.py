"""Production mesh construction (pure function — importing this module never
touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: ``data`` = FSDP/batch (ICI), ``model`` = TP (ICI), ``pod`` = pure
    DP across pods (DCN).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_for(n_devices: int, model_parallel: int = None):
    """Elastic helper: largest (data, model) mesh for the devices present."""
    model_parallel = model_parallel or min(n_devices, 16)
    while n_devices % model_parallel:
        model_parallel //= 2
    return jax.make_mesh((n_devices // model_parallel, model_parallel),
                         ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
