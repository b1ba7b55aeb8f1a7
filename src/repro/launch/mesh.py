"""Production mesh builders.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, smoke tests stay on 1 device.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips (one v5e pod's worth).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the `pod` axis is
    the cross-DCN/ICI axis (outer data-parallel by default, or the GPipe
    axis — see distributed/pipeline.py)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the model code places activations with
    # with_sharding_constraint, which rejects jax.make_mesh's default
    # Explicit axes
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 2, model: int = 2, pod: int = 0) -> Mesh:
    """Small mesh over however many (forced) host devices exist — used by
    multi-device CPU tests."""
    devs = jax.devices()
    n = (pod or 1) * data * model
    if len(devs) < n:
        raise RuntimeError(f"need {n} devices, have {len(devs)}")
    if pod:
        arr = np.array(devs[:n]).reshape(pod, data, model)
        return Mesh(arr, ("pod", "data", "model"))
    arr = np.array(devs[:n]).reshape(data, model)
    return Mesh(arr, ("data", "model"))
