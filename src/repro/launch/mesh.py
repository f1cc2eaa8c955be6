"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run overrides the
host platform device count before first jax init.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(axis_shapes: Tuple[int, ...], axis_names: Tuple[str, ...],
              devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis of the Auto type."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; multi_pod adds a 2-pod DCN axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for_devices(n_devices: Optional[int] = None,
                          model_parallelism: int = 1) -> Mesh:
    """Elastic helper: best (data, model) mesh for whatever is available.
    Used by the train/serve launchers and the elastic-resharding path."""
    n = n_devices or len(jax.devices())
    model = max(1, min(model_parallelism, n))
    while n % model != 0:
        model -= 1
    return make_mesh((n // model, model), ("data", "model"))


def mesh_device_count(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
