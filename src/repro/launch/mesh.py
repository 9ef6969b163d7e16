"""Mesh construction.

Every mesh is built by a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run must set
``XLA_FLAGS`` before anything initializes the backend.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax


def _make_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    return jax.make_mesh(
        shape,
        axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """The dry-run's production meshes.

    single-pod: (16, 16) = 256 chips, axes ("data", "model")
    multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model")
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def host_mesh_shape(n_devices: int) -> Tuple[int, int]:
    """The squarest ``(data, model)`` split of ``n_devices`` with
    ``data >= model``: 1 -> (1, 1), 4 -> (2, 2), 8 -> (4, 2)."""
    model = max(m for m in range(1, math.isqrt(n_devices) + 1) if n_devices % m == 0)
    return n_devices // model, model


def make_host_mesh(devices=None) -> jax.sharding.Mesh:
    """``("data", "model")`` mesh over the devices present (default: all of
    ``jax.devices()``), shaped by ``host_mesh_shape``."""
    devices = list(jax.devices() if devices is None else devices)
    return _make_mesh(host_mesh_shape(len(devices)), ("data", "model"), devices)


def batch_axes_of(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Axes that carry the batch dimension (pod composes with data)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
