"""Mesh construction.

Every mesh is built by a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The mesh's axes are
``("data", "model")``: every layer shards the batch over ``"data"`` and
weights and heads over ``"model"``, by those names.
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

