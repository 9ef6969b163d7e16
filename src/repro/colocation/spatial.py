"""Spatial co-location: split one pod mesh into disjoint sub-meshes.

The scheduler treats sub-meshes like the paper treats GPU sets: a job gets
a contiguous slice of the device grid; FindCandidates operates on sub-mesh
granularity.  Complements the temporal stepper: spatial for
jobs with incompatible memory footprints, temporal for complementary duty
cycles.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import numpy as np


def split_mesh(
    mesh: jax.sharding.Mesh, parts: int, axis: str = "data"
) -> List[jax.sharding.Mesh]:
    """Split ``mesh`` into ``parts`` disjoint sub-meshes along ``axis``.

    Each sub-mesh keeps the original axis names (so the same model
    PartitionSpecs apply) with the split axis shrunk by ``parts``.
    """
    ax = mesh.axis_names.index(axis)
    n = mesh.devices.shape[ax]
    if n % parts:
        raise ValueError(f"axis {axis} of size {n} not divisible into {parts} parts")
    out = []
    for i in range(parts):
        idx = [slice(None)] * mesh.devices.ndim
        idx[ax] = slice(i * (n // parts), (i + 1) * (n // parts))
        sub = mesh.devices[tuple(idx)]
        out.append(jax.sharding.Mesh(sub, mesh.axis_names))
    return out


def submesh_for_job(
    mesh: jax.sharding.Mesh, start: int, size: int, axis: str = "data"
) -> jax.sharding.Mesh:
    """A contiguous sub-mesh slice [start, start+size) along ``axis``."""
    ax = mesh.axis_names.index(axis)
    idx = [slice(None)] * mesh.devices.ndim
    idx[ax] = slice(start, start + size)
    return jax.sharding.Mesh(mesh.devices[tuple(idx)], mesh.axis_names)
