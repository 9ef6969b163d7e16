"""Parameter-definition system.

A model is described as a pytree of :class:`ParamDef` (shape + init + logical
partition spec).  From one definition tree we derive, *congruently by
construction*:

  * materialized parameters (``init``),
  * abstract parameters, shapes without allocation (``abstract``),
  * ``PartitionSpec`` trees for pjit in/out shardings (``specs``),
  * ZeRO-extended specs for optimizer state (``zero_specs``).

Logical axis names used by the model zoo:

  ``model``  tensor-parallel axis (heads / d_ff / experts / vocab)
  ``data``   data-parallel axis (batch; optimizer state under ZeRO)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

Initializer = Callable[[jax.Array, Tuple[int, ...], Any], jax.Array]


def normal_init(stddev: float = 0.02) -> Initializer:
    def init(key, shape, dtype):
        return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)

    return init


def fan_in_init(scale: float = 1.0) -> Initializer:
    """LeCun-normal style: stddev = scale / sqrt(fan_in)."""

    def init(key, shape, dtype):
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        std = scale / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    return init


def zeros_init() -> Initializer:
    def init(key, shape, dtype):
        return jnp.zeros(shape, dtype)

    return init


def ones_init() -> Initializer:
    def init(key, shape, dtype):
        return jnp.ones(shape, dtype)

    return init


def const_init(value: float) -> Initializer:
    def init(key, shape, dtype):
        return jnp.full(shape, value, dtype)

    return init


@dataclasses.dataclass
class ParamDef:
    """One parameter: shape, dtype, initializer and logical sharding spec.

    ``spec`` entries are logical axis names (``"model"`` / ``None``); the
    ``data`` axis is introduced only by the ZeRO transform.
    """

    shape: Tuple[int, ...]
    spec: Tuple[Optional[str], ...]
    init: Initializer = normal_init()
    dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if len(self.shape) != len(self.spec):
            raise ValueError(f"shape {self.shape} vs spec {self.spec} rank mismatch")


def is_param_def(x: Any) -> bool:
    return isinstance(x, ParamDef)


def _tree_map(fn: Callable[[ParamDef], Any], defs: Any) -> Any:
    return jax.tree.map(fn, defs, is_leaf=is_param_def)


def stack(defs: Any, n: int) -> Any:
    """Stack a layer's defs ``n`` times for scan-over-layers (leading L dim)."""

    def _stack(d: ParamDef) -> ParamDef:
        return ParamDef(
            shape=(n,) + d.shape,
            spec=(None,) + d.spec,
            init=_vmap_init(d.init, n),
            dtype=d.dtype,
        )

    return _tree_map(_stack, defs)


def _vmap_init(init: Initializer, n: int) -> Initializer:
    def stacked(key, shape, dtype):
        keys = jax.random.split(key, n)
        return jax.vmap(lambda k: init(k, shape[1:], dtype))(keys)

    return stacked


def init_params(defs: Any, key: jax.Array) -> Any:
    """Materialize parameters (used by smoke tests / real training)."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_param_def)
    keys = jax.random.split(key, len(leaves))
    vals = [d.init(k, d.shape, d.dtype) for d, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(defs: Any) -> Any:
    """ShapeDtypeStruct tree (no allocation)."""
    return _tree_map(lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs)


def partition_specs(defs: Any) -> Any:
    """PartitionSpec tree for pjit shardings."""
    return _tree_map(lambda d: P(*d.spec), defs)


def zero_specs(defs: Any, data_size: int) -> Any:
    """ZeRO/FSDP specs: additionally shard the largest unsharded, divisible
    axis over the ``"data"`` mesh axis.  Params whose spec already uses it
    are returned unchanged (idempotent — FSDP'd weights feed straight through)."""

    def _zero(d: ParamDef) -> P:
        spec = list(d.spec)
        if "data" in spec:
            return P(*spec)  # already data-sharded
        # pick the largest dim that is unsharded and divisible
        best, best_dim = -1, -1
        for i, (dim, s) in enumerate(zip(d.shape, spec)):
            if s is None and dim % data_size == 0 and dim > best_dim:
                best, best_dim = i, dim
        if best >= 0:
            spec[best] = "data"
        return P(*spec)

    return _tree_map(_zero, defs)


def fsdp_param_specs(defs: Any, data_size: int) -> Any:
    """Weight specs with data-axis sharding on the largest free dim.

    XLA all-gathers each scanned layer's weights on use and reduce-scatters
    its gradients — ZeRO-3 semantics expressed purely through shardings."""
    return zero_specs(defs, data_size)


def param_bytes(defs: Any) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=is_param_def)
    return sum(int(np.prod(d.shape)) * jnp.dtype(d.dtype).itemsize for d in leaves)
