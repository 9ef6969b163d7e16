"""The parameters of each reference model: names, shapes, stored dtypes
and their random initialisation from a seed.

Each architecture's module (``chipbench/arch``) gives the layout, the one
the program stores (layers stacked along a leading axis, the vocabulary
padded to a multiple of 256), so that the same weights can be handed to the program and to the
reference.  The weights are made here, from the seed, and by neither of
them."""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from chipbench import arch

VOCAB_PAD = 256


def pad_vocab(v: int) -> int:
    """The vocabulary as the program stores it: padded to a multiple of 256."""
    return -(-v // VOCAB_PAD) * VOCAB_PAD


# leaf spec: (shape, init, argument of the init)
Spec = Tuple[Tuple[int, ...], str, float]


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def specs(cfg: dict) -> Dict[str, Any]:
    """The stored parameters of ``cfg``, as its architecture lays them out."""
    return arch.of(cfg).specs(cfg)


def stored_dtype(cfg: dict, path: str):
    """The dtype a leaf is stored in, as the configuration states it."""
    name = path.rsplit("/", 1)[-1]
    return jnp.float32 if name in cfg["float32_params"] else jnp.dtype(cfg["param_dtype"])


def leaf_paths(cfg: dict):
    """[(path, spec)] in a fixed order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(specs(cfg), is_leaf=_is_spec)
    return [("/".join(k.key for k in path), spec) for path, spec in flat]


def abstract(cfg: dict) -> Dict[str, Any]:
    """ShapeDtypeStruct tree of the stored parameters."""
    return jax.tree_util.tree_map_with_path(
        lambda path, s: jax.ShapeDtypeStruct(s[0], stored_dtype(cfg, "/".join(k.key for k in path))),
        specs(cfg),
        is_leaf=_is_spec,
    )


def _normal(key, shape, arg, cfg):
    return jax.random.normal(key, shape, jnp.float32) * arg


def _ones(key, shape, arg, cfg):
    return jnp.ones(shape, jnp.float32)


# the inits every architecture may use; others are an architecture's own
SHARED_INITS = {"normal": _normal, "ones": _ones}


def _draw(key, shape, kind, arg, cfg):
    inits = {**SHARED_INITS, **getattr(arch.of(cfg), "INITS", {})}
    if kind not in inits:
        raise ValueError(f"unknown init {kind!r}")
    return inits[kind](key, shape, arg, cfg)


def seed_key(seed: int, stream: int) -> jax.Array:
    """A key from a seed of any size (a PRNGKey keeps only 32 bits)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def init(cfg: dict, key: jax.Array) -> Dict[str, Any]:
    """The stored parameters of ``cfg``, drawn from ``key``.  Trace it inside
    a jit: each leaf takes its own fold of the key."""
    out = {}
    for i, (path, (shape, kind, arg)) in enumerate(leaf_paths(cfg)):
        leaf = _draw(jax.random.fold_in(key, i), shape, kind, arg, cfg)
        node = out
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf.astype(stored_dtype(cfg, path))
    return out
