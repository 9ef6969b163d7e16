"""The parameters of each reference model: names, shapes, stored dtypes
and their random initialisation from a seed.

The layout is the one the program stores (layers stacked along a leading
axis under ``dense/l0`` or ``ssm/l0``, the vocabulary padded to a multiple
of 256), so that the same weights can be handed to the program and to the
reference.  The weights are made here, from the seed, and by neither of
them."""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

VOCAB_PAD = 256


def _pad(v: int) -> int:
    return -(-v // VOCAB_PAD) * VOCAB_PAD


# leaf spec: (shape, init, argument of the init)
Spec = Tuple[Tuple[int, ...], str, float]


def transformer_specs(cfg: dict) -> Dict[str, Any]:
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    ff, Vp = cfg["intermediate_size"], _pad(cfg["vocab_size"])
    std = cfg["init"]["linear_std"]
    return {
        "embed": {"table": ((Vp, d), "normal", cfg["init"]["embed_std"])},
        "head": {"w": ((d, Vp), "normal", std)},
        "final_norm": {"scale": ((d,), "ones", 0.0)},
        "dense": {
            "l0": {
                "norm1": {"scale": ((L, d), "ones", 0.0)},
                "mixer": {
                    "wq": ((L, d, H * hd), "normal", std),
                    "wk": ((L, d, Hkv * hd), "normal", std),
                    "wv": ((L, d, Hkv * hd), "normal", std),
                    "wo": ((L, H * hd, d), "normal", std),
                },
                "norm2": {"scale": ((L, d), "ones", 0.0)},
                "channel": {
                    "gate": ((L, d, ff), "normal", std),
                    "up": ((L, d, ff), "normal", std),
                    "down": ((L, ff, d), "normal", std),
                },
            }
        },
    }


def mamba2_specs(cfg: dict) -> Dict[str, Any]:
    d, L = cfg["d_model"], cfg["n_layer"]
    s = cfg["ssm_cfg"]
    d_in = s["expand"] * d
    H, GN, W = d_in // s["headdim"], s["ngroups"] * s["d_state"], s["d_conv"]
    init = cfg["init"]
    std = init["linear_std"]
    return {
        "embed": {"table": ((_pad(cfg["vocab_size"]), d), "normal", init["embed_std"])},
        "final_norm": {"scale": ((d,), "ones", 0.0)},
        "ssm": {
            "l0": {
                "norm1": {"scale": ((L, d), "ones", 0.0)},
                "mixer": {
                    "w_z": ((L, d, d_in), "normal", std),
                    "w_x": ((L, d, d_in), "normal", std),
                    "w_bc": ((L, d, 2 * GN), "normal", std),
                    "w_dt": ((L, d, H), "normal", std),
                    "dt_bias": ((L, H), "dt_bias", 0.0),
                    "A_log": ((L, H), "A_log", 0.0),
                    "D": ((L, H), "ones", 0.0),
                    "conv_x": ((L, W, d_in), "conv", 0.0),
                    "conv_bc": ((L, W, 2 * GN), "conv", 0.0),
                    "norm": ((L, d_in), "ones", 0.0),
                    "w_out": ((L, d_in, d), "normal", init["out_proj_std_over_sqrt_layers"] / math.sqrt(L)),
                },
            }
        },
    }


SPECS = {"transformer": transformer_specs, "mamba2": mamba2_specs}


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def specs(cfg: dict) -> Dict[str, Any]:
    return SPECS[cfg["architecture"]](cfg)


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


def _draw(key, shape, kind, arg, cfg):
    init = cfg["init"]
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * arg
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "dt_bias":  # dt log-uniform in [dt_min, dt_max]; bias = softplus^-1(dt)
        lo, hi = math.log(init["dt_min"]), math.log(init["dt_max"])
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))
    if kind == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, init["A_min"], init["A_max"]))
    if kind == "conv":  # PyTorch's default for a depthwise Conv1d: U(+-1/sqrt(width))
        b = 1.0 / math.sqrt(shape[-2])
        return jax.random.uniform(key, shape, jnp.float32, -b, b)
    raise ValueError(f"unknown init {kind!r}")


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
