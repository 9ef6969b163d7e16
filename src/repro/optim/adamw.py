"""Pure-JAX optimizers: AdamW and Adafactor.

No optax dependency.  State is a plain pytree congruent with the params so
ZeRO sharding specs (``models.params.zero_specs``) apply directly.

AdamW keeps fp32 ``m``/``v`` (the standard mixed-precision recipe).
Adafactor factors the second moment for >=2-D parameters (row/col
accumulators) and skips momentum — the optimizer-state footprint drops from
8 bytes/param to ~0, which is what lets the 398B/671B configurations fit.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array
    m: Any
    v: Any


class AdafactorState(NamedTuple):
    step: jax.Array
    vr: Any  # row accumulators (or full v for 1-D params)
    vc: Any  # col accumulators (zeros-like scalar placeholder for 1-D)


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    # adafactor
    decay_rate: float = 0.8
    clip_threshold: float = 1.0


def make_optimizer(opt_cfg: OptimizerConfig):
    if opt_cfg.name == "adamw":
        return AdamW(opt_cfg)
    if opt_cfg.name == "adafactor":
        return Adafactor(opt_cfg)
    raise ValueError(f"unknown optimizer {opt_cfg.name!r}")


class AdamW:
    def __init__(self, cfg: OptimizerConfig):
        self.cfg = cfg

    def init(self, params: Any) -> AdamWState:
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return AdamWState(
            step=jnp.zeros((), jnp.int32),
            m=jax.tree.map(zeros, params),
            v=jax.tree.map(zeros, params),
        )

    def state_specs(self, param_specs: Any, zero_param_specs: Any) -> AdamWState:
        """Spec tree congruent with the state (ZeRO specs for m/v)."""
        from jax.sharding import PartitionSpec as P

        return AdamWState(step=P(), m=zero_param_specs, v=zero_param_specs)

    def update(
        self, grads: Any, state: AdamWState, params: Any, lr: jax.Array
    ) -> Tuple[Any, AdamWState]:
        c = self.cfg
        step = state.step + 1
        bc1 = 1.0 - c.b1 ** step.astype(jnp.float32)
        bc2 = 1.0 - c.b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            g = g.astype(jnp.float32)
            m = c.b1 * m + (1 - c.b1) * g
            v = c.b2 * v + (1 - c.b2) * jnp.square(g)
            mhat = m / bc1
            vhat = v / bc2
            delta = mhat / (jnp.sqrt(vhat) + c.eps)
            if p.ndim >= 2:  # no decay on norms/biases
                delta = delta + c.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

        out = jax.tree.map(upd, params, grads, state.m, state.v)
        new_params = jax.tree.map(lambda t: t[0], out, is_leaf=lambda t: isinstance(t, tuple))
        new_m = jax.tree.map(lambda t: t[1], out, is_leaf=lambda t: isinstance(t, tuple))
        new_v = jax.tree.map(lambda t: t[2], out, is_leaf=lambda t: isinstance(t, tuple))
        return new_params, AdamWState(step=step, m=new_m, v=new_v)


class Adafactor:
    """Factored second-moment optimizer (Shazeer & Stern, 2018), no momentum."""

    def __init__(self, cfg: OptimizerConfig):
        self.cfg = cfg

    def init(self, params: Any) -> AdafactorState:
        def vr(p):
            if p.ndim >= 2:
                return jnp.zeros(p.shape[:-1], jnp.float32)
            return jnp.zeros(p.shape, jnp.float32)

        def vc(p):
            if p.ndim >= 2:
                return jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)
            return jnp.zeros((), jnp.float32)

        return AdafactorState(
            step=jnp.zeros((), jnp.int32),
            vr=jax.tree.map(vr, params),
            vc=jax.tree.map(vc, params),
        )

    def state_specs(self, param_specs: Any, zero_param_specs: Any) -> AdafactorState:
        from jax.sharding import PartitionSpec as P

        def vr_spec(spec):
            return P(*spec[:-1])

        def vc_spec(spec):
            if len(spec) >= 2:
                return P(*(spec[:-2] + spec[-1:]))
            return P()

        return AdafactorState(
            step=P(),
            vr=jax.tree.map(vr_spec, param_specs, is_leaf=_is_spec),
            vc=jax.tree.map(vc_spec, param_specs, is_leaf=_is_spec),
        )

    def update(
        self, grads: Any, state: AdafactorState, params: Any, lr: jax.Array
    ) -> Tuple[Any, AdafactorState]:
        c = self.cfg
        step = state.step + 1
        beta = 1.0 - (step.astype(jnp.float32) + 1.0) ** (-c.decay_rate)

        def upd(p, g, vr, vc):
            g = g.astype(jnp.float32)
            g2 = jnp.square(g) + 1e-30
            if p.ndim >= 2:
                vr = beta * vr + (1 - beta) * jnp.mean(g2, axis=-1)
                vc = beta * vc + (1 - beta) * jnp.mean(g2, axis=-2)
                rfac = jax.lax.rsqrt(
                    vr / jnp.maximum(jnp.mean(vr, axis=-1, keepdims=True), 1e-30)
                )
                cfac = jax.lax.rsqrt(vc)
                delta = g * rfac[..., None] * cfac[..., None, :]
            else:
                vr = beta * vr + (1 - beta) * g2
                vc = vc
                delta = g * jax.lax.rsqrt(vr)
            # update clipping (RMS(delta) <= clip_threshold)
            rms = jnp.sqrt(jnp.mean(jnp.square(delta)) + 1e-30)
            delta = delta / jnp.maximum(1.0, rms / c.clip_threshold)
            if p.ndim >= 2:
                delta = delta + c.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), vr, vc

        out = jax.tree.map(upd, params, grads, state.vr, state.vc)
        first = lambda i: jax.tree.map(
            lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple)
        )
        return first(0), AdafactorState(step=step, vr=first(1), vc=first(2))


def _is_spec(x):
    from jax.sharding import PartitionSpec

    return isinstance(x, PartitionSpec)


# ---------------------------------------------------------------------------
# Gradient utilities
# ---------------------------------------------------------------------------


def global_norm(tree: Any) -> jax.Array:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree))
    )


def clip_by_global_norm(tree: Any, max_norm: float) -> Tuple[Any, jax.Array]:
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), tree), norm
