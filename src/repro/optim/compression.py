"""Gradient compression with error feedback (int8 quantization).

Models a compressed cross-host (DCN) gradient reduction two ways:

  * numerically: gradients are quantized / dequantized with an
    error-feedback buffer (``compress_grads``), so training dynamics match
    what a compressed all-reduce would produce;
  * analytically: ``compressed_bytes`` charges DCN bytes at ``bits/16`` of
    the bf16 volume.

No train step calls it: a compiled program keeps the full-precision
all-reduce, which XLA's SPMD partitioner owns.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp


class ErrorFeedbackState(NamedTuple):
    residual: Any  # pytree of fp32 residuals, congruent with grads


def init_error_feedback(params: Any) -> ErrorFeedbackState:
    return ErrorFeedbackState(
        residual=jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    )


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization. Returns (q, scale)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compress_grads(
    grads: Any, ef: ErrorFeedbackState
) -> Tuple[Any, ErrorFeedbackState]:
    """Quantize grads with error feedback: g' = Q(g + r); r' = (g + r) - g'."""

    def one(g, r):
        gf = g.astype(jnp.float32) + r
        q, scale = quantize_int8(gf)
        deq = dequantize_int8(q, scale)
        return deq.astype(g.dtype), gf - deq

    out = jax.tree.map(one, grads, ef.residual)
    new_g = jax.tree.map(lambda t: t[0], out, is_leaf=lambda t: isinstance(t, tuple))
    new_r = jax.tree.map(lambda t: t[1], out, is_leaf=lambda t: isinstance(t, tuple))
    return new_g, ErrorFeedbackState(residual=new_r)


def compressed_bytes(nbytes_bf16: int, bits: int = 8) -> int:
    """DCN bytes after compression (used by the simulator's comm model)."""
    return int(nbytes_bf16 * bits / 16)
