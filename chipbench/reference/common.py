"""Pieces shared by the references: matrix products at a stated precision,
RMSNorm and the cross-entropy."""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# largest finite values of the two float8 formats
E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def _round(x: jax.Array, dtype, top: float) -> jax.Array:
    """Round ``x`` to ``dtype`` under a per-tensor scale, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def _fp8(x: jax.Array) -> jax.Array:
    """float8 as it is trained with: operands in e4m3, their gradients in
    e5m2, each under its own per-tensor scale."""
    return _round(x, jnp.float8_e4m3fn, E4M3_MAX)


_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (_round(g, jnp.float8_e5m2, E5M2_MAX),))


def mm(spec: str, a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    """Two-operand einsum in float32.  ``precision`` "float32" is the
    reference; "fp8" rounds both operands to float8 first (the control)."""
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST, preferred_element_type=F32)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def cross_entropy_sum(
    h: jax.Array, head: jax.Array, labels: jax.Array, precision: str, block: int = 1024
) -> jax.Array:
    """Sum over positions of -log softmax(h @ head)[label], in blocks of
    positions so that the logits of one block are alive at a time.
    ``head`` is (d, vocab) over the real vocabulary only."""
    S = h.shape[1]

    @jax.checkpoint
    def part(hb, yb):
        logits = mm("bsd,dv->bsv", hb, head, precision)
        gold = jnp.take_along_axis(logits, yb[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    total = jnp.zeros((), F32)
    for s in range(0, S, block):
        total = total + part(h[:, s : s + block], labels[:, s : s + block])
    return total
