"""Causal flash attention with a backward pass: JAX's splash attention.

``jax.experimental.pallas.ops.tpu.splash_attention`` holds a forward, a dq
and a dkv Pallas kernel under one ``custom_vjp``.  They stream key blocks
through VMEM with the softmax statistics in f32, skip every block that the
mask hides entirely (the causal upper triangle, and keys older than the
window), and read KV head ``h // (H / Hkv)`` for query head ``h``, so K and V
are never repeated.  This module adapts the model's arrays to it:

  * layout: head-major ``(B, H, S, D)``, the kernel vmapped over the batch;
  * scale: the kernel takes none, so ``1/sqrt(D)`` is folded into q (in f32,
    rounded once to q's dtype);
  * mask: ``CausalMask`` when the window covers the sequence, else
    ``LocalMask`` with ``qpos - window < kpos <= qpos``;
  * blocks: one size for the forward, dq and dkv kernels, the largest of
    ``BLOCKS`` that divides S into at least four blocks (fewer would leave
    the causal mask no block to skip), else 128.  On a v5e at danube's
    4096 x 32 heads of 80, 1024 took 6.77 ms for the training composite
    (forward twice, dq, dkv), 512 took 7.35 ms and 256 15.4 ms.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

BLOCKS = (1024, 512, 256, 128)


def block_size(seq: int) -> int:
    """The largest of ``BLOCKS`` that divides ``seq`` into at least four
    blocks, else the smallest if it divides ``seq``."""
    for b in BLOCKS:
        if seq % b == 0 and seq // b >= 4:
            return b
    if seq % BLOCKS[-1]:
        raise ValueError(f"sequence {seq} is not a multiple of {BLOCKS[-1]}")
    return BLOCKS[-1]


def _kernel(heads: int, seq: int, window: Optional[int], interpret: bool):
    if window is None or window >= seq:
        mask = splash.CausalMask((seq, seq))
    else:
        mask = splash.LocalMask((seq, seq), window_size=(window - 1, 0), offset=0)
    b = block_size(seq)
    sizes = splash.BlockSizes(
        block_q=b,
        block_kv=b,
        block_kv_compute=b,
        block_q_dkv=b,
        block_kv_dkv=b,
        block_kv_dkv_compute=b,
        block_q_dq=b,
        block_kv_dq=b,
    )
    return splash.make_splash_mha(
        splash.MultiHeadMask([mask] * heads),
        block_sizes=sizes,
        head_shards=1,
        q_seq_shards=1,
        interpret=interpret,
    )


def flash_attention(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,  # (B, Hkv, S, D)
    *,
    window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal (and, for ``window`` < S, sliding-window) attention of
    head-major arrays; differentiable in q, k and v."""
    _, H, S, D = q.shape
    q = (q.astype(jnp.float32) * (1.0 / math.sqrt(D))).astype(q.dtype)
    return jax.vmap(_kernel(H, S, window, interpret))(q, k, v)
