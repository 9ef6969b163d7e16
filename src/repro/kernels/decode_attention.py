"""Flash-decoding Pallas TPU kernel: one query token vs a long KV cache.

Decode attention is HBM-bandwidth bound (the whole cache is read once per
token), so the kernel's job is to stream K/V blocks through VMEM exactly
once with the online-softmax state in scratch.  Grid = (B x Hkv, S/bk):
each program handles all ``rep`` grouped q-heads of one kv head (loads the
kv block once for the whole group — the GQA bandwidth win), with the kv
axis innermost/sequential.

The kernel reads the cache head-major, ``(B, Hkv, S, D)``, so that each block
is a ``(bk, D)`` tile: the TPU compiler requires the last two block dims to
be tiled (multiples of 8 and 128) or whole, which a ``(1, D)`` slice of the
``(Hkv, D)`` trailing dims of the ``(B, S, Hkv, D)`` cache is not.  The
wrapper still takes the sequence-major cache and transposes it on every
call: a read and a write of the whole cache before the kernel reads it,
about three times the kernel's own HBM traffic.  The first change when a
model wires this kernel in is to keep that model's cache head-major and
make ``(B, Hkv, S, D)`` the wrapper's contract.

The valid cache length arrives via scalar prefetch (SMEM) so block masking
costs no VMEM traffic; blocks beyond ``valid_len`` are skipped entirely
(``pl.when``), which matters for partially-filled caches.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    valid_ref,  # SMEM (1,) int32 — scalar prefetch
    q_ref,  # (1, rep, d)
    k_ref, v_ref,  # (1, 1, bk, d)
    o_ref,  # (1, rep, d)
    m_ref, l_ref, acc_ref,  # scratch (rep, 1), (rep, 1), (rep, d)
    *,
    bk: int,
    nk: int,
    scale: float,
):
    ki = pl.program_id(1)
    k_start = ki * bk
    valid = valid_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k_start < valid)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # (rep, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (rep, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = kpos < valid  # (1, bk)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        vblk = v_ref[0, 0].astype(jnp.float32)  # (bk, d)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, vblk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,  # (B, H, D) one token per sequence
    k: jax.Array,  # (B, S, Hkv, D) cache (ring or linear)
    v: jax.Array,
    valid_len: jax.Array,  # scalar int32: number of valid cache entries
    *,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    rep = H // Hkv
    bk = min(block_k, S)
    assert S % bk == 0
    nk = S // bk
    scale = 1.0 / math.sqrt(D)
    # group q-heads by kv head: (B, Hkv, rep, D)
    qg = q.reshape(B, Hkv, rep, D)
    valid = jnp.asarray(valid_len, jnp.int32).reshape((1,))
    k = jnp.swapaxes(k, 1, 2)  # (B, Hkv, S, D)
    v = jnp.swapaxes(v, 1, 2)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, rep, D), lambda bh, ki, valid: (bh // Hkv, bh % Hkv, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda bh, ki, valid: (bh // Hkv, bh % Hkv, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda bh, ki, valid: (bh // Hkv, bh % Hkv, ki, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, rep, D), lambda bh, ki, valid: (bh // Hkv, bh % Hkv, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, bk=bk, nk=nk, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, D), q.dtype),
        interpret=interpret,
    )(valid, qg, k, v)
    return out.reshape(B, H, D)
