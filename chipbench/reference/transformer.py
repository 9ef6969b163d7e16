"""Decoder-only transformer as in Llama/Mistral and H2O-Danube
(arXiv:2401.16818): pre-RMSNorm blocks of grouped-query attention with
rotary embeddings (rotate-half form) and a sliding window, then a SwiGLU
MLP; final RMSNorm and an untied output head.  Float32 throughout."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.common import F32, cross_entropy_sum, mm, rmsnorm

QUERY_BLOCK = 1024


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x (B, S, H, D): rotate (x1, x2) halves by position * theta^(-2i/D)."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv  # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, window, precision):
    """Causal attention within ``window`` keys, one block of queries at a
    time.  q (B, S, H, D); k, v (B, S, Hkv, D)."""
    B, S, H, D = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)  # query head i reads kv head i // rep
    v = jnp.repeat(v, rep, axis=2)
    w = S if window is None else window

    @jax.checkpoint
    def block(qb, kb, vb, q0, k0):
        s = mm("bqhd,bkhd->bhqk", qb, kb, precision) / math.sqrt(D)
        qpos = q0 + jnp.arange(qb.shape[1])[:, None]
        kpos = k0 + jnp.arange(kb.shape[1])[None, :]
        s = jnp.where((kpos <= qpos) & (kpos > qpos - w), s, -jnp.inf)
        return mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vb, precision)

    out = []
    for q0 in range(0, S, QUERY_BLOCK):
        q1 = min(q0 + QUERY_BLOCK, S)
        k0 = max(0, q0 - w + 1)
        out.append(block(q[:, q0:q1], k[:, k0:q1], v[:, k0:q1], q0, k0))
    return jnp.concatenate(out, axis=1)


def loss(params, tokens, labels, cfg: dict, precision: str) -> jax.Array:
    """Mean next-token cross-entropy of float32 ``params``."""
    eps = cfg["rms_norm_eps"]
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    V = cfg["vocab_size"]
    B, S = tokens.shape
    x = params["embed"]["table"][tokens]

    @jax.checkpoint
    def layer(x, p):
        h = rmsnorm(x, p["norm1"]["scale"], eps)
        a = p["mixer"]
        q = mm("bsd,dk->bsk", h, a["wq"], precision).reshape(B, S, H, hd)
        k = mm("bsd,dk->bsk", h, a["wk"], precision).reshape(B, S, Hkv, hd)
        v = mm("bsd,dk->bsk", h, a["wv"], precision).reshape(B, S, Hkv, hd)
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
        o = attention(q, k, v, cfg.get("sliding_window"), precision).reshape(B, S, H * hd)
        x = x + mm("bsk,kd->bsd", o, a["wo"], precision)
        h = rmsnorm(x, p["norm2"]["scale"], eps)
        f = p["channel"]
        g = mm("bsd,df->bsf", h, f["gate"], precision)
        u = mm("bsd,df->bsf", h, f["up"], precision)
        return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, f["down"], precision), None

    x, _ = jax.lax.scan(layer, x, params["dense"]["l0"])
    h = rmsnorm(x, params["final_norm"]["scale"], eps)
    return cross_entropy_sum(h, params["head"]["w"][:, :V], labels, precision) / (B * S)
