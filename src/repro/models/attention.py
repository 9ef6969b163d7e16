"""Attention layers: GQA (+qk-norm, +sliding-window) and DeepSeek MLA.

Each layer exposes:

  ``*_def(cfg)``      parameter definitions (see ``models.params``),
  ``*_forward``       full-sequence forward (train / prefill),
  ``*_decode``        one-token decode against a cache,
  ``*_init_cache``    abstract/zero cache construction.

Caches are dicts of arrays whose sequence axis is sharded over the ``model``
mesh axis in the serving configs (the KV cache is by far the largest decode
buffer; sharding it over seq keeps the per-chip HBM bounded while the
collectives stay tiny — see DESIGN.md §5).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import (
    apply_rope,
    attention,
    causal_attention,
    head_rmsnorm,
    rmsnorm,
    scope,
)
from repro.models.params import ParamDef, fan_in_init, ones_init

Cache = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_def(cfg: ArchConfig, cross: bool = False) -> Dict[str, ParamDef]:
    d, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    kv_spec = "model" if Hkv % 16 == 0 else None  # replicate when indivisible
    defs: Dict[str, ParamDef] = {
        "wq": ParamDef((d, H * hd), (None, "model"), fan_in_init()),
        "wk": ParamDef((d, Hkv * hd), (None, kv_spec), fan_in_init()),
        "wv": ParamDef((d, Hkv * hd), (None, kv_spec), fan_in_init()),
        "wo": ParamDef((H * hd, d), ("model", None), fan_in_init()),
    }
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((hd,), (None,), ones_init(), jnp.float32)
        defs["k_norm"] = ParamDef((hd,), (None,), ones_init(), jnp.float32)
    return defs


def _gqa_qkv(
    p: Dict[str, jax.Array],
    cfg: ArchConfig,
    x: jax.Array,
    positions: jax.Array,
    rope: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, S, H, hd)
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(B, S, Hkv, hd)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(B, S, Hkv, hd)
    if cfg.qk_norm:
        q = head_rmsnorm(p["q_norm"], q)
        k = head_rmsnorm(p["k_norm"], k)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


@scope("attention")
def gqa_forward(
    p: Dict[str, jax.Array],
    cfg: ArchConfig,
    x: jax.Array,
    positions: jax.Array,
    q_chunk: int = 1024,
    mesh: Optional[jax.sharding.Mesh] = None,
    batch_axes: Tuple[str, ...] = ("data",),
) -> jax.Array:
    """Causal self-attention over a full sequence (train / prefill); the
    path is ``causal_attention``'s choice."""
    B, S, _ = x.shape
    q, k, v = _gqa_qkv(p, cfg, x, positions)
    o = causal_attention(
        q,
        k,
        v,
        window=cfg.sliding_window,
        q_chunk=q_chunk,
        mesh=mesh,
        batch_axes=batch_axes,
    )
    return jnp.einsum("bsh,hd->bsd", o.reshape(B, S, -1), p["wo"])


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-(token, head) symmetric int8 quantization of K/V entries.

    Halves decode HBM traffic and cache footprint (§Perf int8-KV
    optimization); scales are fp32 at 1/head_dim the volume (<4% overhead).
    """
    amax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1), 1e-8)
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def gqa_make_cache(
    cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16
) -> Cache:
    Hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    if cfg.sliding_window is not None:
        max_len = min(max_len, cfg.sliding_window)  # ring buffer
    shape = (batch, max_len, Hkv, hd)
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1], jnp.float32),
            "v_scale": jnp.zeros(shape[:-1], jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_cache_spec(cfg: ArchConfig, batch_axes: Any) -> Dict[str, Any]:
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axes, "model", None, None)
    out = {"k": spec, "v": spec}
    if cfg.kv_cache_dtype == "int8":
        out["k_scale"] = P(batch_axes, "model", None)
        out["v_scale"] = P(batch_axes, "model", None)
    return out


def gqa_decode(
    p: Dict[str, jax.Array],
    cfg: ArchConfig,
    x: jax.Array,  # (B, 1, d)
    cache: Cache,
    cache_len: jax.Array,  # scalar: number of tokens already cached
    shard_fn=None,  # optional fn(tensor, spec_tuple) -> sharding-constrained tensor
) -> Tuple[jax.Array, Cache]:
    B = x.shape[0]
    H, hd = cfg.num_heads, cfg.resolved_head_dim
    positions = jnp.full((B, 1), cache_len, dtype=jnp.int32)
    q, k_new, v_new = _gqa_qkv(p, cfg, x, positions)
    if shard_fn is not None:
        # decode runs the seq-sharded attention strategy: the (tiny) query is
        # replicated over the model axis while the cache stays sharded on its
        # sequence dim — without this, SPMD resolves the q(heads)/k(seq)
        # conflict by replicating the whole cache (HBM blow-up).
        q = shard_fn(q, ("batch", None, None, None))
        k_new = shard_fn(k_new, ("batch", None, None, None))
        v_new = shard_fn(v_new, ("batch", None, None, None))
    W = cache["k"].shape[1]
    slot = cache_len % W if cfg.sliding_window is not None else cache_len
    new_cache: Cache = {}
    if cfg.kv_cache_dtype == "int8":
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, slot, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, slot, axis=1)
        ksc = jax.lax.dynamic_update_slice_in_dim(cache["k_scale"], ks, slot, axis=1)
        vsc = jax.lax.dynamic_update_slice_in_dim(cache["v_scale"], vs, slot, axis=1)
        new_cache = {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc}
        # on TPU the dequant fuses into the attention matmul stream (HBM
        # reads stay int8); here it materializes for the XLA fallback
        k = dequantize_kv(kc, ksc, k_new.dtype)
        v = dequantize_kv(vc, vsc, v_new.dtype)
    else:
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1)
        new_cache = {"k": k, "v": v}
    if shard_fn is not None:
        k = shard_fn(k, ("batch", "model", None, None))
        v = shard_fn(v, ("batch", "model", None, None))
    valid = jnp.minimum(cache_len + 1, W)
    # grouped-query attention as a grouped einsum: never materializes the
    # repeated KV (memory) and keeps the seq-sharded strategy (no resharding
    # pressure from the head-sharded wo projection).
    Hkv = cfg.num_kv_heads
    rep = H // Hkv
    q2 = q.reshape(B, Hkv, rep, hd)  # q head i uses kv head i // rep
    scores = jnp.einsum("bkrd,bskd->bkrs", q2, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    smask = (jnp.arange(W) < valid)[None, None, None, :]
    scores = jnp.where(smask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkrs,bskd->bkrd", probs, v)
    if shard_fn is not None:
        o = shard_fn(o, ("batch", None, None, None))
    out = jnp.einsum("bsh,hd->bsd", o.reshape(B, 1, H * hd), p["wo"])
    return out, new_cache


# ---------------------------------------------------------------------------
# Cross attention (enc-dec decoder layers)
# ---------------------------------------------------------------------------


def cross_def(cfg: ArchConfig) -> Dict[str, ParamDef]:
    return gqa_def(cfg)


def cross_forward(
    p: Dict[str, jax.Array],
    cfg: ArchConfig,
    x: jax.Array,  # decoder hidden (B, Sq, d)
    memory_kv: Tuple[jax.Array, jax.Array],  # precomputed (k, v) of encoder memory
    q_chunk: int = 1024,
) -> jax.Array:
    B, Sq, _ = x.shape
    H, hd = cfg.num_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, Sq, H, hd)
    k, v = memory_kv
    o = attention(q, k, v, causal=False, q_chunk=q_chunk)
    return jnp.einsum("bsh,hd->bsd", o.reshape(B, Sq, -1), p["wo"])


def cross_memory_kv(
    p: Dict[str, jax.Array], cfg: ArchConfig, memory: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Precompute cross-attention K/V once per request (encoder output)."""
    B, Sk, _ = memory.shape
    Hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    k = jnp.einsum("bsd,dh->bsh", memory, p["wk"]).reshape(B, Sk, Hkv, hd)
    v = jnp.einsum("bsd,dh->bsh", memory, p["wv"]).reshape(B, Sk, Hkv, hd)
    return k, v


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_def(cfg: ArchConfig) -> Dict[str, ParamDef]:
    m = cfg.mla
    assert m is not None
    d, H = cfg.d_model, cfg.num_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    defs: Dict[str, ParamDef] = {}
    if m.q_lora_rank:
        defs["w_dq"] = ParamDef((d, m.q_lora_rank), (None, None), fan_in_init())
        defs["q_norm"] = ParamDef((m.q_lora_rank,), (None,), ones_init(), jnp.float32)
        defs["w_uq"] = ParamDef(
            (m.q_lora_rank, H * qk_head), (None, "model"), fan_in_init()
        )
    else:
        defs["w_uq"] = ParamDef((d, H * qk_head), (None, "model"), fan_in_init())
    defs["w_dkv"] = ParamDef(
        (d, m.kv_lora_rank + m.qk_rope_head_dim), (None, None), fan_in_init()
    )
    defs["kv_norm"] = ParamDef((m.kv_lora_rank,), (None,), ones_init(), jnp.float32)
    defs["w_ukv"] = ParamDef(
        (m.kv_lora_rank, H * (m.qk_nope_head_dim + m.v_head_dim)),
        (None, "model"),
        fan_in_init(),
    )
    defs["wo"] = ParamDef((H * m.v_head_dim, d), ("model", None), fan_in_init())
    return defs


def _mla_q(p, cfg, x, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank:
        cq = rmsnorm({"scale": p["q_norm"]}, jnp.einsum("bsd,dr->bsr", x, p["w_dq"]))
        q = jnp.einsum("bsr,rh->bsh", cq, p["w_uq"]).reshape(B, S, H, qk_head)
    else:
        q = jnp.einsum("bsd,dh->bsh", x, p["w_uq"]).reshape(B, S, H, qk_head)
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim :], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(p, cfg, x, positions):
    """Compressed KV latent + decoupled rope key (what the cache stores)."""
    m = cfg.mla
    dkv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    ckv = rmsnorm({"scale": p["kv_norm"]}, dkv[..., : m.kv_lora_rank])
    k_rope = dkv[..., m.kv_lora_rank :][:, :, None, :]  # (B,S,1,rope)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0, :]
    return ckv, k_rope


@scope("attention")
def mla_forward(
    p: Dict[str, jax.Array],
    cfg: ArchConfig,
    x: jax.Array,
    positions: jax.Array,
    q_chunk: int = 1024,
) -> jax.Array:
    """Training / prefill path: expand the latent into per-head K/V."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    ckv, k_rope = _mla_ckv(p, cfg, x, positions)
    kv = jnp.einsum("bsr,rh->bsh", ckv, p["w_ukv"]).reshape(
        B, S, H, m.qk_nope_head_dim + m.v_head_dim
    )
    k_nope, v = kv[..., : m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim :]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], q_rope.shape[:3] + (m.qk_rope_head_dim,))],
        axis=-1,
    )
    o = attention(
        q,
        k,
        v,
        causal=True,
        q_chunk=q_chunk,
        softmax_scale=1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim),
    )
    return jnp.einsum("bsh,hd->bsd", o.reshape(B, S, -1), p["wo"])


def mla_make_cache(
    cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16
) -> Cache:
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "kr": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
    }


def mla_cache_spec(cfg: ArchConfig, batch_axes: Any) -> Dict[str, Any]:
    from jax.sharding import PartitionSpec as P

    return {"ckv": P(batch_axes, "model", None), "kr": P(batch_axes, "model", None)}


def mla_decode(
    p: Dict[str, jax.Array],
    cfg: ArchConfig,
    x: jax.Array,  # (B, 1, d)
    cache: Cache,
    cache_len: jax.Array,
    shard_fn=None,
) -> Tuple[jax.Array, Cache]:
    """Weight-absorbed decode: attention runs in the 512-d latent space and
    the cache stays compressed — the core MLA serving win."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    positions = jnp.full((B, 1), cache_len, dtype=jnp.int32)
    q_nope, q_rope = _mla_q(p, cfg, x, positions)  # (B,1,H,*)
    ckv_new, kr_new = _mla_ckv(p, cfg, x, positions)
    if shard_fn is not None:  # see gqa_decode: seq-sharded decode strategy
        q_nope = shard_fn(q_nope, ("batch", None, None, None))
        q_rope = shard_fn(q_rope, ("batch", None, None, None))
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv_new, cache_len, axis=1)
    kr = jax.lax.dynamic_update_slice_in_dim(cache["kr"], kr_new, cache_len, axis=1)
    if shard_fn is not None:
        ckv = shard_fn(ckv, ("batch", "model", None))
        kr = shard_fn(kr, ("batch", "model", None))

    w_ukv = p["w_ukv"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim)
    w_uk = w_ukv[..., : m.qk_nope_head_dim]  # (r, H, nope)
    w_uv = w_ukv[..., m.qk_nope_head_dim :]  # (r, H, v)

    # absorb: q in latent space
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)  # (B,1,H,r)
    scores = jnp.einsum("bqhr,bsr->bhqs", q_lat, ckv) + jnp.einsum(
        "bqhe,bse->bhqs", q_rope, kr
    )
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    scores = scores.astype(jnp.float32) * scale
    S = ckv.shape[1]
    valid = (jnp.arange(S) < cache_len + 1)[None, None, None, :]
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(ckv.dtype)
    o_lat = jnp.einsum("bhqs,bsr->bqhr", probs, ckv)  # (B,1,H,r)
    if shard_fn is not None:
        o_lat = shard_fn(o_lat, ("batch", None, None, None))
    o = jnp.einsum("bqhr,rhv->bqhv", o_lat, w_uv)  # (B,1,H,v)
    out = jnp.einsum("bsh,hd->bsd", o.reshape(B, 1, -1), p["wo"])
    return out, {"ckv": ckv, "kr": kr}
