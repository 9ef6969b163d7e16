"""Shared model components: norms, RoPE, embeddings, MLPs, chunked attention.

Everything is written as plain functions over parameter dicts so the same
code path serves (a) smoke tests on 1 CPU device, (b) the 512-chip dry-run
under pjit, and (c) real training.  On a TPU causal self-attention runs the
splash flash kernel (``causal_attention``); elsewhere attention is *chunked
over queries* (lax.scan) so no S x S score tensor is ever materialized.
"""

from __future__ import annotations

import collections
import functools
import json
import math
import sys
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import splash_attention
from repro.models import flags
from repro.models.params import ParamDef, fan_in_init, normal_init, ones_init

# ---------------------------------------------------------------------------
# Layer-kind scopes
# ---------------------------------------------------------------------------


def scope(name: str) -> Callable[[Callable], Callable]:
    """Run the decorated function under ``jax.named_scope(name)``.

    The scope names the layer kind in every operation's ``op_name``
    metadata, through scans, remat and the backward pass, so a device
    trace can attribute time to it; the compiled program is otherwise the
    same.  ``jax.named_scope`` is looked up at each call.
    """

    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return scoped

    return wrap


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_def(dim: int) -> Dict[str, ParamDef]:
    return {"scale": ParamDef((dim,), (None,), ones_init(), jnp.float32)}


def rmsnorm(params: Dict[str, jax.Array], x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return y.astype(dtype)


def head_rmsnorm(scale: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head qk-norm (Qwen3): normalize the last (head_dim) axis."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponent)  # (head_dim//2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) with D even; positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)  # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., S, 1, d/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------


def embedding_def(vocab: int, d_model: int) -> Dict[str, ParamDef]:
    return {"table": ParamDef((vocab, d_model), ("model", None), normal_init(0.02))}


def embed(params: Dict[str, jax.Array], tokens: jax.Array) -> jax.Array:
    # one-hot matmul keeps the vocab-sharded table local (MXU-friendly gather)
    return params["table"][tokens]


def lm_head_def(d_model: int, vocab: int) -> Dict[str, ParamDef]:
    return {"w": ParamDef((d_model, vocab), (None, "model"), fan_in_init())}


@scope("head")
def chunked_cross_entropy(
    head_w: jax.Array,
    hidden: jax.Array,
    labels: jax.Array,
    vocab_size: int,
    chunk: int = 512,
) -> jax.Array:
    """Cross-entropy over a vocab-sharded head without materializing the
    full (B, S, V) logits in fp32: lax.scan over sequence chunks.

    ``labels`` uses -100 as the ignore index (padding / frontend slots).
    """
    B, S, D = hidden.shape
    chunk = min(chunk, S)
    n = S // chunk
    rem = S - n * chunk

    def chunk_loss(h, y):
        logits = jnp.einsum("bsd,dv->bsv", h, head_w).astype(jnp.float32)
        # mask padded vocab entries
        logits = jnp.where(
            jnp.arange(logits.shape[-1]) < vocab_size, logits, -1e30
        )
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.clip(y, 0, None)[..., None], axis=-1
        )[..., 0]
        valid = (y >= 0).astype(jnp.float32)
        return jnp.sum((logz - gold) * valid), jnp.sum(valid)

    def body(carry, xs):
        h, y = xs
        s, c = chunk_loss(h, y)
        return (carry[0] + s, carry[1] + c), None

    h_main = hidden[:, : n * chunk].reshape(B, n, chunk, D).swapaxes(0, 1)
    y_main = labels[:, : n * chunk].reshape(B, n, chunk).swapaxes(0, 1)
    (tot, cnt), _ = flags.scan(body, (jnp.zeros(()), jnp.zeros(())), (h_main, y_main))
    if rem:
        s, c = chunk_loss(hidden[:, n * chunk :], labels[:, n * chunk :])
        tot, cnt = tot + s, cnt + c
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_def(d_model: int, d_ff: int) -> Dict[str, ParamDef]:
    return {
        "gate": ParamDef((d_model, d_ff), (None, "model"), fan_in_init()),
        "up": ParamDef((d_model, d_ff), (None, "model"), fan_in_init()),
        "down": ParamDef((d_ff, d_model), ("model", None), fan_in_init()),
    }


@scope("mlp")
def swiglu(params: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, params["gate"])
    u = jnp.einsum("...d,df->...f", x, params["up"])
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, params["down"])


# ---------------------------------------------------------------------------
# Chunked (flash-style) attention — the pure-XLA hot path
# ---------------------------------------------------------------------------


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, Hkv, D) -> (B, S, Hkv*n_rep, D) for GQA."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


@scope("attention_core")
def attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,  # (B, Sk, Hkv, Dv)
    *,
    causal: bool,
    q_offset: Any = 0,  # position of q[0] relative to k[0] (int or scalar array)
    sliding_window: Optional[int] = None,
    kv_valid_len: Optional[jax.Array] = None,  # mask keys >= this position
    q_chunk: int = 1024,
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """Query-chunked attention: lax.scan over query blocks.

    Per block the (B, H, q_chunk, Sk) score tile is materialized, soft-maxed
    in fp32 and contracted with V — the whole-S x S tensor never exists.
    """
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    Dv = v.shape[-1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    k = _repeat_kv(k, H // Hkv)
    v = _repeat_kv(v, H // Hkv)
    Sk = k.shape[1]

    def block(qb: jax.Array, q_start: Any) -> jax.Array:
        # qb: (B, C, H, D)
        C = qb.shape[1]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k).astype(jnp.float32) * scale
        kpos = jnp.arange(Sk)
        qpos = q_start + q_offset + jnp.arange(C)
        mask = jnp.ones((C, Sk), dtype=bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if sliding_window is not None:
            mask &= kpos[None, :] > qpos[:, None] - sliding_window
        if kv_valid_len is not None:
            mask &= (kpos < kv_valid_len)[None, :]
        scores = jnp.where(mask[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    if Sq <= q_chunk:
        return block(q, 0)

    n = Sq // q_chunk
    rem = Sq - n * q_chunk
    qs = q[:, : n * q_chunk].reshape(B, n, q_chunk, H, D).swapaxes(0, 1)

    def body(_, xs):
        qb, i = xs
        return None, block(qb, i * q_chunk)

    _, out = flags.scan(body, None, (qs, jnp.arange(n)))
    out = out.swapaxes(0, 1).reshape(B, n * q_chunk, H, Dv)
    if rem:
        tail = block(q[:, n * q_chunk :], n * q_chunk)
        out = jnp.concatenate([out, tail], axis=1)
    return out


@scope("attention_core")
def banded_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: int,
    q_chunk: int = 1024,
) -> jax.Array:
    """Sliding-window attention that only *touches* the KV band.

    For each query chunk [t, t+C) the key range is [t - window, t + C); we
    slice it with dynamic_slice so compute/bytes scale with S*window rather
    than S^2.  Falls back to masked full attention when S <= window + chunk.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if Sk <= window + q_chunk or Sq != Sk:
        return attention(
            q, k, v, causal=True, sliding_window=window, q_chunk=q_chunk
        )
    Hkv = k.shape[2]
    k = _repeat_kv(k, H // Hkv)
    v = _repeat_kv(v, H // Hkv)
    scale = 1.0 / math.sqrt(D)
    band = window + q_chunk  # key slab covering one query chunk
    n = Sq // q_chunk

    def body(_, xs):
        qb, i = xs  # (B, C, H, D)
        t = i * q_chunk
        start = jnp.maximum(t + q_chunk - band, 0)
        kb = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, kb).astype(jnp.float32) * scale
        qpos = t + jnp.arange(q_chunk)
        kpos = start + jnp.arange(band)
        mask = (kpos[None, :] <= qpos[:, None]) & (
            kpos[None, :] > qpos[:, None] - window
        )
        scores = jnp.where(mask[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(vb.dtype)
        return None, jnp.einsum("bhqk,bkhd->bqhd", probs, vb)

    qs = q[:, : n * q_chunk].reshape(B, n, q_chunk, H, D).swapaxes(0, 1)
    _, out = flags.scan(body, None, (qs, jnp.arange(n)))
    out = out.swapaxes(0, 1).reshape(B, n * q_chunk, H, -1)
    if n * q_chunk < Sq:
        tail = attention(
            q[:, n * q_chunk :],
            k,
            v,
            causal=True,
            q_offset=n * q_chunk,
            sliding_window=window,
        )
        out = jnp.concatenate([out, tail], axis=1)
    return out


# ---------------------------------------------------------------------------
# Causal self-attention: the flash kernel where it applies, else XLA
# ---------------------------------------------------------------------------

# (path, why) -> how many traces of ``causal_attention`` took it
ATTENTION_PATHS: collections.Counter = collections.Counter()


def attention_path(
    platform: str,
    q_shape: Sequence[int],  # (B, Sq, H, D)
    k_shape: Sequence[int],  # (B, Sk, Hkv, D)
    v_shape: Sequence[int],  # (B, Sk, Hkv, Dv)
    shards: Tuple[int, int] = (1, 1),  # (batch shards, model shards)
) -> Tuple[str, str]:
    """``("kernel" | "xla", why)`` for causal self-attention of these shapes.

    The kernel (``kernels/splash_attention.py``) needs a TPU, as many keys
    as queries, a length that is a multiple of 128, one head dimension for
    q, k and v, and a batch and heads that split evenly over the mesh.
    """
    B, S, H, D = q_shape
    Sk, Hkv, Dk = k_shape[1], k_shape[2], k_shape[3]
    if platform != "tpu":
        return "xla", f"platform {platform}"
    if Sk != S:
        return "xla", f"{S} queries against {Sk} keys"
    if S % 128:
        return "xla", f"length {S} is not a multiple of 128"
    if not D == Dk == v_shape[3]:
        return "xla", f"head dims q {D}, k {Dk}, v {v_shape[3]}"
    n_batch, n_model = shards
    if B % n_batch or H % n_model or Hkv % n_model:
        return "xla", f"batch {B}, heads {H}/{Hkv} over {n_batch} x {n_model} shards"
    return "kernel", f"tpu, length {S}, head dim {D}"


def _platform(mesh: Optional[jax.sharding.Mesh]) -> str:
    devices = mesh.devices.flat if mesh is not None else jax.devices()
    return next(iter(devices)).platform


def _flash(q, k, v, window):
    """The kernel on (B, S, H, D) arrays, head-major inside."""
    o = splash_attention.flash_attention(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), window=window
    )
    return o.swapaxes(1, 2)


def causal_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, Hkv, D)
    v: jax.Array,  # (B, S, Hkv, Dv)
    *,
    window: Optional[int] = None,
    q_chunk: int = 1024,
    mesh: Optional[jax.sharding.Mesh] = None,
    batch_axes: Tuple[str, ...] = ("data",),
) -> jax.Array:
    """Causal self-attention over a full sequence (train / prefill), with
    keys older than ``window`` masked.

    Takes the flash kernel where ``attention_path`` allows, under a mesh
    through ``shard_map``: batch over ``batch_axes``, heads over ``model``
    (query heads of one group stay with their KV head, so nothing moves
    between chips), under the ``attention_core`` scope that the XLA path's
    ``banded_attention`` / ``attention`` carry.  Each trace counts its path
    in ``ATTENTION_PATHS`` and logs it on standard error.
    """
    shards = (1, 1)
    if mesh is not None:
        shards = (math.prod(mesh.shape[a] for a in batch_axes), mesh.shape["model"])
    path, why = attention_path(_platform(mesh), q.shape, k.shape, v.shape, shards)
    ATTENTION_PATHS[path, why] += 1
    print(
        "attention.path: "
        + json.dumps({"path": path, "why": why, "q": q.shape, "k": k.shape, "window": window}),
        file=sys.stderr,
        flush=True,
    )
    if path == "xla":
        if window is not None:
            return banded_attention(q, k, v, window=window, q_chunk=q_chunk)
        return attention(q, k, v, causal=True, q_chunk=q_chunk)
    flash = functools.partial(_flash, window=window)
    if mesh is not None:
        bspec = batch_axes if len(batch_axes) > 1 else batch_axes[0]
        spec = P(bspec, None, "model", None)
        flash = jax.shard_map(
            flash, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
        )
    with jax.named_scope("attention_core"):
        return flash(q, k, v)
