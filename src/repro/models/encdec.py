"""Encoder–decoder model (SeamlessM4T backbone; audio frontend stubbed).

The encoder consumes precomputed frame embeddings (the w2v-BERT feature
extractor is a stub per the assignment); the decoder is a causal LM with
cross attention.  Serving caches the decoder self-attention K/V plus the
cross-attention K/V (computed once from the encoder memory).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import flags
from repro.models import params as pu
from repro.models.common import (
    attention as attention_fn,
    chunked_cross_entropy,
    embed,
    embedding_def,
    lm_head_def,
    rmsnorm,
    rmsnorm_def,
    swiglu,
    swiglu_def,
)


class EncDecModel:
    """Seamless-style encoder-decoder."""

    def __init__(
        self,
        cfg: ArchConfig,
        mesh: Optional[jax.sharding.Mesh] = None,
        batch_axes: Tuple[str, ...] = ("data",),
        q_chunk: int = 1024,
    ):
        assert cfg.enc_dec
        self.cfg = cfg
        self.mesh = mesh
        self.batch_axes = batch_axes
        self.q_chunk = q_chunk

    # -- defs --------------------------------------------------------------

    def _enc_layer_def(self):
        cfg = self.cfg
        return {
            "norm1": rmsnorm_def(cfg.d_model),
            "mixer": attn.gqa_def(cfg),
            "norm2": rmsnorm_def(cfg.d_model),
            "channel": swiglu_def(cfg.d_model, cfg.d_ff),
        }

    def _dec_layer_def(self):
        cfg = self.cfg
        return {
            "norm1": rmsnorm_def(cfg.d_model),
            "mixer": attn.gqa_def(cfg),
            "norm_x": rmsnorm_def(cfg.d_model),
            "cross": attn.cross_def(cfg),
            "norm2": rmsnorm_def(cfg.d_model),
            "channel": swiglu_def(cfg.d_model, cfg.d_ff),
        }

    def param_defs(self) -> Dict[str, Any]:
        cfg = self.cfg
        return {
            "embed": embedding_def(cfg.padded_vocab, cfg.d_model),
            "encoder": pu.stack(self._enc_layer_def(), cfg.encoder_layers),
            "decoder": pu.stack(self._dec_layer_def(), cfg.num_layers),
            "enc_norm": rmsnorm_def(cfg.d_model),
            "final_norm": rmsnorm_def(cfg.d_model),
            "head": lm_head_def(cfg.d_model, cfg.padded_vocab),
        }

    def init(self, key):
        return pu.init_params(self.param_defs(), key)

    def abstract_params(self):
        return pu.abstract_params(self.param_defs())

    def param_specs(self):
        return pu.partition_specs(self.param_defs())

    def _constrain(self, x):
        if self.mesh is None:
            return x
        spec = self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0]
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(spec, None, None))
        )

    def _decode_shard_fn(self, batch: int):
        if self.mesh is None:
            return None
        n_data = 1
        for a in self.batch_axes:
            n_data *= self.mesh.shape[a]
        baxes = self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0]
        b_entry = baxes if (batch % n_data == 0 and batch > 1) else None

        def shard(t, spec):
            entries = tuple(b_entry if e == "batch" else e for e in spec)
            return jax.lax.with_sharding_constraint(
                t, NamedSharding(self.mesh, P(*entries))
            )

        return shard

    # -- encoder ------------------------------------------------------------

    def encode(self, params, frames: jax.Array) -> jax.Array:
        """frames: precomputed frontend embeddings (B, F, d_model)."""
        cfg = self.cfg
        B, F, _ = frames.shape
        positions = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32), (B, F))
        x = self._constrain(frames.astype(jnp.bfloat16))

        def body(x, p):
            h = rmsnorm(p["norm1"], x)
            q, k, v = attn._gqa_qkv(p["mixer"], cfg, h, positions)
            o = attention_fn(q, k, v, causal=False, q_chunk=self.q_chunk)
            o = jnp.einsum(
                "bsh,hd->bsd", o.reshape(B, F, -1), p["mixer"]["wo"]
            )
            x = x + o
            x = x + swiglu(p["channel"], rmsnorm(p["norm2"], x))
            return self._constrain(x), None

        if cfg.remat != "none":
            body = jax.checkpoint(body)
        x, _ = flags.scan(body, x, params["encoder"])
        return rmsnorm(params["enc_norm"], x)

    # -- decoder (training) ---------------------------------------------------

    def _dec_block(self, p, x, positions, memory):
        cfg = self.cfg
        h = rmsnorm(p["norm1"], x)
        x = x + attn.gqa_forward(
            p["mixer"], cfg, h, positions, self.q_chunk, self.mesh, self.batch_axes
        )
        h = rmsnorm(p["norm_x"], x)
        mem_kv = attn.cross_memory_kv(p["cross"], cfg, memory)
        x = x + attn.cross_forward(p["cross"], cfg, h, mem_kv, self.q_chunk)
        x = x + swiglu(p["channel"], rmsnorm(p["norm2"], x))
        return self._constrain(x)

    def loss(
        self,
        params,
        tokens: jax.Array,
        labels: jax.Array,
        frontend_embeds: jax.Array,
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        B, S = tokens.shape
        memory = self.encode(params, frontend_embeds)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = self._constrain(embed(params["embed"], tokens))

        def body(x, p):
            return self._dec_block(p, x, positions, memory), None

        if cfg.remat != "none":
            body = jax.checkpoint(body)
        x, _ = flags.scan(body, x, params["decoder"])
        h = rmsnorm(params["final_norm"], x)
        ce = chunked_cross_entropy(params["head"]["w"], h, labels, cfg.vocab_size)
        return ce, {"ce": ce, "aux": jnp.zeros((), jnp.float32)}

    # -- serving --------------------------------------------------------------

    def make_cache(self, batch: int, max_len: int) -> Dict[str, Any]:
        cfg = self.cfg
        L, F = cfg.num_layers, cfg.frontend_positions
        Hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        self_c = attn.gqa_make_cache(cfg, batch, max_len)
        return {
            "self": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (L,) + a.shape), self_c
            ),
            "cross_k": jnp.zeros((L, batch, F, Hkv, hd), jnp.bfloat16),
            "cross_v": jnp.zeros((L, batch, F, Hkv, hd), jnp.bfloat16),
        }

    def cache_specs(self) -> Dict[str, Any]:
        baxes = self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0]
        kv = P(None, baxes, "model", None, None)
        return {
            "self": {s: kv for s in ("k", "v")},
            "cross_k": P(None, baxes, None, "model" if self.cfg.num_kv_heads % 16 == 0 else None, None),
            "cross_v": P(None, baxes, None, "model" if self.cfg.num_kv_heads % 16 == 0 else None, None),
        }

    def prefill(
        self, params, tokens: jax.Array, frontend_embeds: jax.Array,
        max_len: Optional[int] = None,
    ):
        """Encode + decoder prefill; returns (last logits, cache)."""
        cfg = self.cfg
        B, S = tokens.shape
        max_len = max_len or S
        memory = self.encode(params, frontend_embeds)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = self._constrain(embed(params["embed"], tokens))

        def body(x, p):
            h = rmsnorm(p["norm1"], x)
            _, k, v = attn._gqa_qkv(p["mixer"], cfg, h, positions)
            c = attn.gqa_make_cache(cfg, B, max_len, dtype=k.dtype)
            c = {
                "k": jax.lax.dynamic_update_slice_in_dim(c["k"], k, 0, axis=1),
                "v": jax.lax.dynamic_update_slice_in_dim(c["v"], v, 0, axis=1),
            }
            ck, cv = attn.cross_memory_kv(p["cross"], cfg, memory)
            x = self._dec_block(p, x, positions, memory)
            return x, {"self": c, "cross_k": ck, "cross_v": cv}

        x, caches = flags.scan(body, x, params["decoder"])
        h = rmsnorm(params["final_norm"], x)
        logits = jnp.einsum("bd,dv->bv", h[:, -1], params["head"]["w"])
        cache = {
            "self": caches["self"],
            "cross_k": caches["cross_k"],
            "cross_v": caches["cross_v"],
        }
        return logits, cache

    def decode_step(self, params, cache, tokens: jax.Array, cache_len: jax.Array):
        cfg = self.cfg
        B = tokens.shape[0]
        x = embed(params["embed"], tokens)

        shard_fn = self._decode_shard_fn(B)

        def body(x, scanned):
            p, self_c, ck, cv = scanned
            h = rmsnorm(p["norm1"], x)
            o, new_c = attn.gqa_decode(p["mixer"], cfg, h, self_c, cache_len, shard_fn)
            x = x + o
            h = rmsnorm(p["norm_x"], x)
            x = x + attn.cross_forward(p["cross"], cfg, h, (ck, cv), self.q_chunk)
            x = x + swiglu(p["channel"], rmsnorm(p["norm2"], x))
            return x, new_c

        x, new_self = flags.scan(
            body, x, (params["decoder"], cache["self"], cache["cross_k"], cache["cross_v"])
        )
        h = rmsnorm(params["final_norm"], x)
        logits = jnp.einsum("bsd,dv->bsv", h, params["head"]["w"])
        new_cache = dict(cache)
        new_cache["self"] = new_self
        return logits[:, 0], new_cache
