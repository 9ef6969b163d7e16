"""Decoder-only LM assembly for dense / MoE / SSM / hybrid / VLM families.

One :class:`Model` drives every assigned decoder-only architecture:

  * layers are grouped into scan groups (identical pytree structure inside a
    group) — e.g. DeepSeek-V3 = [3 dense] + [58 MoE], Jamba = 9 super-blocks
    of (ssm x4+attn+ssm x3 with alternating dense/MoE channel mixers);
  * each group is a single ``lax.scan`` over stacked parameters with
    ``jax.checkpoint`` (remat) around the block body — keeps the HLO small
    and activation memory O(layers x checkpoint inputs);
  * decode threads a per-group stacked cache through the same scan.

The class exposes ``loss`` (train), ``prefill`` and ``decode_step`` (serve),
plus congruent parameter/cache PartitionSpec trees for pjit.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import flags
from repro.models import mamba as mb
from repro.models import moe as moe_mod
from repro.models import params as pu
from repro.models.common import (
    chunked_cross_entropy,
    embed,
    embedding_def,
    lm_head_def,
    rmsnorm,
    rmsnorm_def,
    swiglu,
    swiglu_def,
)

MTP_LOSS_WEIGHT = 0.3


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One physical layer inside a scan group."""

    mixer: str  # "attn" | "ssm"
    channel: str  # "dense" | "moe" | "none"


def _layer_groups(cfg: ArchConfig) -> List[Tuple[str, int, Tuple[LayerSpec, ...]]]:
    """(group_name, repeat, per-repeat layer tuple) for scan-over-layers."""
    if cfg.hybrid_pattern is not None:
        period = len(cfg.hybrid_pattern)
        assert cfg.num_layers % period == 0
        layers = []
        for j, kind in enumerate(cfg.hybrid_pattern):
            channel = "moe" if cfg.is_moe_layer(j) else "dense"
            layers.append(LayerSpec(kind, channel))
        return [("blocks", cfg.num_layers // period, tuple(layers))]
    if cfg.family == "ssm":
        return [("ssm", cfg.num_layers, (LayerSpec("ssm", "none"),))]
    if cfg.moe is not None:
        k = cfg.moe.first_k_dense
        groups = []
        if k:
            groups.append(("dense", k, (LayerSpec("attn", "dense"),)))
        groups.append(("moe", cfg.num_layers - k, (LayerSpec("attn", "moe"),)))
        return groups
    return [("dense", cfg.num_layers, (LayerSpec("attn", "dense"),))]


class Model:
    """Decoder-only language model (all non-enc-dec assigned archs)."""

    def __init__(
        self,
        cfg: ArchConfig,
        mesh: Optional[jax.sharding.Mesh] = None,
        q_chunk: int = 1024,
    ):
        self.cfg = cfg
        self.mesh = mesh
        self.q_chunk = q_chunk
        self.groups = _layer_groups(cfg)

    # -- parameter definitions -------------------------------------------

    def _layer_def(self, spec: LayerSpec) -> Dict[str, Any]:
        cfg = self.cfg
        d: Dict[str, Any] = {"norm1": rmsnorm_def(cfg.d_model)}
        if spec.mixer == "attn":
            d["mixer"] = (
                attn.mla_def(cfg) if cfg.attention == "mla" else attn.gqa_def(cfg)
            )
        else:
            d["mixer"] = mb.mamba_def(cfg)
        if spec.channel != "none":
            d["norm2"] = rmsnorm_def(cfg.d_model)
            if spec.channel == "moe":
                d["channel"] = moe_mod.moe_def(cfg)
            else:
                d["channel"] = swiglu_def(cfg.d_model, cfg.d_ff)
        return d

    def param_defs(self) -> Dict[str, Any]:
        cfg = self.cfg
        defs: Dict[str, Any] = {
            "embed": embedding_def(cfg.padded_vocab, cfg.d_model),
            "final_norm": rmsnorm_def(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            defs["head"] = lm_head_def(cfg.d_model, cfg.padded_vocab)
        for name, n, layers in self.groups:
            group = {f"l{j}": self._layer_def(s) for j, s in enumerate(layers)}
            defs[name] = pu.stack(group, n)
        if cfg.mtp_depth:
            defs["mtp"] = {
                "proj": pu.ParamDef(
                    (2 * cfg.d_model, cfg.d_model), (None, None), pu.fan_in_init()
                ),
                "norm_h": rmsnorm_def(cfg.d_model),
                "norm_e": rmsnorm_def(cfg.d_model),
                "block": self._layer_def(LayerSpec("attn", "dense")),
            }
        return defs

    def init(self, key: jax.Array):
        return pu.init_params(self.param_defs(), key)

    def abstract_params(self):
        return pu.abstract_params(self.param_defs())

    def param_specs(self):
        return pu.partition_specs(self.param_defs())

    # -- forward ------------------------------------------------------------

    def _head_weight(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"]["table"].T
        return params["head"]["w"]

    def _block_forward(
        self, spec: LayerSpec, p: Dict[str, Any], x: jax.Array, positions: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        h = rmsnorm(p["norm1"], x)
        if spec.mixer == "attn":
            if cfg.attention == "mla":
                h = attn.mla_forward(p["mixer"], cfg, h, positions, self.q_chunk)
            else:
                h = attn.gqa_forward(p["mixer"], cfg, h, positions, self.q_chunk, self.mesh)
        else:
            h = mb.mamba_forward(p["mixer"], cfg, h)
        x = x + h
        if spec.channel != "none":
            h = rmsnorm(p["norm2"], x)
            if spec.channel == "moe":
                if self.mesh is not None:
                    h, aux = moe_mod.moe_forward(p["channel"], cfg, h, self.mesh)
                else:
                    h, aux = moe_mod.moe_forward_onehot(p["channel"], cfg, h)
            else:
                h = swiglu(p["channel"], h)
            x = x + h
        x = self._constrain(x)
        return x, aux

    def _constrain(self, x: jax.Array) -> jax.Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P("data", None, None))
        )

    def _decode_shard_fn(self, batch: int):
        """Sharding-constraint callback for decode attention ("batch" in a
        spec tuple maps to the data axis, dropped when indivisible)."""
        if self.mesh is None:
            return None
        n_data = self.mesh.shape["data"]
        b_entry = "data" if (batch % n_data == 0 and batch > 1) else None

        def shard(t, spec):
            entries = tuple(b_entry if e == "batch" else e for e in spec)
            return jax.lax.with_sharding_constraint(
                t, NamedSharding(self.mesh, P(*entries))
            )

        return shard

    def _remat(self, body):
        if self.cfg.remat != "none":
            return jax.checkpoint(body)  # full remat per scanned block
        return body

    def _scan_groups(
        self, params, x: jax.Array, positions: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """Run all layer groups; returns (hidden, total aux loss)."""
        total_aux = jnp.zeros((), jnp.float32)
        for name, n, layers in self.groups:

            def body(carry, layer_params, _layers=layers):
                h, aux_sum = carry
                for j, spec in enumerate(_layers):
                    h, aux = self._block_forward(
                        spec, layer_params[f"l{j}"], h, positions
                    )
                    aux_sum = aux_sum + aux
                return (h, aux_sum), None

            body = self._remat(body)
            (x, total_aux), _ = flags.scan(body, (x, total_aux), params[name])
        return x, total_aux

    def _embed_inputs(
        self, params, tokens: jax.Array, frontend_embeds: Optional[jax.Array]
    ) -> jax.Array:
        x = embed(params["embed"], tokens)
        if frontend_embeds is not None:
            npos = frontend_embeds.shape[1]
            x = jnp.concatenate(
                [frontend_embeds.astype(x.dtype), x[:, npos:]], axis=1
            )
        return self._constrain(x)

    def loss(
        self,
        params,
        tokens: jax.Array,
        labels: jax.Array,
        frontend_embeds: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = self._embed_inputs(params, tokens, frontend_embeds)
        if frontend_embeds is not None:
            npos = frontend_embeds.shape[1]
            labels = jnp.where(jnp.arange(S) < npos, -100, labels)
        x, aux = self._scan_groups(params, x, positions)
        h = rmsnorm(params["final_norm"], x)
        head_w = self._head_weight(params)
        ce = chunked_cross_entropy(head_w, h, labels, cfg.vocab_size)
        metrics = {"ce": ce, "aux": aux}
        loss = ce
        if cfg.moe is not None:
            loss = loss + cfg.moe.router_aux_weight * aux
        if cfg.mtp_depth:
            mtp_ce = self._mtp_loss(params, h, tokens, labels, positions)
            metrics["mtp_ce"] = mtp_ce
            loss = loss + MTP_LOSS_WEIGHT * mtp_ce
        return loss, metrics

    def _mtp_loss(self, params, h, tokens, labels, positions):
        """DeepSeek-V3 multi-token prediction (depth 1): predict t+2 from the
        main trunk state at t combined with the embedding of token t+1."""
        cfg = self.cfg
        p = params["mtp"]
        B, S = tokens.shape
        emb_next = embed(params["embed"], jnp.roll(tokens, -1, axis=1))
        z = jnp.concatenate(
            [rmsnorm(p["norm_h"], h), rmsnorm(p["norm_e"], emb_next)], axis=-1
        )
        z = jnp.einsum("bsd,de->bse", z, p["proj"])
        z, _ = self._block_forward(LayerSpec("attn", "dense"), p["block"], z, positions)
        # labels shifted one extra step; last position invalid
        mtp_labels = jnp.roll(labels, -1, axis=1)
        mtp_labels = jnp.where(jnp.arange(S) >= S - 1, -100, mtp_labels)
        return chunked_cross_entropy(
            self._head_weight(params), z, mtp_labels, cfg.vocab_size
        )

    # -- serving ------------------------------------------------------------

    def make_cache(self, batch: int, max_len: int) -> Dict[str, Any]:
        cfg = self.cfg
        cache: Dict[str, Any] = {}
        for name, n, layers in self.groups:
            per_layer = {}
            for j, spec in enumerate(layers):
                if spec.mixer == "attn":
                    if cfg.attention == "mla":
                        per_layer[f"l{j}"] = attn.mla_make_cache(cfg, batch, max_len)
                    else:
                        per_layer[f"l{j}"] = attn.gqa_make_cache(cfg, batch, max_len)
                else:
                    per_layer[f"l{j}"] = mb.mamba_make_cache(cfg, batch)
            cache[name] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n,) + a.shape), per_layer
            )
        return cache

    def cache_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        out: Dict[str, Any] = {}
        for name, n, layers in self.groups:
            per_layer = {}
            for j, spec in enumerate(layers):
                if spec.mixer == "attn":
                    s = (
                        attn.mla_cache_spec(cfg)
                        if cfg.attention == "mla"
                        else attn.gqa_cache_spec(cfg)
                    )
                else:
                    s = mb.mamba_cache_spec(cfg)
                per_layer[f"l{j}"] = s
            out[name] = jax.tree.map(
                lambda sp: P(*((None,) + tuple(sp))),
                per_layer,
                is_leaf=lambda v: isinstance(v, P),
            )
        return out

    def _block_decode(self, spec: LayerSpec, p, x, cache, cache_len):
        cfg = self.cfg
        shard_fn = self._decode_shard_fn(x.shape[0])
        h = rmsnorm(p["norm1"], x)
        if spec.mixer == "attn":
            if cfg.attention == "mla":
                h, cache = attn.mla_decode(
                    p["mixer"], cfg, h, cache, cache_len, shard_fn
                )
            else:
                h, cache = attn.gqa_decode(
                    p["mixer"], cfg, h, cache, cache_len, shard_fn
                )
        else:
            h, cache = mb.mamba_decode(p["mixer"], cfg, h, cache)
        x = x + h
        if spec.channel != "none":
            h = rmsnorm(p["norm2"], x)
            if spec.channel == "moe":
                h, _ = (
                    moe_mod.moe_forward(p["channel"], cfg, h, self.mesh)
                    if self.mesh is not None
                    else moe_mod.moe_forward_onehot(p["channel"], cfg, h)
                )
            else:
                h = swiglu(p["channel"], h)
            x = x + h
        return x, cache

    def decode_step(
        self, params, cache, tokens: jax.Array, cache_len: jax.Array
    ) -> Tuple[jax.Array, Dict[str, Any]]:
        """One decode step. tokens (B, 1) -> logits (B, padded_vocab).

        The stacked per-group cache rides in the scan CARRY (updated in
        place with a per-layer dynamic slice) rather than being emitted as
        stacked scan outputs — XLA can then alias the (donated) input cache
        with the output and the decode step allocates no second cache.
        """
        x = embed(params["embed"], tokens)
        new_cache: Dict[str, Any] = {}
        for name, n, layers in self.groups:

            def body(carry, layer_params, _layers=layers):
                x, cache_st, i = carry
                layer_cache = jax.tree.map(
                    lambda c: jax.lax.dynamic_index_in_dim(c, i, 0, keepdims=False),
                    cache_st,
                )
                upd = {}
                for j, spec in enumerate(_layers):
                    x, c = self._block_decode(
                        spec, layer_params[f"l{j}"], x, layer_cache[f"l{j}"], cache_len
                    )
                    upd[f"l{j}"] = c
                cache_st = jax.tree.map(
                    lambda c, nw: jax.lax.dynamic_update_index_in_dim(c, nw, i, 0),
                    cache_st,
                    upd,
                )
                return (x, cache_st, i + 1), None

            (x, new_cache[name], _), _ = flags.scan(
                body, (x, cache[name], jnp.zeros((), jnp.int32)), params[name]
            )
        h = rmsnorm(params["final_norm"], x)
        logits = jnp.einsum("bsd,dv->bsv", h, self._head_weight(params))
        return logits[:, 0], new_cache

    def prefill(
        self,
        params,
        tokens: jax.Array,
        frontend_embeds: Optional[jax.Array] = None,
        max_len: Optional[int] = None,
    ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Prefill: returns (last-position logits, populated cache).

        Attention caches are populated by recomputing K/V projections per
        layer group (cheap relative to the forward) so that serving decode
        can continue; SSM caches carry the final recurrent state.
        """
        cfg = self.cfg
        B, S = tokens.shape
        max_len = max_len or S
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = self._embed_inputs(params, tokens, frontend_embeds)
        cache: Dict[str, Any] = {}
        for name, n, layers in self.groups:

            def body(x, layer_params, _layers=layers):
                upd = {}
                for j, spec in enumerate(_layers):
                    x, c = self._prefill_block(
                        spec, layer_params[f"l{j}"], x, positions, max_len
                    )
                    upd[f"l{j}"] = c
                return x, upd

            body = self._remat(body)
            x, cache[name] = flags.scan(body, x, params[name])
        h = rmsnorm(params["final_norm"], x)
        logits = jnp.einsum("bd,dv->bv", h[:, -1], self._head_weight(params))
        return logits, cache

    def _prefill_block(self, spec: LayerSpec, p, x, positions, max_len):
        cfg = self.cfg
        B, S, _ = x.shape
        h = rmsnorm(p["norm1"], x)
        if spec.mixer == "ssm":
            out, c = mb.mamba_prefill(p["mixer"], cfg, h)
        elif cfg.attention == "mla":
            out = attn.mla_forward(p["mixer"], cfg, h, positions, self.q_chunk)
            ckv, kr = attn._mla_ckv(p["mixer"], cfg, h, positions)
            c = attn.mla_make_cache(cfg, B, max_len, dtype=ckv.dtype)
            c = {
                "ckv": jax.lax.dynamic_update_slice_in_dim(c["ckv"], ckv, 0, axis=1),
                "kr": jax.lax.dynamic_update_slice_in_dim(c["kr"], kr, 0, axis=1),
            }
        else:
            out = attn.gqa_forward(p["mixer"], cfg, h, positions, self.q_chunk, self.mesh)
            _, k, v = attn._gqa_qkv(p["mixer"], cfg, h, positions)
            c = attn.gqa_make_cache(cfg, B, max_len, dtype=k.dtype)
            W = c["k"].shape[1]
            parts = {"k": k, "v": v}
            if cfg.sliding_window is not None and S >= W:
                # keep the last W entries, rolled so slot = pos % W
                idx = (S - W + jnp.arange(W)) % W
                c = {
                    name: jnp.zeros_like(c[name]).at[:, idx].set(val[:, S - W :])
                    for name, val in parts.items()
                }
            else:
                c = {
                    name: jax.lax.dynamic_update_slice_in_dim(c[name], val, 0, axis=1)
                    for name, val in parts.items()
                }
        x = x + out
        if spec.channel != "none":
            hh = rmsnorm(p["norm2"], x)
            if spec.channel == "moe":
                hh, _ = (
                    moe_mod.moe_forward(p["channel"], cfg, hh, self.mesh)
                    if self.mesh is not None
                    else moe_mod.moe_forward_onehot(p["channel"], cfg, hh)
                )
            else:
                hh = swiglu(p["channel"], hh)
            x = x + hh
        x = self._constrain(x)
        return x, c
