"""Mixture-of-experts layer with expert-parallel dispatch.

Two dispatch implementations share one router:

``sort``  (production): per-data-shard sort-based dispatch built inside a
    ``jax.shard_map`` (local argsort + scatter — *no* collectives inside);
    the expert-parallel resharding ``(shard, E, C, D) -> (E, shard, C, D)``
    is expressed as a sharding constraint so XLA lowers exactly one
    all-to-all each way.  Per-chip dispatch buffers stay at
    ``E_local * C_local * D`` — this is what makes the 256-expert
    DeepSeek-V3 cell fit (a dense one-hot dispatch tensor would be ~4e10
    elements at the assigned shapes).

``onehot`` (oracle): textbook dense one-hot einsum dispatch.  Used by the
    correctness tests as the reference the sort path must match bit-for-bit
    (same capacity/dropping semantics) and by tiny smoke configs.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, MoEConfig
from repro.models.common import swiglu, swiglu_def
from repro.models.params import ParamDef, fan_in_init, normal_init


def moe_def(cfg: ArchConfig) -> Dict[str, ParamDef]:
    m = cfg.moe
    assert m is not None
    d, f, E = cfg.d_model, m.d_ff_expert, m.num_experts
    defs: Dict[str, ParamDef] = {
        "router": ParamDef((d, E), (None, None), normal_init(0.02), jnp.float32),
        "gate": ParamDef((E, d, f), ("model", None, None), fan_in_init()),
        "up": ParamDef((E, d, f), ("model", None, None), fan_in_init()),
        "down": ParamDef((E, f, d), ("model", None, None), fan_in_init()),
    }
    if m.num_shared_experts:
        defs["shared"] = swiglu_def(d, m.num_shared_experts * f)
    return defs


def _capacity(tokens_per_shard: int, m: MoEConfig) -> int:
    c = math.ceil(tokens_per_shard * m.top_k / m.num_experts * m.capacity_factor)
    return max(8, (c + 7) // 8 * 8)


def router_probs(
    p: Dict[str, jax.Array], x: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Softmax router with top-k renormalized combine weights.

    Returns (probs fp32 (T.., E), topk weights (.., k), topk idx (.., k)).
    """
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    return probs


def aux_load_balance_loss(probs: jax.Array, idx: jax.Array, E: int) -> jax.Array:
    """Switch-style load-balancing loss: E * sum_e f_e * p_e."""
    flat_idx = idx.reshape(-1)
    f = jnp.zeros((E,), jnp.float32).at[flat_idx].add(1.0)
    f = f / jnp.maximum(flat_idx.size, 1)
    pbar = jnp.mean(probs.reshape(-1, E), axis=0)
    return E * jnp.sum(f * pbar)


# ---------------------------------------------------------------------------
# Reference dispatch (dense one-hot) — the oracle
# ---------------------------------------------------------------------------


def moe_forward_onehot(
    p: Dict[str, jax.Array], cfg: ArchConfig, x: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """(B, S, D) -> (B, S, D), aux_loss.  Capacity semantics identical to
    the sort path *for a single shard* (tests compare them on 1 device)."""
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    probs = router_probs(p, xt)
    w, idx = jax.lax.top_k(probs, m.top_k)  # (T, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    C = _capacity(T, m)
    E = m.num_experts
    # slot of token-choice (t, j) within its expert, in flat (t*k+j) order
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # (T, k, E)
    flat = onehot.reshape(T * m.top_k, E)
    slot = jnp.cumsum(flat, axis=0) * flat - 1  # (T*k, E), -1 where absent
    slot = jnp.max(slot, axis=-1).reshape(T, m.top_k)
    keep = (slot >= 0) & (slot < C)
    disp = (
        jax.nn.one_hot(idx, E, dtype=x.dtype)[..., None]
        * jax.nn.one_hot(jnp.where(keep, slot, C), C + 1, dtype=x.dtype)[:, :, None, :]
    )  # (T, k, E, C+1)
    disp = disp[..., :C]
    buf = jnp.einsum("td,tkec->ecd", xt, disp)  # (E, C, D)
    h = jnp.einsum("ecd,edf->ecf", buf, p["gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["up"])
    out_e = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, p["down"])
    combine = disp * w.astype(x.dtype)[..., None, None]
    out = jnp.einsum("ecd,tkec->td", out_e, combine)
    aux = aux_load_balance_loss(probs, idx, E)
    out = out.reshape(B, S, D)
    if m.num_shared_experts:
        out = out + swiglu(p["shared"], x)
    return out, aux


# ---------------------------------------------------------------------------
# Production dispatch: shard-local sort + one all-to-all each way
# ---------------------------------------------------------------------------


def _local_dispatch(xt, idx, C, E):
    """Pure shard-local token->expert-buffer scatter.

    xt (T, D); idx (T, k) -> buf (E*C+1, D), dest (T*k,) row ids (trash=E*C).
    """
    T, D = xt.shape
    k = idx.shape[1]
    flat_e = idx.reshape(-1)  # (T*k,) in token-major order
    # stable sort by expert; position within expert = rank - first_rank(e)
    order = jnp.argsort(flat_e, stable=True)
    ranks = jnp.zeros((T * k,), jnp.int32).at[order].set(jnp.arange(T * k, dtype=jnp.int32))
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    slot = ranks - starts[flat_e]
    dest = jnp.where(slot < C, flat_e * C + slot, E * C)  # overflow -> trash row
    buf = jnp.zeros((E * C + 1, D), xt.dtype)
    rows = jnp.repeat(jnp.arange(T), k)
    buf = buf.at[dest].add(xt[rows])
    return buf, dest


def moe_forward(
    p: Dict[str, jax.Array],
    cfg: ArchConfig,
    x: jax.Array,
    mesh: jax.sharding.Mesh,
) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE forward. x: (B, S, D) sharded on batch."""
    m = cfg.moe
    B, S, D = x.shape
    E = m.num_experts
    n_shards = mesh.shape["data"]
    if B % n_shards:
        # tiny-token path (e.g. batch-1 long-context decode): the dense
        # one-hot dispatch is cheaper than any resharding at this size.
        return moe_forward_onehot(p, cfg, x)
    T_local = (B // n_shards) * S
    C = _capacity(T_local, m)

    xt = x.reshape(B * S, D)
    probs = router_probs(p, xt)
    w, idx = jax.lax.top_k(probs, m.top_k)
    w = (w / jnp.sum(w, axis=-1, keepdims=True)).astype(x.dtype)
    aux = aux_load_balance_loss(probs, idx, E)

    def dispatch(xt_l, idx_l):
        buf, dest = _local_dispatch(xt_l, idx_l, C, E)
        return buf[None], dest[None]  # add shard dim

    buf, dest = jax.shard_map(
        dispatch,
        mesh=mesh,
        in_specs=(P("data", None), P("data", None)),
        out_specs=(P("data", None, None), P("data", None)),
    )(xt, idx)
    # buf: (shards, E*C+1, D) sharded on dim0 -> expert-major (E, shards, C, D)
    grid_spec = P("model", "data", None, None)
    grid = buf[:, : E * C, :].reshape(n_shards, E, C, D)
    grid = jnp.swapaxes(grid, 0, 1)
    grid = jax.lax.with_sharding_constraint(
        grid, jax.sharding.NamedSharding(mesh, grid_spec)
    )  # <- the forward all-to-all
    h = jnp.einsum("escd,edf->escf", grid, p["gate"])
    u = jnp.einsum("escd,edf->escf", grid, p["up"])
    y = jnp.einsum("escf,efd->escd", jax.nn.silu(h) * u, p["down"])
    y = jax.lax.with_sharding_constraint(
        y, jax.sharding.NamedSharding(mesh, grid_spec)
    )
    y = jnp.swapaxes(y, 0, 1).reshape(n_shards, E * C, D)
    y = jax.lax.with_sharding_constraint(
        y, jax.sharding.NamedSharding(mesh, P("data", None, None))
    )  # <- the return all-to-all

    def combine(y_l, dest_l, w_l):
        y_l, dest_l, w_l = y_l[0], dest_l[0], w_l  # drop shard dim
        y_pad = jnp.concatenate([y_l, jnp.zeros((1, D), y_l.dtype)], axis=0)
        rows = y_pad[dest_l].reshape(-1, cfg.moe.top_k, D)  # (T, k, D)
        return jnp.einsum("tkd,tk->td", rows, w_l.astype(y_l.dtype))

    out = jax.shard_map(
        combine,
        mesh=mesh,
        in_specs=(P("data", None, None), P("data", None), P("data", None)),
        out_specs=P("data", None),
    )(y, dest, w)
    out = out.reshape(B, S, D)
    if m.num_shared_experts:
        out = out + swiglu(p["shared"], x)
    return out, aux
