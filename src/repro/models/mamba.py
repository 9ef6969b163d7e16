"""Mamba-2 (state-space duality) mixer.

Implements the chunked SSD algorithm (Dao & Gu, 2024, section 6) in pure
JAX, chunk-parallel: the sequence is cut into chunks of Q steps and every
chunk is a batch entry of the same batched products.

* within a chunk, the quadratic ("attention-like") term: C.B^T formed once
  per group, gated per head by exp(L_i - L_j) of the within-chunk log-decay
  prefix L (masked to -inf above the diagonal before the exp), then
  multiplied into x;
* each chunk's own final state, sum_j exp(L_end - L_j) B_j x_j^T, for all
  chunks in one product;
* across chunks, the linear recurrence h <- exp(L_end) h + state carried
  over the chunk states alone, by one short ``lax.scan`` (unrolled);
* the carried-in state read out by C and decayed by exp(L).

Every chunk is in flight at once, so the working set is O(S * Q * H): the
gated C.B^T of all chunks.  The Pallas ``ssd_scan`` kernel
(``repro.kernels.ssd_scan``, forward only) streams the chunks instead.

Decode is the O(1) recurrent update: ``h = dA*h + dt*x (x) B; y = C.h + D*x``
— this is why the ``long_500k`` cell runs for SSM/hybrid archs.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, SSMConfig
from repro.models import flags
from repro.models.common import rmsnorm, scope
from repro.models.params import (
    ParamDef,
    const_init,
    fan_in_init,
    normal_init,
    ones_init,
    zeros_init,
)

Cache = Dict[str, jax.Array]

# chunks of the across-chunk state carry per loop trip: a rolled loop's
# per-trip cost outweighs its few elementwise ops, so short sequences
# (up to 8 chunks) run it as straight-line code
CARRY_UNROLL = 8


def _dims(cfg: ArchConfig) -> Tuple[int, int, int, int, int]:
    s = cfg.ssm
    assert s is not None
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim
    return d_in, H, s.head_dim, s.n_groups, s.d_state


def mamba_def(cfg: ArchConfig) -> Dict[str, ParamDef]:
    s = cfg.ssm
    d = cfg.d_model
    d_in, H, P_, G, N = _dims(cfg)
    return {
        "w_z": ParamDef((d, d_in), (None, "model"), fan_in_init()),
        "w_x": ParamDef((d, d_in), (None, "model"), fan_in_init()),
        "w_bc": ParamDef((d, 2 * G * N), (None, None), fan_in_init()),
        "w_dt": ParamDef((d, H), (None, "model"), fan_in_init()),
        "dt_bias": ParamDef((H,), ("model",), const_init(0.5), jnp.float32),
        # A in (-1, 0): A_log init ~ log(uniform[1,16]) => A = -exp(A_log)
        "A_log": ParamDef((H,), ("model",), const_init(0.9), jnp.float32),
        "D": ParamDef((H,), ("model",), ones_init(), jnp.float32),
        "conv_x": ParamDef((s.conv_width, d_in), (None, "model"), normal_init(0.1)),
        "conv_bc": ParamDef((s.conv_width, 2 * G * N), (None, None), normal_init(0.1)),
        "norm": ParamDef((d_in,), ("model",), ones_init(), jnp.float32),
        "w_out": ParamDef((d_in, d), ("model", None), fan_in_init()),
    }


def _causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv via shifted adds. x (B,S,C), w (W,C)."""
    W = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    S = x.shape[1]
    out = jnp.zeros_like(x)
    for i in range(W):
        out = out + pad[:, i : i + S, :] * w[i]
    return out


def _conv_step(window: jax.Array, x_new: jax.Array, w: jax.Array):
    """One decode step of the causal conv. window (B,W,C) holds the last W
    inputs (oldest first); returns (new_window, conv_out (B,C))."""
    window = jnp.concatenate([window[:, 1:], x_new[:, None, :]], axis=1)
    out = jnp.einsum("bwc,wc->bc", window, w)
    return window, out


def _proj_inputs(p, cfg, x):
    d_in, H, P_, G, N = _dims(cfg)
    z = jnp.einsum("bsd,de->bse", x, p["w_z"])
    xs = jnp.einsum("bsd,de->bse", x, p["w_x"])
    bc = jnp.einsum("bsd,de->bse", x, p["w_bc"])
    dt = jnp.einsum("bsd,dh->bsh", x.astype(jnp.float32), p["w_dt"].astype(jnp.float32))
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (B,S,H) fp32
    return z, xs, bc, dt


@scope("ssd_scan")
def ssd_chunked(
    x: jax.Array,  # (B, S, H, P) already dt-scaled *inputs* (dt*x)
    log_dA: jax.Array,  # (B, S, H) fp32, negative
    Bm: jax.Array,  # (B, S, G, N)
    Cm: jax.Array,  # (B, S, G, N)
    chunk: int,
    h_init: jax.Array | None = None,  # (B, H, N, P)
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan. Returns (y (B,S,H,P), final state (B,H,N,P))."""
    B, S, H, P_ = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    R = H // G  # heads per group
    Q = min(chunk, S)
    S_orig = S
    if S % Q:
        # pad to a chunk multiple: zero inputs with zero log-decay are exact
        # no-ops for the recurrence (h *= exp(0); += B.0 x 0)
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        log_dA = jnp.pad(log_dA, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = S + pad
    nc = S // Q
    K = nc * G
    f32 = jnp.float32

    def to_chunks(a):
        # (B, S, G, ...) -> (B, nc*G, Q, ...): every (chunk, group) is a
        # batch entry; a free reshape when G == 1
        a = a.reshape((B, nc, Q, G) + a.shape[3:])
        return jnp.moveaxis(a, 3, 2).reshape((B, K, Q) + a.shape[4:])

    xk = to_chunks(x.reshape(B, S, G, R, P_)).astype(f32)  # (B,K,Q,R,P)
    Bk = to_chunks(Bm).astype(f32)  # (B,K,Q,N)
    Ck = to_chunks(Cm).astype(f32)
    L = jnp.cumsum(to_chunks(log_dA.reshape(B, S, G, R)), axis=2)  # (B,K,Q,R) inclusive
    L_end = L[:, :, -1]  # (B,K,R): log-decay over the whole chunk
    if h_init is None:
        h_init = jnp.zeros((B, H, N, P_), f32)

    # ---- intra-chunk (quadratic in Q): C.B^T once per group ----
    CB = jnp.einsum("bkin,bkjn->bkij", Ck, Bk)  # (B,K,Q,Q)
    Lt = jnp.swapaxes(L, 2, 3)  # (B,K,R,Q)
    iq = jnp.arange(Q)
    mask = iq[:, None] >= iq[None, :]
    # mask BEFORE exp: exp of the (positive) upper triangle would overflow
    # and poison gradients through the 0*inf product.
    gate = jnp.exp(jnp.where(mask, Lt[..., :, None] - Lt[..., None, :], -jnp.inf))
    y_intra = jnp.einsum("bkrij,bkjrp->bkirp", CB[:, :, None] * gate, xk)

    # ---- each chunk's own state: decay from step j to the chunk's end ----
    seg = jnp.exp(L_end[:, :, None] - L)  # (B,K,Q,R)
    states = jnp.einsum("bkjn,bkjrp->bkrnp", Bk, xk * seg[..., None])

    # ---- across chunks: carry only the (B,H,N,P) states ----
    def carry(h, inp):
        st, dec = inp  # (B,G,R,N,P), (B,G,R)
        return h * jnp.exp(dec)[..., None, None] + st, h

    by_chunk = lambda a: jnp.moveaxis(a.reshape((B, nc, G) + a.shape[2:]), 1, 0)
    h0 = h_init.reshape(B, G, R, N, P_)
    h_final, h_prev = flags.scan(
        carry, h0, (by_chunk(states), by_chunk(L_end)), unroll=min(nc, CARRY_UNROLL)
    )
    h_prev = jnp.moveaxis(h_prev, 0, 1).reshape(B, K, R, N, P_)

    # ---- inter-chunk: contribution of the state entering each chunk ----
    y_inter = jnp.einsum("bkin,bkrnp->bkirp", Ck, h_prev) * jnp.exp(L)[..., None]
    y = (y_intra + y_inter).reshape(B, nc, G, Q, R, P_)
    y = jnp.moveaxis(y, 2, 3).reshape(B, S, H, P_)[:, :S_orig]
    return y, h_final.reshape(B, H, N, P_)


@scope("ssm")
def mamba_forward(
    p: Dict[str, jax.Array], cfg: ArchConfig, x: jax.Array
) -> jax.Array:
    """Full-sequence forward (train / prefill). x: (B, S, d_model)."""
    s = cfg.ssm
    d_in, H, P_, G, N = _dims(cfg)
    B, S, _ = x.shape
    z, xs, bc, dt = _proj_inputs(p, cfg, x)
    xs = jax.nn.silu(_causal_conv(xs, p["conv_x"]))
    bc = jax.nn.silu(_causal_conv(bc, p["conv_bc"]))
    Bm = bc[..., : G * N].reshape(B, S, G, N)
    Cm = bc[..., G * N :].reshape(B, S, G, N)
    xh = xs.reshape(B, S, H, P_)
    A = -jnp.exp(p["A_log"])  # (H,)
    log_dA = dt * A  # (B,S,H)
    y, _ = ssd_chunked(xh * dt[..., None], log_dA, Bm, Cm, s.chunk)
    y = y + xh.astype(jnp.float32) * p["D"][:, None]
    y = y.reshape(B, S, d_in).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = rmsnorm({"scale": p["norm"]}, y)
    return jnp.einsum("bse,ed->bsd", y, p["w_out"])


def mamba_prefill(
    p: Dict[str, jax.Array], cfg: ArchConfig, x: jax.Array
) -> Tuple[jax.Array, Cache]:
    """Full-sequence forward that also returns the decode cache (final SSD
    state + conv windows over the last ``conv_width`` raw inputs)."""
    s = cfg.ssm
    d_in, H, P_, G, N = _dims(cfg)
    B, S, _ = x.shape
    W = s.conv_width
    z, xs_raw, bc_raw, dt = _proj_inputs(p, cfg, x)
    xs = jax.nn.silu(_causal_conv(xs_raw, p["conv_x"]))
    bc = jax.nn.silu(_causal_conv(bc_raw, p["conv_bc"]))
    Bm = bc[..., : G * N].reshape(B, S, G, N)
    Cm = bc[..., G * N :].reshape(B, S, G, N)
    xh = xs.reshape(B, S, H, P_)
    A = -jnp.exp(p["A_log"])
    log_dA = dt * A
    y, h_final = ssd_chunked(xh * dt[..., None], log_dA, Bm, Cm, s.chunk)
    y = y + xh.astype(jnp.float32) * p["D"][:, None]
    y = y.reshape(B, S, d_in).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = rmsnorm({"scale": p["norm"]}, y)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"])
    cache = {
        "h": h_final,
        "conv_x": xs_raw[:, S - W :, :],
        "conv_bc": bc_raw[:, S - W :, :],
    }
    return out, cache


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def mamba_make_cache(cfg: ArchConfig, batch: int, dtype=jnp.bfloat16) -> Cache:
    s = cfg.ssm
    d_in, H, P_, G, N = _dims(cfg)
    return {
        "h": jnp.zeros((batch, H, N, P_), jnp.float32),
        "conv_x": jnp.zeros((batch, s.conv_width, d_in), dtype),
        "conv_bc": jnp.zeros((batch, s.conv_width, 2 * G * N), dtype),
    }


def mamba_cache_spec(cfg: ArchConfig) -> Dict[str, Any]:
    from jax.sharding import PartitionSpec as P

    return {
        "h": P("data", "model", None, None),
        "conv_x": P("data", None, "model"),
        "conv_bc": P("data", None, None),
    }


def mamba_decode(
    p: Dict[str, jax.Array], cfg: ArchConfig, x: jax.Array, cache: Cache
) -> Tuple[jax.Array, Cache]:
    """One-token recurrent step. x: (B, 1, d_model)."""
    d_in, H, P_, G, N = _dims(cfg)
    B = x.shape[0]
    z, xs, bc, dt = _proj_inputs(p, cfg, x)
    conv_x, xs1 = _conv_step(cache["conv_x"], xs[:, 0], p["conv_x"])
    conv_bc, bc1 = _conv_step(cache["conv_bc"], bc[:, 0], p["conv_bc"])
    xs1 = jax.nn.silu(xs1)
    bc1 = jax.nn.silu(bc1)
    Bm = bc1[..., : G * N].reshape(B, G, N)
    Cm = bc1[..., G * N :].reshape(B, G, N)
    rep = H // G
    if rep > 1:
        Bm, Cm = jnp.repeat(Bm, rep, axis=1), jnp.repeat(Cm, rep, axis=1)
    xh = xs1.reshape(B, H, P_).astype(jnp.float32)
    dt1 = dt[:, 0]  # (B,H)
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt1 * A)  # (B,H)
    h = cache["h"] * dA[..., None, None] + jnp.einsum(
        "bhn,bhp->bhnp", Bm.astype(jnp.float32), xh * dt1[..., None]
    )
    y = jnp.einsum("bhn,bhnp->bhp", Cm.astype(jnp.float32), h)
    y = y + xh * p["D"][:, None]
    y = y.reshape(B, 1, d_in).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = rmsnorm({"scale": p["norm"]}, y)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"])
    return out, {"h": h, "conv_x": conv_x, "conv_bc": conv_bc}
