"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

Implements the state-space-dual blocked algorithm: per (batch, head)
program, the chunk axis is the innermost (sequential) grid dimension and
the running state h (N x P fp32) lives in VMEM scratch; each chunk does

  intra:  y += (C B^T * decay-gate) x        (Q x Q MXU tile)
  inter:  y += (C h_prev) * exp(L)
  state:  h  = exp(L_Q) h_prev + (B * seg)^T x

with Q = chunk length (e.g. 256), so VMEM holds Q x max(N, P, Q) fp32
tiles (~1 MiB) and the HBM traffic is one pass over x/B/C per layer — the
property that makes SSD linear in sequence length on TPU.

The kernel reads its operands head-major (``(B, H, S, P)``, ``(B, G, S, N)``)
so that every block is a ``(Q, P)`` or ``(Q, N)`` tile: the TPU compiler
requires the last two block dims to be tiled (multiples of 8 and 128) or
whole.  The within-chunk log-decay prefix L is computed by the wrapper and
passed twice, as a ``(Q, 1)`` column and a ``(1, Q)`` row, because the
kernel needs both orientations and Mosaic lowers neither ``cumsum`` nor a
transpose of a vector.  The wrapper keeps the sequence-major contract of
``models/mamba.py`` and transposes x, B, C and y on every call, which adds
a read and a write of each to the one pass above; a model that wires this
kernel in should keep those head-major and drop the transposes.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    x_ref,  # (1, 1, Q, P)
    lc_ref,  # (1, 1, Q, 1) inclusive log-decay prefix, column
    lr_ref,  # (1, 1, 1, Q) the same prefix, row
    b_ref,  # (1, 1, Q, N)
    c_ref,  # (1, 1, Q, N)
    y_ref,  # (1, 1, Q, P)
    hout_ref,  # (1, 1, N, P) final state (written at last chunk)
    h_ref,  # scratch (N, P) fp32
    *,
    Q: int,
    nc: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)  # (Q, P)
    lc = lc_ref[0, 0]  # (Q, 1)
    lr = lr_ref[0, 0]  # (1, Q)
    b = b_ref[0, 0].astype(jnp.float32)  # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)  # (Q, N)
    # L at the chunk's end, (1, 1); a masked sum, since Mosaic cannot
    # broadcast a slice taken at the last lane
    last = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1
    l_last = jnp.sum(jnp.where(last, lr, 0.0), axis=1, keepdims=True)
    # ---- intra-chunk quadratic term ----
    scores = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q) = C_i . B_j
    iq = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    gate = jnp.exp(jnp.where(iq >= jq, lc - lr, -jnp.inf))
    y = jax.lax.dot_general(
        scores * gate, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, P)
    # ---- inter-chunk: carried state ----
    y += jax.lax.dot_general(
        c, h_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.exp(lc)
    # ---- state update ----
    seg = jnp.exp(l_last - lc)  # (Q, 1)
    h_new = h_ref[...] * jnp.exp(l_last) + jax.lax.dot_general(
        b * seg, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (N, P)
    h_ref[...] = h_new
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _final():
        hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


def ssd_scan(
    x: jax.Array,  # (B, S, H, P) dt-scaled inputs
    log_dA: jax.Array,  # (B, S, H) fp32
    Bm: jax.Array,  # (B, S, G, N)
    Cm: jax.Array,  # (B, S, G, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P) fp32, final state (B,H,N,P) fp32)."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert H % G == 0
    rep = H // G
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q

    # inclusive log-decay prefix within each chunk, head-major: (B, H, S)
    L = jnp.cumsum(
        log_dA.astype(jnp.float32).reshape(B, nc, Q, H), axis=2
    ).reshape(B, S, H).transpose(0, 2, 1)
    xh = jnp.swapaxes(x, 1, 2)  # (B, H, S, P)
    bh_ = jnp.swapaxes(Bm, 1, 2)  # (B, G, S, N)
    ch_ = jnp.swapaxes(Cm, 1, 2)

    kernel = functools.partial(_kernel, Q=Q, nc=nc)
    y, h = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda bh, ci: (bh // H, bh % H, ci, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda bh, ci: (bh // H, bh % H, ci, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda bh, ci: (bh // H, bh % H, 0, ci)),
            pl.BlockSpec((1, 1, Q, N), lambda bh, ci: (bh // H, (bh % H) // rep, ci, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda bh, ci: (bh // H, (bh % H) // rep, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda bh, ci: (bh // H, bh % H, ci, 0)),
            pl.BlockSpec((1, 1, N, P), lambda bh, ci: (bh // H, bh % H, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xh, L[..., None], L[:, :, None, :], bh_, ch_)
    return jnp.swapaxes(y, 1, 2), h
