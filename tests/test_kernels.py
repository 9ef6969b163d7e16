"""Pallas kernel correctness: shape/dtype sweeps against the ref.py
oracles, executed in interpret mode on CPU (assignment requirement)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, splash_attention


def _arr(rng, *shape, dtype=jnp.bfloat16):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _assert_close(a, b, dtype):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(a, b, atol=tol, rtol=tol)


ATTN_SHAPES = [
    # (B, H, Hkv, S, D)
    (1, 1, 1, 128, 64),
    (2, 4, 2, 256, 64),
    (1, 8, 8, 128, 128),  # MHA
    (2, 4, 1, 256, 32),  # MQA
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_causal(shape, dtype, rng):
    B, H, Hkv, S, D = shape
    q = _arr(rng, B, H, S, D, dtype=dtype)
    k = _arr(rng, B, Hkv, S, D, dtype=dtype)
    v = _arr(rng, B, Hkv, S, D, dtype=dtype)
    out = splash_attention.flash_attention(q, k, v, interpret=True)
    exp = ref.attention_ref(q, k, v, causal=True)
    _assert_close(out, exp, dtype)


@pytest.mark.parametrize("window", [32, 64, 1024])
def test_flash_attention_sliding_window(window, rng):
    q = _arr(rng, 1, 4, 256, 64)
    k = _arr(rng, 1, 2, 256, 64)
    v = _arr(rng, 1, 2, 256, 64)
    out = splash_attention.flash_attention(q, k, v, window=window, interpret=True)
    exp = ref.attention_ref(q, k, v, causal=True, window=window)
    _assert_close(out, exp, jnp.bfloat16)


@pytest.mark.parametrize(
    "B,H,Hkv,S,D,valid",
    [
        (1, 2, 1, 256, 64, 256),
        (2, 4, 2, 512, 64, 300),
        (1, 8, 8, 256, 128, 1),
        (2, 8, 2, 1024, 64, 700),
    ],
)
def test_decode_attention(B, H, Hkv, S, D, valid, rng):
    q = _arr(rng, B, H, D)
    k = _arr(rng, B, S, Hkv, D)
    v = _arr(rng, B, S, Hkv, D)
    vl = jnp.asarray(valid, jnp.int32)
    out = ops.decode_attention(q, k, v, vl, backend="interpret")
    exp = ref.decode_attention_ref(q, k, v, vl)
    _assert_close(out, exp, jnp.bfloat16)


@pytest.mark.parametrize(
    "B,S,H,P,G,N,chunk",
    [
        (1, 64, 2, 16, 1, 8, 16),
        (2, 128, 4, 16, 2, 8, 32),
        (1, 256, 4, 32, 1, 16, 64),
        (1, 128, 8, 64, 1, 16, 128),
    ],
)
def test_ssd_scan(B, S, H, P, G, N, chunk, rng):
    x = _arr(rng, B, S, H, P, dtype=jnp.float32)
    log_dA = -jnp.abs(_arr(rng, B, S, H, dtype=jnp.float32)) * 0.1
    Bm = _arr(rng, B, S, G, N, dtype=jnp.float32)
    Cm = _arr(rng, B, S, G, N, dtype=jnp.float32)
    y, h = ops.ssd_scan(x, log_dA, Bm, Cm, chunk=chunk, backend="interpret")
    ye, he = ref.ssd_ref(x, log_dA, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(he), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("rows,d", [(4, 64), (100, 128), (257, 256)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_rmsnorm(rows, d, dtype, rng):
    x = _arr(rng, rows, d, dtype=dtype)
    scale = jnp.asarray(rng.standard_normal(d), jnp.float32)
    out = ops.rmsnorm(x, scale, backend="interpret")
    exp = ref.rmsnorm_ref(x, scale)
    _assert_close(out, exp, dtype)


def test_ssd_kernel_matches_model_chunked(rng):
    """The Pallas SSD kernel and the model's pure-jnp chunked SSD agree."""
    from repro.models.mamba import ssd_chunked

    B, S, H, P, G, N = 1, 128, 2, 16, 1, 8
    x = _arr(rng, B, S, H, P, dtype=jnp.float32)
    log_dA = -jnp.abs(_arr(rng, B, S, H, dtype=jnp.float32)) * 0.1
    Bm = _arr(rng, B, S, G, N, dtype=jnp.float32)
    Cm = _arr(rng, B, S, G, N, dtype=jnp.float32)
    yk, hk = ops.ssd_scan(x, log_dA, Bm, Cm, chunk=32, backend="interpret")
    ym, hm = ssd_chunked(x, log_dA, Bm, Cm, chunk=32)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(ym), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hm), atol=2e-4, rtol=2e-4)
