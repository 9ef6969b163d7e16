"""Pallas kernel micro-benchmarks (CPU: XLA-fallback timings + interpret
correctness deltas; on TPU the same harness times the real kernels)."""

from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, timed_us
from repro.kernels import ops, ref, splash_attention


def run() -> List[Row]:
    rows: List[Row] = []
    rng = np.random.default_rng(0)

    def arr(*s, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(s), dtype)

    # flash attention (XLA path timing; interpret path correctness)
    q, k, v = arr(1, 8, 512, 64), arr(1, 2, 512, 64), arr(1, 2, 512, 64)
    f = jax.jit(lambda q, k, v: ref.attention_ref(q, k, v, causal=True))
    us = timed_us(lambda: jax.block_until_ready(f(q, k, v)), iters=3)
    out_i = splash_attention.flash_attention(q, k, v, interpret=True)
    err = float(
        np.abs(np.asarray(out_i, np.float32) - np.asarray(f(q, k, v), np.float32)).max()
    )
    rows.append(Row("kernels/flash_attention_512", us, f"interp_max_err={err:.2e}"))

    # decode attention
    q1, kc, vc = arr(4, 8, 64), arr(4, 2048, 2, 64), arr(4, 2048, 2, 64)
    vl = jnp.asarray(1500, jnp.int32)
    g = jax.jit(lambda q, k, v, n: ref.decode_attention_ref(q, k, v, n))
    us = timed_us(lambda: jax.block_until_ready(g(q1, kc, vc, vl)), iters=5)
    out_i = ops.decode_attention(q1, kc, vc, vl, backend="interpret")
    err = float(
        np.abs(np.asarray(out_i, np.float32) - np.asarray(g(q1, kc, vc, vl), np.float32)).max()
    )
    rows.append(Row("kernels/decode_attention_2k", us, f"interp_max_err={err:.2e}"))

    # ssd scan
    x = arr(2, 512, 4, 32, dtype=jnp.float32)
    a = -jnp.abs(arr(2, 512, 4, dtype=jnp.float32)) * 0.1
    B = arr(2, 512, 1, 16, dtype=jnp.float32)
    C = arr(2, 512, 1, 16, dtype=jnp.float32)
    h = jax.jit(lambda *t: ref.ssd_ref(*t))
    us = timed_us(lambda: jax.block_until_ready(h(x, a, B, C)[0]), iters=3)
    yi, _ = ops.ssd_scan(x, a, B, C, chunk=128, backend="interpret")
    err = float(np.abs(np.asarray(yi) - np.asarray(h(x, a, B, C)[0])).max())
    rows.append(Row("kernels/ssd_scan_512", us, f"interp_max_err={err:.2e}"))

    # rmsnorm
    xx = arr(4096, 1024)
    sc = jnp.ones((1024,), jnp.float32)
    r = jax.jit(lambda x, s: ref.rmsnorm_ref(x, s))
    us = timed_us(lambda: jax.block_until_ready(r(xx, sc)), iters=10)
    out_i = ops.rmsnorm(xx, sc, backend="interpret")
    err = float(
        np.abs(np.asarray(out_i, np.float32) - np.asarray(r(xx, sc), np.float32)).max()
    )
    rows.append(Row("kernels/rmsnorm_4kx1k", us, f"interp_max_err={err:.2e}"))
    return rows


if __name__ == "__main__":
    for row in run():
        print(row)
