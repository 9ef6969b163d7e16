"""Jit'd public wrappers for the Pallas kernels.

``backend`` selects the execution path:
  * "pallas"    — the TPU kernels (the default; nothing here falls back
                  when the device is not a TPU),
  * "interpret" — Pallas interpret mode (CPU callers pass it explicitly),
  * "xla"       — the pure-jnp production fallback in ``repro.models`` /
                  ``repro.kernels.ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas

DEFAULT_BACKEND = "pallas"


@functools.partial(jax.jit, static_argnames=("backend",))
def decode_attention(q, k, v, valid_len, *, backend: str = DEFAULT_BACKEND):
    if backend == "xla":
        return ref.decode_attention_ref(q, k, v, valid_len)
    return _decode_pallas(q, k, v, valid_len, interpret=(backend == "interpret"))


@functools.partial(jax.jit, static_argnames=("chunk", "backend"))
def ssd_scan(x, log_dA, Bm, Cm, *, chunk: int = 256, backend: str = DEFAULT_BACKEND):
    if backend == "xla":
        return ref.ssd_ref(x, log_dA, Bm, Cm)
    return _ssd_pallas(x, log_dA, Bm, Cm, chunk=chunk, interpret=(backend == "interpret"))


@functools.partial(jax.jit, static_argnames=("eps", "backend"))
def rmsnorm(x, scale, *, eps: float = 1e-6, backend: str = DEFAULT_BACKEND):
    if backend == "xla":
        return ref.rmsnorm_ref(x, scale, eps)
    return _rmsnorm_pallas(x, scale, eps=eps, interpret=(backend == "interpret"))
