"""The Pallas kernels compile for a described TPU v5e at the widths the
main path uses: ``h2o-danube-1.8b`` attention (32 query / 8 kv heads of 80,
a 4096 sliding window) and ``mamba2-370m`` SSD (32 heads x 64, state 128,
chunk 256).  Interpret mode (``test_kernels.py``) cannot see the TPU
compiler's tiling rules; these compiles can, with no chip attached.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, splash_attention
from repro.models import common

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash_train(q, k, v):
    def loss(q, k, v):
        return jnp.sum(splash_attention.flash_attention(q, k, v, window=4096).astype(F32))

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _decode(q, k, v, valid_len):
    return ops.decode_attention(q, k, v, valid_len)


def _ssd(x, log_dA, b, c):
    return ops.ssd_scan(x, log_dA, b, c, chunk=256)


def _rmsnorm(x, scale):
    return ops.rmsnorm(x, scale)


KERNELS = {
    # name: (fn, [(shape, dtype), ...])
    "splash_attention_train": (
        _flash_train,
        [((1, 32, 4096, 80), BF16), ((1, 8, 4096, 80), BF16), ((1, 8, 4096, 80), BF16)],
    ),
    "decode_attention": (
        _decode,
        [((8, 32, 80), BF16), ((8, 4096, 8, 80), BF16), ((8, 4096, 8, 80), BF16), ((), I32)],
    ),
    "ssd_scan": (
        _ssd,
        [((1, 2048, 32, 64), F32), ((1, 2048, 32), F32),
         ((1, 2048, 1, 128), F32), ((1, 2048, 1, 128), F32)],
    ),
    "rmsnorm": (_rmsnorm, [((4096, 2560), BF16), ((2560,), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "the Pallas kernel must be in the program"


def test_flash_train_ops_under_attention_core(one_chip, no_persistent_cache, monkeypatch):
    """``causal_attention`` on a TPU takes the kernel; its forward, dq and
    dkv calls, under ``jax.checkpoint`` as the layer loop runs them, carry
    the ``attention_core`` scope that the device trace is read by."""
    monkeypatch.setattr(common, "_platform", lambda mesh: "tpu")

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            return jnp.sum(common.causal_attention(q, k, v, window=4096).astype(F32))

        return jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2))(q, k, v)

    shapes = [(1, 4096, 32, 80), (1, 4096, 8, 80), (1, 4096, 8, 80)]
    args = [jax.ShapeDtypeStruct(s, BF16, sharding=one_chip) for s in shapes]
    text = jax.jit(fwd_bwd).lower(*args).compile().as_text()
    scoped = {}
    for m in re.finditer(r"%(splash_mha_(fwd|dq|dkv)\w*)[.\d]* = ", text):
        op_name = re.search(r'op_name="([^"]*)"', text[m.end():]).group(1)
        scoped[m.group(2)] = "/attention_core/" in op_name
    assert scoped == {"fwd": True, "dq": True, "dkv": True}


def test_ssd_chunked_train_cost_for_v5e(one_chip, no_persistent_cache):
    """The model's chunked SSD scan, forward and backward under
    ``jax.checkpoint`` as the layer loop runs it, at the mamba2-370m
    training shapes (4 x 2048, 32 heads x 64, one group, state 128, chunk
    256).  C.B^T is formed once per group and every chunk in one batched
    product; repeating it over the heads inside a loop over chunks moves
    6.6e9 bytes and holds 0.5 GB of temporaries."""
    from repro.models import flags
    from repro.models.mamba import ssd_chunked

    B, S, H, P, G, N = 4, 2048, 32, 64, 1, 128

    def fwd_bwd(x, log_dA, b, c, dy, dh):
        scan = jax.checkpoint(lambda *a: ssd_chunked(*a, chunk=256))
        _, vjp = jax.vjp(scan, x, log_dA, b, c)
        return vjp((dy, dh))

    shapes = [(B, S, H, P), (B, S, H), (B, S, G, N), (B, S, G, N), (B, S, H, P), (B, H, N, P)]
    args = [jax.ShapeDtypeStruct(s, F32, sharding=one_chip) for s in shapes]
    with flags.full_unroll():  # a loop's body would be counted once
        compiled = jax.jit(fwd_bwd).lower(*args).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < 4.0e9
    assert compiled.memory_analysis().temp_size_in_bytes < 400e6
