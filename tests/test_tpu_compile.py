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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

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


def _flash(q, k, v):
    return ops.flash_attention(q, k, v, causal=True, window=4096)


def _decode(q, k, v, valid_len):
    return ops.decode_attention(q, k, v, valid_len)


def _ssd(x, log_dA, b, c):
    return ops.ssd_scan(x, log_dA, b, c, chunk=256)


def _rmsnorm(x, scale):
    return ops.rmsnorm(x, scale)


KERNELS = {
    # name: (fn, [(shape, dtype), ...])
    "flash_attention": (
        _flash,
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
