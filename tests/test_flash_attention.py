"""The flash kernel path of causal self-attention (``causal_attention``):
JAX's splash attention kernel, in interpret mode on the CPU, against the
XLA ``attention()`` it replaces on the TPU; the choice between the two as a
pure function of platform and shapes; and the count of what was taken."""

import dataclasses
import functools
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import splash_attention
from repro.models import attention as attn
from repro.models import common
from repro.models.params import init_params

B, H, HKV, D = 2, 8, 2, 80  # GQA 4:1 at danube's head dim


def _qkv(S, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    return q, k, v, w


def _kernel(q, k, v, window):
    o = splash_attention.flash_attention(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), window=window, interpret=True
    )
    return o.swapaxes(1, 2)


def _xla(q, k, v, window):
    return common.attention(q, k, v, causal=True, sliding_window=window, q_chunk=128)


@pytest.mark.parametrize("S,window", [(256, None), (512, None), (256, 100), (512, 300)])
def test_kernel_matches_xla_attention(S, window):
    """Output and the gradients of q, k and v of a scalar loss."""
    q, k, v, w = _qkv(S)

    def grads(fn):
        loss = lambda q, k, v: jnp.sum(fn(q, k, v, window) * w)  # noqa: E731
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    np.testing.assert_allclose(_kernel(q, k, v, window), _xla(q, k, v, window), atol=2e-5, rtol=1e-4)
    for name, a, b in zip("qkv", grads(_kernel), grads(_xla)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a / scale, b / scale, atol=1e-5, err_msg=f"d{name}")


@pytest.mark.parametrize(
    "platform,q,k,v,shards,path",
    [
        ("tpu", (1, 4096, 32, 80), (1, 4096, 8, 80), (1, 4096, 8, 80), (1, 1), "kernel"),
        ("tpu", (4, 4096, 32, 80), (4, 4096, 8, 80), (4, 4096, 8, 80), (2, 2), "kernel"),
        ("cpu", (1, 4096, 32, 80), (1, 4096, 8, 80), (1, 4096, 8, 80), (1, 1), "xla"),
        ("tpu", (1, 1000, 32, 80), (1, 1000, 8, 80), (1, 1000, 8, 80), (1, 1), "xla"),
        ("tpu", (1, 1, 32, 80), (1, 4096, 8, 80), (1, 4096, 8, 80), (1, 1), "xla"),  # decode
        ("tpu", (1, 4096, 16, 192), (1, 4096, 16, 192), (1, 4096, 16, 128), (1, 1), "xla"),  # MLA
        ("tpu", (2, 256, 8, 64), (2, 384, 8, 64), (2, 384, 8, 64), (1, 1), "xla"),  # cross
        ("tpu", (2, 4096, 32, 80), (2, 4096, 8, 80), (2, 4096, 8, 80), (1, 16), "xla"),  # heads
    ],
)
def test_attention_path(platform, q, k, v, shards, path):
    got, why = common.attention_path(platform, q, k, v, shards)
    assert got == path, why


def test_counter_reports_the_path_taken(monkeypatch):
    cfg = dataclasses.replace(
        get_config("h2o-danube-1.8b"), num_layers=1, d_model=128, num_heads=H, num_kv_heads=HKV
    )
    p = jax.eval_shape(lambda: init_params(attn.gqa_def(cfg), jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((1, 256, cfg.d_model), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    fwd = functools.partial(attn.gqa_forward, cfg=cfg)

    before = common.ATTENTION_PATHS.copy()
    jax.eval_shape(lambda p, x, pos: fwd(p, x=x, positions=pos), p, x, pos)
    assert common.ATTENTION_PATHS - before == {("xla", f"platform {jax.default_backend()}"): 1}

    monkeypatch.setattr(common, "_platform", lambda mesh: "tpu")
    before = common.ATTENTION_PATHS.copy()
    jax.eval_shape(lambda p, x, pos: fwd(p, x=x, positions=pos), p, x, pos)
    assert common.ATTENTION_PATHS - before == {("kernel", "tpu, length 256, head dim 80"): 1}


_MESH_CHILD = textwrap.dedent(
    """
    import dataclasses, functools, json
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.kernels import splash_attention
    from repro.launch.mesh import make_host_mesh
    from repro.models import attention as attn, common
    from repro.models.params import init_params

    mesh = make_host_mesh()
    assert dict(mesh.shape) == {"data": 2, "model": 2}, mesh.shape
    # the kernel under the mesh, in interpret mode; XLA without one
    common._platform = lambda mesh: "tpu" if mesh is not None else "cpu"
    splash_attention.flash_attention = functools.partial(
        splash_attention.flash_attention, interpret=True
    )
    cfg = dataclasses.replace(
        get_config("h2o-danube-1.8b"), num_layers=1, d_model=128, num_heads=8,
        num_kv_heads=4, sliding_window=200,
    )
    p = init_params(attn.gqa_def(cfg), jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 256, 128)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(256), (2, 256))

    def loss(p, x, mesh):
        o = attn.gqa_forward(p, cfg, x, pos, 128, mesh)
        return jnp.sum(o * o), o

    out = {}
    for name, m in (("kernel", mesh), ("xla", None)):
        (_, o), g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True), static_argnums=2)(p, x, m)
        out[name] = (o, g)
    (ok, gk), (ox, gx) = out["kernel"], out["xla"]
    err = lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
    errs = {"out": err(ok, ox), "dx": err(gk[1], gx[1])}
    errs.update({"d" + n: err(gk[0][n], gx[0][n]) for n in ("wq", "wk", "wv", "wo")})
    paths = {k[0]: n for k, n in common.ATTENTION_PATHS.items()}
    print(json.dumps({"errs": errs, "paths": paths}))
    """
)


def test_gqa_forward_under_shard_map():
    """``gqa_forward`` on a CPU mesh of data 2 x model 2 takes the kernel
    through ``shard_map`` and matches the XLA path without a mesh."""
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.pathsep.join([os.path.join(os.path.dirname(__file__), "..", "src")]),
    )
    out = subprocess.run(
        [sys.executable, "-c", _MESH_CHILD], env=env, capture_output=True, text=True, timeout=300
    )
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["paths"] == {"kernel": 1, "xla": 1}
    for name, e in got["errs"].items():
        assert e < 1e-5, (name, e)


@pytest.mark.parametrize(
    "seq,block", [(4096, 1024), (8192, 1024), (2048, 512), (1024, 256), (512, 128), (256, 128), (384, 128)]
)
def test_block_size(seq, block):
    assert splash_attention.block_size(seq) == block


def test_block_size_refuses_a_ragged_length():
    with pytest.raises(ValueError):
        splash_attention.block_size(1000)
