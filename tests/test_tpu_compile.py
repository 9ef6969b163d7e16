"""The Pallas kernels compile for a described TPU v5e at the widths the
main path uses: ``h2o-danube-1.8b`` attention (32 query / 8 kv heads of 80,
a 4096 sliding window) and ``mamba2-370m`` SSD (32 heads x 64, state 128,
chunk 256).  Interpret mode (``test_kernels.py``) cannot see the TPU
compiler's tiling rules; these compiles can, with no chip attached.

The topology is described by the ``v5e_2x2`` fixture (``conftest.py``).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.kernels import ops, splash_attention
from repro.models import common

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


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


def _tiny_danube():
    from repro.configs import get_config, smoke_config

    # 4 query / 2 kv heads split over model 2; 128 positions take the kernel
    return smoke_config(get_config("h2o-danube-1.8b"))


def _sharded(tree, shardings):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, shardings
    )


def _assert_shardings(got, mesh, specs):
    want = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.spec == w.spec, (g.spec, w.spec)


@pytest.mark.parametrize("step", ["train", "decode"])
def test_host_mesh_bundle_compiles_for_v5e(step, v5e_2x2, no_persistent_cache):
    """Tiny danube's train step and decode step compile over the host mesh
    of data 2 x model 2, the layout ``mesh4.danube`` runs: the parameters
    keep the specs of their definitions and the optimizer state its ZeRO
    specs, in and out, and the train step all-reduces its gradients."""
    from repro.launch.mesh import make_host_mesh
    from repro.models import params as pu
    from repro.roofline.analysis import parse_collectives
    from repro.train.steps import make_serve_bundle, make_train_bundle

    cfg = _tiny_danube()
    mesh = make_host_mesh(v5e_2x2.devices)
    assert dict(mesh.shape) == {"data": 2, "model": 2}
    if step == "train":
        bundle = make_train_bundle(cfg, mesh, q_chunk=64)
        defs = bundle.model.param_defs()
        param_specs = pu.partition_specs(defs)
        opt_specs = bundle.optimizer.state_specs(param_specs, pu.zero_specs(defs, 2))
        tokens = jax.ShapeDtypeStruct((4, 128), I32, sharding=bundle.batch_shardings["tokens"])
        compiled = bundle.step_fn.lower(
            _sharded(bundle.abstract_params, bundle.param_shardings),
            _sharded(bundle.abstract_opt, bundle.opt_shardings),
            {"tokens": tokens, "labels": tokens},
        ).compile()
        (p_in, o_in, _), _ = compiled.input_shardings
        p_out, o_out, _ = compiled.output_shardings
        for got, specs in ((p_in, param_specs), (p_out, param_specs), (o_in, opt_specs), (o_out, opt_specs)):
            _assert_shardings(got, mesh, specs)
        assert parse_collectives(compiled.as_text()).counts.get("all-reduce", 0) > 0
    else:
        bundle = make_serve_bundle(cfg, mesh, batch=4, max_len=128, q_chunk=64)
        param_specs = pu.partition_specs(bundle.model.param_defs())
        compiled = bundle.decode_fn.lower(
            bundle.abstract_params,
            _sharded(bundle.abstract_cache, bundle.cache_shardings),
            jax.ShapeDtypeStruct((4, 1), I32),
            jax.ShapeDtypeStruct((), I32),
        ).compile()
        (p_in, c_in, _, _), _ = compiled.input_shardings
        _, c_out = compiled.output_shardings
        _assert_shardings(p_in, mesh, param_specs)
        for got in (c_in, c_out):
            _assert_shardings(got, mesh, bundle.model.cache_specs())
