"""Roofline analysis: HLO parsing and model FLOPs."""

import jax
import jax.numpy as jnp
import pytest

from repro.roofline.analysis import _shape_bytes, parse_collectives


def test_shape_bytes():
    assert _shape_bytes("bf16[8,128]{1,0}") == 8 * 128 * 2
    assert _shape_bytes("f32[2,2,2]") == 32
    assert _shape_bytes("(bf16[4], f32[4])") == 8 + 16
    assert _shape_bytes("pred[16]") == 16
    assert _shape_bytes("s32[]") == 4


def test_parse_collectives_synthetic():
    hlo = """
  %p0 = bf16[128,64]{1,0} parameter(0)
  %ar = bf16[128,64]{1,0} all-reduce(%p0), replica_groups={}
  %ag = bf16[256,64]{1,0} all-gather(%p0), dimensions={0}
  %rs.1 = bf16[64,64]{1,0} reduce-scatter(%ar), dimensions={0}
  %cp = bf16[128,64]{1,0} collective-permute(%p0)
  %a2a = bf16[128,64]{1,0} all-to-all(%p0)
"""
    stats = parse_collectives(hlo)
    assert stats.counts == {
        "all-reduce": 1,
        "all-gather": 1,
        "reduce-scatter": 1,
        "all-to-all": 1,
        "collective-permute": 1,
    }
    b = 128 * 64 * 2
    assert stats.operand_bytes["all-reduce"] == b
    assert stats.operand_bytes["all-gather"] == b  # operand, not result
    assert stats.total_operand_bytes == 5 * b
    assert stats.wire_bytes == 6 * b  # all-reduce counts 2x


def test_parse_collectives_async_pairs_not_double_counted():
    hlo = """
  %p0 = bf16[128,64]{1,0} parameter(0)
  %ar0 = bf16[128,64]{1,0} all-reduce-start(%p0)
  %ar1 = bf16[128,64]{1,0} all-reduce-done(%ar0)
"""
    stats = parse_collectives(hlo)
    assert stats.counts == {"all-reduce": 1}


def test_parse_real_sharded_program(v5e_2x2, no_persistent_cache):
    """Collectives of a real sharded matmul, compiled for a (data 2, model 2)
    mesh over a described v5e: contracting over the model-sharded dim
    all-reduces the partial products across the model axis."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(v5e_2x2.devices)
    x = jax.ShapeDtypeStruct((256, 512), jnp.bfloat16, sharding=NamedSharding(mesh, P("data", "model")))
    w = jax.ShapeDtypeStruct((512, 384), jnp.bfloat16, sharding=NamedSharding(mesh, P("model", None)))
    out = NamedSharding(mesh, P("data", None))
    compiled = jax.jit(jnp.matmul, out_shardings=out).lower(x, w).compile()
    stats = parse_collectives(compiled.as_text())
    assert stats.counts == {"all-reduce": 1}
    # each device all-reduces its (128, 384) partial product
    assert stats.operand_bytes["all-reduce"] == 128 * 384 * 2
    assert stats.wire_bytes == 2 * 128 * 384 * 2


def test_model_flops_for_cell():
    from repro.configs import SHAPES, get_config
    from repro.roofline.analysis import model_flops_for_cell

    cfg = get_config("qwen3-32b")
    n = cfg.param_count(active_only=True)
    train = model_flops_for_cell(cfg, SHAPES["train_4k"])
    assert train == pytest.approx(6 * n * 256 * 4096)
    decode = model_flops_for_cell(cfg, SHAPES["decode_32k"])
    assert decode == pytest.approx(2 * n * 128)
    # MoE: active params, not total
    moe = get_config("deepseek-v3-671b")
    assert model_flops_for_cell(moe, SHAPES["train_4k"]) < 6 * moe.param_count() * 256 * 4096 / 5
