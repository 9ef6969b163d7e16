"""The FLOP count against hand counts at smoke widths, and against the
matrix products XLA is given for the program's own remat-free forward:
exactly, by named differences, for each architecture, and within bounds for
every configuration of the benchmark."""

import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arch, flops, peaks
from chipbench.harness import program_config
from chipbench.tests import tiny

SEQ = 64


def test_transformer_by_hand():
    cfg = tiny.config("h2o-danube-1.8b-l4")
    # d 64, 4 heads and 2 kv heads of 16, ff 128, 2 layers, vocab 503, window 32
    per_token = 2 * 64 * (64 + 32 + 32) + 2 * 64 * 64 + 3 * 2 * 64 * 128  # 73,728
    pairs = 32 * 33 // 2 + (64 - 32) * 32  # 1,552 (query, key) pairs
    per_layer = per_token * 64 + 4 * 64 * pairs  # 5,115,904
    head = 2 * 64 * 503 * 64  # 4,120,576
    assert per_token == 73_728 and pairs == 1_552 and per_layer == 5_115_904
    assert arch.of(cfg).forward_flops(cfg, SEQ) == 2 * per_layer + head == 14_352_384
    assert flops.train_step_flops(cfg, 1, SEQ) == 3 * 14_352_384


def test_mamba2_by_hand():
    cfg = tiny.config("mamba2-370m")
    # d 64, inner 128 in 8 heads of 16, state 16, one group, chunk 32, 2 layers
    proj = 2 * 64 * (2 * 128 + 2 * 16 + 8) + 2 * 128 * 64  # 54,272 per token
    tri = 32 * 33 // 2  # 528
    chunk = 2 * tri * 16 + 2 * tri * 16 + 2 * 32 * 16 * 16 * 2 + 2 * 16 * 16  # 67,072 per head
    per_layer = proj * 64 + chunk * 2 * 8  # 4,546,560
    assert proj == 54_272 and chunk == 67_072 and per_layer == 4_546_560
    assert arch.of(cfg).forward_flops(cfg, SEQ) == 2 * per_layer + 2 * 64 * 503 * 64 == 13_213_696


def _dot_flops(jaxpr) -> float:
    """2 x multiply-adds of every dot_general in a (closed) jaxpr, loops
    counted by their trip count."""
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, rc), (lb, _) = eqn.params["dimension_numbers"]
            a, b = (v.aval.shape for v in eqn.invars)
            k = math.prod(a[i] for i in lc)
            out = math.prod(eqn.outvars[0].aval.shape)
            total += 2.0 * out * k
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n = eqn.params["length"] if eqn.primitive.name == "scan" else 1
            total += n * _dot_flops(sub)
    return total


def _program_forward(name):
    from repro.models import flags
    from repro.models.factory import build_model
    from chipbench.reference import params as P

    cfg = tiny.config(name)
    arch = dataclasses.replace(program_config(cfg), remat="none")
    model = build_model(arch)
    p = P.abstract(cfg)
    tok = jax.ShapeDtypeStruct((1, SEQ), jnp.int32)
    fn = lambda p, t, l: model.loss(p, t, l)[0]
    with flags.full_unroll():
        closed = jax.make_jaxpr(fn)(p, tok, tok)
        cost = jax.jit(fn).lower(p, tok, tok).compile().cost_analysis()
    return cfg, _dot_flops(closed.jaxpr), cost["flops"]


def test_transformer_against_xla():
    cfg, dots, xla = _program_forward("h2o-danube-1.8b-l4")
    ours = arch.of(cfg).forward_flops(cfg, SEQ)
    # named differences: the program scores every (query, key) pair and
    # masks what the window and causality exclude; its head spans the
    # vocabulary padded to 512
    masked = 2 * 2 * 64 * (SEQ * SEQ - 1552) * 2
    padded = 2 * 64 * (512 - 503) * SEQ
    assert dots == ours + masked + padded
    # the rest of XLA's count is elementwise work (norms, RoPE, softmax,
    # SiLU, the loss), which the count leaves out
    assert dots < xla < 1.25 * dots


def test_mamba2_against_xla():
    cfg, dots, xla = _program_forward("mamba2-370m")
    ours = arch.of(cfg).forward_flops(cfg, SEQ)
    q, tri, n, p, heads, groups, chunks, layers = 32, 528, 16, 16, 8, 1, 2, 2
    # named differences: the program forms the C.B scores once per group
    # over the whole chunk square, where the count takes them per head on
    # the causal triangle; it multiplies the scores into the inputs over the
    # whole square and masks its upper triangle; it carries the state across
    # chunks with an elementwise multiply-add (counted here as 2 N P);
    # padded vocabulary
    scores = 2 * q * q * n * groups * chunks * layers - 2 * tri * n * heads * chunks * layers
    square = 2 * (q * q - tri) * p * heads * chunks * layers
    carried = 2 * n * p * heads * chunks * layers
    padded = 2 * 64 * (512 - 503) * SEQ
    assert dots == ours + scores + square - carried + padded
    # the rest is elementwise: the decay gates over each chunk square (exp,
    # mask, product), the convolution, SiLU, norms and the loss; at this
    # width it is more than a third of the matrix work
    assert dots < xla < 1.5 * dots


@pytest.mark.parametrize("name", [c["name"] for c in json.loads((tiny.ROOT / "BENCHMARK.json").read_text())["configs"]])
def test_every_config_against_xla(name):
    cfg, dots, xla = _program_forward(name)
    ours = arch.of(cfg).forward_flops(cfg, SEQ)
    # the count holds no product the program does not compute (masked and
    # padded work aside, which it computes and the count leaves out), and
    # XLA's count adds the elementwise work to the products
    assert 0 < ours <= dots < xla
    assert flops.train_step_flops(cfg, 2, SEQ) == 3 * 2 * ours


def test_peaks_keyed_by_device_kind():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peak("cpu")
