"""The float32 references against the program's models (``repro.models``)
at smoke widths, on the same weights in float32, for every configuration
of the benchmark: the loss and every leaf's gradient agree to float32
rounding.  The program's Mamba-2 scan runs in
chunks of 32 and the reference's in chunks of 128, so the sequence of 256
crosses chunk boundaries differently on the two sides."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arch
from chipbench.harness import program_config
from chipbench.reference import params as P
from chipbench.tests import tiny
from chipbench.traffic import TokenStream

CONFIGS = [c["name"] for c in json.loads((tiny.ROOT / "BENCHMARK.json").read_text())["configs"]]


@pytest.mark.parametrize("name", CONFIGS)
def test_loss_and_gradient_match_the_program(name):
    from repro.models.factory import build_model

    cfg = tiny.config(name)
    model = build_model(program_config(cfg))
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32), jax.jit(lambda k: P.init(cfg, k))(P.seed_key(2**31 + 5, 0))
    )
    tokens, labels = TokenStream(cfg["vocab_size"], 256, 2, seed=3).batch_at(0)
    prog = jax.value_and_grad(lambda p: model.loss(p, tokens, labels)[0])
    ref = jax.value_and_grad(lambda p: arch.of(cfg).loss(p, tokens, labels, cfg, "float32"))
    with jax.default_matmul_precision("highest"):
        (lp, gp), (lr, gr) = jax.jit(prog)(params), jax.jit(ref)(params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for path, a in jax.tree_util.tree_flatten_with_path(gp)[0]:
        b = gr
        for k in path:
            b = b[k.key]
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, path
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4 * scale, err_msg=str(path))


def test_window_masks_distant_keys():
    """With a window of 32 the reference's last query cannot see key 0:
    changing the first token moves only the first 32 positions' outputs."""
    attention = arch.load("transformer").attention

    k = jax.random.normal(jax.random.key(0), (1, 64, 2, 16))
    q = jax.random.normal(jax.random.key(1), (1, 64, 4, 16))
    a = attention(q, k, k, 32, "float32")
    k2 = k.at[:, 0].set(5.0)
    b = attention(q, k2, k2, 32, "float32")
    moved = np.abs(np.asarray(a - b)).max(axis=(0, 2, 3)) > 0
    assert moved[:32].all() and not moved[32:].any()


def test_ssd_matches_the_recurrence():
    """The chunked SSD of the reference against the step-by-step recurrence
    h_t = exp(A_t) h_{t-1} + B_t x_t^T, y_t = C_t h_t."""
    ssd = arch.load("mamba2").ssd

    ks = jax.random.split(jax.random.key(0), 4)
    b, l, h, p, n = 1, 16, 2, 3, 4
    X = jax.random.normal(ks[0], (b, l, h, p))
    A = -jax.random.uniform(ks[1], (b, l, h))
    Bm = jax.random.normal(ks[2], (b, l, h, n))
    Cm = jax.random.normal(ks[3], (b, l, h, n))
    y = ssd(X, A, Bm, Cm, "float32", chunk=4)
    state = np.zeros((b, h, p, n))
    for t in range(l):
        state = np.exp(np.asarray(A[:, t]))[..., None, None] * state + np.einsum(
            "bhp,bhn->bhpn", X[:, t], Bm[:, t]
        )
        np.testing.assert_allclose(
            np.asarray(y[:, t]), np.einsum("bhpn,bhn->bhp", state, Cm[:, t]), rtol=1e-4, atol=1e-5
        )


def test_init_is_a_function_of_the_seed():
    cfg = tiny.config("mamba2-370m")
    make = jax.jit(lambda k: P.init(cfg, k))
    a, b = make(P.seed_key(2**31 + 11, 1)), make(P.seed_key(2**31 + 11, 1))
    c = make(P.seed_key(11, 1))  # the same low 32 bits, another seed
    same = jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)), a, b)
    assert all(jax.tree.leaves(same))
    assert not jnp.array_equal(a["embed"]["table"], c["embed"]["table"])
