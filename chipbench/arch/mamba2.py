"""The architecture ``mamba2``: the Mamba-2 language model (Dao and Gu,
arXiv:2405.21060): pre-RMSNorm blocks of the Mamba-2 mixer, final RMSNorm
and a head tied to the embedding.  The reference (``loss``) is float32
throughout; see ``chipbench/arch`` for what the module holds.

The mixer: z, x, B, C and dt from input projections; a causal depthwise
convolution and SiLU over x, B and C; the SSD recurrence
``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T``, ``y_t = C_t h_t + D x_t``
computed by the paper's minimal chunked listing (``ssd_minimal_discrete``);
then ``RMSNorm(y * silu(z))`` and the output projection."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.arch import require
from chipbench.reference.common import F32, cross_entropy_sum, mm, rmsnorm
from chipbench.reference.params import pad_vocab

# the chunk of the minimal listing; the result does not depend on it
CHUNK = 128

# ssd_scan (the chunked scan) runs inside ssm (the whole mixer)
SCOPES = {"ssm": None, "ssd_scan": "ssm", "head": None, "optimizer": None}


def check(cfg: dict, arch) -> None:
    """The file's sizes against the program's ``ArchConfig``."""
    s, m = cfg["ssm_cfg"], arch.ssm
    require(
        cfg,
        {
            "d_model": (arch.d_model, cfg["d_model"]),
            "num_layers": (arch.num_layers, cfg["n_layer"]),
            "vocab_size": (arch.vocab_size, cfg["vocab_size"]),
            "tie_embeddings": (arch.tie_embeddings, cfg["tie_embeddings"]),
            "ssm": (
                (m.d_state, m.head_dim, m.expand, m.n_groups, m.conv_width, m.chunk),
                (s["d_state"], s["headdim"], s["expand"], s["ngroups"], s["d_conv"], s["chunk_size"]),
            ),
            "dtype": (arch.dtype, cfg["param_dtype"]),
        },
    )


def specs(cfg: dict):
    d, L = cfg["d_model"], cfg["n_layer"]
    s = cfg["ssm_cfg"]
    d_in = s["expand"] * d
    H, GN, W = d_in // s["headdim"], s["ngroups"] * s["d_state"], s["d_conv"]
    init = cfg["init"]
    std = init["linear_std"]
    return {
        "embed": {"table": ((pad_vocab(cfg["vocab_size"]), d), "normal", init["embed_std"])},
        "final_norm": {"scale": ((d,), "ones", 0.0)},
        "ssm": {
            "l0": {
                "norm1": {"scale": ((L, d), "ones", 0.0)},
                "mixer": {
                    "w_z": ((L, d, d_in), "normal", std),
                    "w_x": ((L, d, d_in), "normal", std),
                    "w_bc": ((L, d, 2 * GN), "normal", std),
                    "w_dt": ((L, d, H), "normal", std),
                    "dt_bias": ((L, H), "dt_bias", 0.0),
                    "A_log": ((L, H), "A_log", 0.0),
                    "D": ((L, H), "ones", 0.0),
                    "conv_x": ((L, W, d_in), "conv", 0.0),
                    "conv_bc": ((L, W, 2 * GN), "conv", 0.0),
                    "norm": ((L, d_in), "ones", 0.0),
                    "w_out": ((L, d_in, d), "normal", init["out_proj_std_over_sqrt_layers"] / math.sqrt(L)),
                },
            }
        },
    }


def _dt_bias(key, shape, arg, cfg):
    """dt log-uniform in [dt_min, dt_max]; bias = softplus^-1(dt)."""
    init = cfg["init"]
    lo, hi = math.log(init["dt_min"]), math.log(init["dt_max"])
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    return dt + jnp.log(-jnp.expm1(-dt))


def _A_log(key, shape, arg, cfg):
    init = cfg["init"]
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, init["A_min"], init["A_max"]))


def _conv(key, shape, arg, cfg):
    """PyTorch's default for a depthwise Conv1d: U(+-1/sqrt(width))."""
    b = 1.0 / math.sqrt(shape[-2])
    return jax.random.uniform(key, shape, jnp.float32, -b, b)


INITS = {"dt_bias": _dt_bias, "A_log": _A_log, "conv": _conv}


def ssd_chunk_terms(seq: int, heads: int, head_dim: int, state: int, chunk: int) -> float:
    """SSD FLOPs of one sequence in one layer (chunked algorithm)."""
    q = min(chunk, seq)
    n_chunks = -(-seq // q)
    tri = q * (q + 1) // 2
    per_chunk = (
        2 * tri * state  # C_i . B_j scores, j <= i
        + 2 * tri * head_dim  # scores x inputs
        + 2 * q * state * head_dim  # chunk state: sum_j B_j x_j
        + 2 * q * state * head_dim  # output from the carried state: C_i . h
        + 2 * state * head_dim  # carried state decayed and added across chunks
    )
    return n_chunks * heads * per_chunk


def forward_flops(cfg: dict, seq: int) -> float:
    """Forward FLOPs of one sequence through a Mamba-2 stack with tied head."""
    d = cfg["d_model"]
    s = cfg["ssm_cfg"]
    d_in = s["expand"] * d
    heads = d_in // s["headdim"]
    gn = s["ngroups"] * s["d_state"]
    proj = 2 * d * (2 * d_in + 2 * gn + heads) + 2 * d_in * d
    ssd = ssd_chunk_terms(seq, heads, s["headdim"], s["d_state"], s["chunk_size"])
    head = 2 * d * cfg["vocab_size"] * seq
    return cfg["n_layer"] * (proj * seq + ssd) + head


def segsum(a: jax.Array) -> jax.Array:
    """(..., T) -> (..., T, T): entry (i, j) is a_{j+1} + ... + a_i for
    j <= i and -inf above the diagonal."""
    T = a.shape[-1]
    x = jnp.broadcast_to(a[..., :, None], a.shape + (T,))
    i = jnp.arange(T)
    x = jnp.where(i[:, None] > i[None, :], x, 0.0)
    x = jnp.cumsum(x, axis=-2)
    return jnp.where(i[:, None] >= i[None, :], x, -jnp.inf)


def ssd(X, A, Bm, Cm, precision, chunk=CHUNK):
    """X (b, l, h, p) = dt * x; A (b, l, h) = dt * A; Bm, Cm (b, l, h, n).
    Returns y (b, l, h, p) from a zero initial state."""
    b, l, h, p = X.shape
    chunk = min(chunk, l)
    if l % chunk:
        raise ValueError(f"sequence {l} is not a multiple of the chunk {chunk}")
    c = l // chunk
    X, Bm, Cm = (t.reshape((b, c, chunk) + t.shape[2:]) for t in (X, Bm, Cm))
    A = A.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)  # (b, h, c, l)
    A_cum = jnp.cumsum(A, axis=-1)
    # 1. outputs within each chunk
    L = jnp.exp(segsum(A))  # (b, h, c, l, s)
    scores = mm("bclhn,bcshn->bchls", Cm, Bm, precision) * L.transpose(0, 2, 1, 3, 4)
    y_diag = mm("bchls,bcshp->bclhp", scores, X, precision)
    # 2. the state each chunk adds
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum).transpose(0, 2, 3, 1)  # (b, c, l, h)
    states = mm("bclhn,bclhp->bchpn", Bm * decay_states[..., None], X, precision)
    # 3. states carried across chunks
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    decay_chunk = jnp.exp(segsum(jnp.pad(A_cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = mm("bhzc,bchpn->bzhpn", decay_chunk, states, precision)[:, :-1]
    # 4. outputs from the carried state
    out_decay = jnp.exp(A_cum).transpose(0, 2, 3, 1)  # (b, c, l, h)
    y_off = mm("bclhn,bchpn->bclhp", Cm, states, precision) * out_decay[..., None]
    return (y_diag + y_off).reshape(b, l, h, p)


def causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal convolution: out_t = sum_k w_k x_{t - W + 1 + k}.
    x (B, S, C), w (W, C)."""
    W, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(xp[:, k : k + S] * w[k] for k in range(W))


def loss(params, tokens, labels, cfg: dict, precision: str) -> jax.Array:
    """Mean next-token cross-entropy of float32 ``params``."""
    eps = cfg["rms_norm_eps"]
    s = cfg["ssm_cfg"]
    d = cfg["d_model"]
    d_in = s["expand"] * d
    P, N, G = s["headdim"], s["d_state"], s["ngroups"]
    H = d_in // P
    V = cfg["vocab_size"]
    B, S = tokens.shape
    table = params["embed"]["table"]
    x = table[tokens]

    @jax.checkpoint
    def layer(x, p):
        h = rmsnorm(x, p["norm1"]["scale"], eps)
        m = p["mixer"]
        z = mm("bsd,de->bse", h, m["w_z"], precision)
        xs = mm("bsd,de->bse", h, m["w_x"], precision)
        bc = mm("bsd,de->bse", h, m["w_bc"], precision)
        dt = jax.nn.softplus(mm("bsd,dh->bsh", h, m["w_dt"], precision) + m["dt_bias"])
        xs = jax.nn.silu(causal_conv(xs, m["conv_x"])).reshape(B, S, H, P)
        bc = jax.nn.silu(causal_conv(bc, m["conv_bc"]))
        Bm = jnp.repeat(bc[..., : G * N].reshape(B, S, G, N), H // G, axis=2)
        Cm = jnp.repeat(bc[..., G * N :].reshape(B, S, G, N), H // G, axis=2)
        A = -jnp.exp(m["A_log"])
        y = ssd(xs * dt[..., None], dt * A, Bm, Cm, precision)
        y = (y + xs * m["D"][:, None]).reshape(B, S, d_in)
        y = rmsnorm(y * jax.nn.silu(z), m["norm"], eps)
        return x + mm("bse,ed->bsd", y, m["w_out"], precision), None

    x, _ = jax.lax.scan(layer, x, params["ssm"]["l0"])
    h = rmsnorm(x, params["final_norm"]["scale"], eps)
    return cross_entropy_sum(h, table[:V].T, labels, precision) / (B * S)
