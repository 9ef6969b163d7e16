"""FLOPs that one training step of a job requires.

Counted from the configuration file's published sizes, per step of
``batch`` sequences of ``seq`` tokens:

- every matrix product of the forward pass, at 2 FLOPs per multiply-add:
  projections, the MLP, the output head over the real vocabulary;
- causal attention within the sliding window: query t attends to
  ``min(t + 1, window)`` keys, for the scores and again for the values;
- the SSD chunk terms of Mamba-2 (Dao & Gu 2024, section 6), with the
  intra-chunk products counted on the causal triangle only;
- the backward pass as twice the forward.

Not counted: the input-embedding gather (no arithmetic), norms, activations,
softmax and other elementwise work, the depthwise convolution, and any
recomputation (remat) the program chooses.
"""

from __future__ import annotations


def causal_pairs(seq: int, window: int | None) -> int:
    """Number of (query, key) pairs with key <= query < key + window."""
    w = seq if window is None else min(window, seq)
    # queries 0..w-1 see t+1 keys; the rest see w
    return w * (w + 1) // 2 + (seq - w) * w


def transformer_forward(cfg: dict, seq: int) -> float:
    """Forward FLOPs of one sequence through a GQA transformer with a SwiGLU MLP."""
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    ff = cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    per_token = 2 * d * (h * hd + 2 * kv * hd) + 2 * h * hd * d + 3 * 2 * d * ff
    attn = 2 * 2 * h * hd * causal_pairs(seq, cfg.get("sliding_window"))
    head = 2 * d * cfg["vocab_size"] * seq
    return layers * (per_token * seq + attn) + head


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


def mamba2_forward(cfg: dict, seq: int) -> float:
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


FORWARD = {"transformer": transformer_forward, "mamba2": mamba2_forward}


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    """FLOPs one optimizer step requires: forward plus a backward of twice it."""
    return 3.0 * batch * FORWARD[cfg["architecture"]](cfg, seq)
