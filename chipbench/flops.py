"""FLOPs that one training step of a job requires.

Counted from the configuration file's published sizes, per step of
``batch`` sequences of ``seq`` tokens, by the ``forward_flops`` of the
file's architecture (``chipbench/arch``):

- every matrix product of the forward pass, at 2 FLOPs per multiply-add:
  projections, the MLP, the output head over the real vocabulary;
- causal attention within the sliding window: query t attends to
  ``min(t + 1, window)`` keys, for the scores and again for the values;
- each architecture's own terms, such as the SSD chunk terms of Mamba-2
  (Dao & Gu 2024, section 6), with the intra-chunk products counted on the
  causal triangle only;
- the backward pass as twice the forward.

Not counted: the input-embedding gather (no arithmetic), norms, activations,
softmax and other elementwise work, the depthwise convolution, and any
recomputation (remat) the program chooses.
"""

from __future__ import annotations

from chipbench import arch


def causal_pairs(seq: int, window: int | None) -> int:
    """Number of (query, key) pairs with key <= query < key + window."""
    w = seq if window is None else min(window, seq)
    # queries 0..w-1 see t+1 keys; the rest see w
    return w * (w + 1) // 2 + (seq - w) * w


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    """FLOPs one optimizer step requires: forward plus a backward of twice it."""
    return 3.0 * batch * arch.of(cfg).forward_flops(cfg, seq)
