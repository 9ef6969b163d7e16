"""The benchmark's token generator.

A copy of the repository's synthetic pipeline, kept here so that the
yardstick cannot move: batches are a pure function of ``(seed, step)``
through a counter-based PRNG.  Tokens are Zipf-distributed over the
vocabulary (id 0 is kept for BOS) and spans of each row are copied from
earlier in the row, so that the loss can fall.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class TokenStream:
    vocab_size: int
    seq_len: int
    batch: int
    seed: int
    zipf_a: float = 1.2
    structure: float = 0.5  # share of positions copied from earlier in the row

    def batch_at(self, step: int) -> Tuple[np.ndarray, np.ndarray]:
        """(tokens, labels), each (batch, seq_len) int32, of step ``step``."""
        rng = np.random.Generator(np.random.Philox(key=self.seed, counter=[0, 0, 0, step]))
        n = self.batch * (self.seq_len + 1)
        draws = rng.zipf(self.zipf_a, size=n).astype(np.int64)
        toks = (draws - 1) % max(self.vocab_size - 2, 1) + 1
        toks = toks.reshape(self.batch, self.seq_len + 1).astype(np.int32)
        toks[:, 0] = 0
        span = max(self.seq_len // 16, 1)
        for _ in range(int(self.structure * self.seq_len / span)):
            src = rng.integers(0, self.seq_len - span)
            dst = rng.integers(src + 1, self.seq_len - span + 1)
            toks[:, dst : dst + span] = toks[:, src : src + span]
        return toks[:, :-1], toks[:, 1:]
