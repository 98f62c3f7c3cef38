"""Seeded token traffic for the benchmark's cells.

``SyntheticTokens`` is a copy of the program's ``data/pipeline.py``
generator, kept here so that no later change to the program can change what
the benchmark feeds it: a batch is a pure function of (seed, step), drawn
from a Philox stream with a Zipf-ish skew over the vocabulary.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class SyntheticTokens:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0

    def ids_at(self, step: int) -> np.ndarray:
        """int32 ids [global_batch, seq_len + 1] for one step (pure function)."""
        rng = np.random.Generator(np.random.Philox(key=self.seed, counter=[0, 0, step, 0]))
        shape = (self.global_batch, self.seq_len + 1)
        # Zipf-ish skew without scipy: a geometric head mixed with a uniform tail.
        head = rng.geometric(p=64.0 / self.vocab_size, size=shape)
        uni = rng.integers(0, self.vocab_size, size=shape)
        use_head = rng.random(shape) < 0.5
        toks = np.where(use_head, np.minimum(head, self.vocab_size - 1), uni)
        return toks.astype(np.int32)

    def batch_at(self, step: int) -> dict:
        toks = self.ids_at(step)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
