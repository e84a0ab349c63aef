"""The one traffic generator: training batches from a mix's parameters.

A copy of the token arithmetic of the program's synthetic data pipeline
(``repro.data.synthetic``): a fixed random Markov chain over the vocabulary
with ``branching`` successors a token, every row started at a random token.
It lives here so that a change to the program cannot move the yardstick.
Seeds are any whole number (NumPy's ``SeedSequence``), so seeds past 32
bits are taken as they are.
"""
from __future__ import annotations

import numpy as np


class MarkovBatches:
    """``batch_at(step)`` is a pure function of ``(seed, step)``."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        if mix["kind"] != "markov":
            raise ValueError(f"unknown traffic kind {mix['kind']!r}")
        self.batch, self.seq = int(mix["batch"]), int(mix["seq"])
        self.branching = int(mix["branching"])
        self.vocab, self.seed = int(vocab), int(seed)
        rng = np.random.default_rng([self.seed, 0])
        self.table = rng.integers(0, self.vocab,
                                  size=(self.vocab, self.branching),
                                  dtype=np.int32)

    @property
    def tokens_per_step(self) -> int:
        return self.batch * self.seq

    def batch_at(self, step: int) -> np.ndarray:
        """(batch, seq) int32 tokens; the labels are the same tokens (the
        loss predicts position t + 1 from position t)."""
        rng = np.random.default_rng([self.seed, 1, int(step)])
        toks = np.empty((self.batch, self.seq), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, size=self.batch)
        choices = rng.integers(0, self.branching,
                               size=(self.batch, self.seq - 1))
        for t in range(1, self.seq):
            toks[:, t] = self.table[toks[:, t - 1], choices[:, t - 1]]
        return toks
