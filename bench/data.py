"""The benchmark's token generator: Zipf unigrams with planted motifs.

A copy of the synthetic LM stream the trainer's data pipeline produces, kept
here so that the benchmark's inputs cannot change with the program.  A batch
is a pure function of ``(seed, step)``: the same seed gives the same tokens
in every run, and every seed gives batches of the same shape.
"""
from __future__ import annotations

import numpy as np


class SyntheticLM:
    """``batch(step)`` -> ``{"tokens", "labels"}``, int32 ``(batch, seq)``."""

    def __init__(self, vocab_size: int, seq_len: int, batch: int, seed: int,
                 zipf_a: float = 1.3, motif_len: int = 16, n_motifs: int = 64):
        self.vocab_size, self.seq_len, self.batch_size = vocab_size, seq_len, batch
        self.seed, self.motif_len = seed, motif_len
        rng = np.random.default_rng(seed)
        # fixed motif bank: the second half of a motif is predictable from
        # its first half, so a model has structure to learn
        self.motifs = rng.integers(0, vocab_size, size=(n_motifs, motif_len),
                                   dtype=np.int64)
        p = 1.0 / np.arange(1, vocab_size + 1, dtype=np.float64) ** zipf_a
        self.unigram = p / p.sum()

    def _tokens(self, rng: np.random.Generator) -> np.ndarray:
        S, L = self.seq_len + 1, self.motif_len
        toks = rng.choice(self.vocab_size, size=(self.batch_size, S),
                          p=self.unigram)
        if S > L:
            n_plants = max(S // (4 * L), 1)
            for b in range(self.batch_size):
                for _ in range(n_plants):
                    m = self.motifs[rng.integers(0, len(self.motifs))]
                    start = rng.integers(0, S - L)
                    toks[b, start:start + L] = m
        return toks.astype(np.int32)

    def batch(self, step: int) -> dict:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, step]))
        toks = self._tokens(rng)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
