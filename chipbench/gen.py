"""Inputs made from the run's seed: the key of its weights and its
training batches. Every seed gets the same work, batches of one shape;
the seed chooses the token ids and the weights."""

from __future__ import annotations

from typing import Dict

import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """A JAX threefry key's two 32-bit words for any non-negative seed (a
    plain ``PRNGKey`` keeps only the low 32 bits of a larger one)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


class SyntheticLM:
    """Zipfian bigram token stream: token t is one of 8 fixed successors
    of token t-1, drawn with Zipf weights. Batch ``i`` is a function of
    (seed, i) alone. The program's ``repro.data.pipeline.SyntheticLM``
    does the same; this copy keeps the training input fixed whatever the
    program changes."""

    def __init__(self, vocab: int, seq: int, batch: int, seed: int,
                 zipf_a: float = 1.2):
        self.vocab, self.seq, self.batch, self.seed = vocab, seq, batch, seed
        rng = np.random.default_rng([seed, 0x5EED])
        self.succ = rng.integers(0, vocab, size=(vocab, 8))
        p = np.arange(1, 9, dtype=np.float64) ** (-zipf_a)
        self.succ_p = p / p.sum()
        self.step = 0

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([self.seed, 1, step])
        B, L = self.batch, self.seq
        toks = np.empty((B, L + 1), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, size=B)
        choices = rng.choice(8, size=(B, L), p=self.succ_p)
        for t in range(1, L + 1):
            toks[:, t] = self.succ[toks[:, t - 1], choices[:, t - 1]]
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def __iter__(self):
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        b = self.batch_at(self.step)
        self.step += 1
        return b
