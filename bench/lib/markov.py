"""Seeded Markov token stream: the training traffic.

A copy of the program's ``SyntheticLMDataset.batch_at`` (data/pipeline.py)
kept here so that no change to the program moves the benchmark's inputs.
Tokens follow x_{t+1} = (31 x_t + 17) mod V, replaced by a uniform draw
with probability ``noise``, so the loss can fall.  Every row starts from
its own uniform draw, so the rows of a batch differ.
"""
from __future__ import annotations

import numpy as np


def markov_batch(vocab: int, seq: int, batch: int, seed: int, step: int,
                 noise: float = 0.1) -> dict:
    rng = np.random.default_rng((seed * 1_000_003 + step) * 65_537)
    toks = [rng.integers(0, vocab, size=(batch, 1))]
    for _ in range(seq):
        nxt = (toks[-1] * 31 + 17) % vocab
        flip = rng.random((batch, 1)) < noise
        rand = rng.integers(0, vocab, size=(batch, 1))
        toks.append(np.where(flip, rand, nxt))
    out = np.concatenate(toks, axis=1).astype(np.int32)
    return {"tokens": out[:, :-1], "labels": out[:, 1:]}
