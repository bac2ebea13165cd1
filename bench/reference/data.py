"""The trainer's synthetic token stream, regenerated from the seed.

A copy of the documented generator (Zipf-distributed unigrams with copied
8-token motifs, one NumPy ``default_rng(seed * 1000 + worker)`` stream per
worker), so that the reference reads its own batches and can check, token
for token, the batches the trainer fed its step.
"""
from __future__ import annotations

import numpy as np


def synthetic_tokens(rng: np.random.Generator, n: int, vocab: int,
                     motif_len: int = 8, n_motifs: int = 256) -> np.ndarray:
    zipf = rng.zipf(1.3, size=n).astype(np.int64)
    toks = (zipf - 1) % vocab
    motifs = rng.integers(0, vocab, size=(n_motifs, motif_len))
    i = 0
    while i < n - motif_len:
        if rng.random() < 0.15:
            toks[i:i + motif_len] = motifs[rng.integers(0, n_motifs)]
            i += motif_len
        else:
            i += rng.integers(1, motif_len)
    return toks.astype(np.int32)


def worker_batches(seed: int, workers: int, batch: int, seq: int,
                   vocab: int, steps: int) -> np.ndarray:
    """Tokens (steps, workers, batch, seq) of the first ``steps`` steps."""
    out = np.empty((steps, workers, batch, seq), np.int32)
    for w in range(workers):
        rng = np.random.default_rng(seed * 1000 + w)
        for t in range(steps):
            out[t, w] = synthetic_tokens(rng, batch * seq,
                                         vocab).reshape(batch, seq)
    return out
