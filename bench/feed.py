"""Token traffic for a cell, made from ``--seed`` and the traffic file.

The order-2 Markov token streams of the program's ``repro.data.SyntheticLM``
(each context prefers a handful of next tokens, 5% uniform noise), copied
here so that the benchmark's inputs do not depend on the program under
test. A run draws ``pool_batches`` distinct batches, each
``[clusters, mus_per_cluster * batch_per_mu, seq]``; every row of the pool
differs, and the window cycles through the pool.
"""
from __future__ import annotations

import numpy as np

CTX_MOD = 997
NOISE = 0.05


def markov_tokens(vocab: int, rows: int, seq: int,
                  rng: np.random.Generator) -> np.ndarray:
    """``[rows, seq]`` int32 tokens from a transition table drawn from
    ``rng``."""
    table = rng.integers(0, vocab, size=(CTX_MOD, 4))
    out = np.empty((rows, seq), dtype=np.int32)
    t1 = rng.integers(0, vocab, rows)
    t2 = rng.integers(0, vocab, rows)
    for i in range(seq):
        ctx = (t1 * 31 + t2 * 17) % CTX_MOD
        nxt = table[ctx, rng.integers(0, 4, rows)]
        noise = rng.random(rows) < NOISE
        nxt = np.where(noise, rng.integers(0, vocab, rows), nxt)
        out[:, i] = nxt
        t2, t1 = t1, nxt
    return out


def batch_pool(seed: int, *, vocab: int, clusters: int, rows_per_cluster: int,
               seq: int, batches: int) -> np.ndarray:
    """``[batches, clusters, rows_per_cluster, seq]`` int32, the same for
    the same seed."""
    rng = np.random.default_rng([seed, 1])
    toks = markov_tokens(vocab, batches * clusters * rows_per_cluster, seq, rng)
    return toks.reshape(batches, clusters, rows_per_cluster, seq)
