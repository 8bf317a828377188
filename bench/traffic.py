"""The training traffic, made again on the benchmark's side.

The program's trainer draws each step's batch from the seed and the step
index: a Zipf(1.1) unigram over the first min(vocab, 4096) ids mixed 70:30
with a random four-successor bigram table.  The reference needs the same
rows without taking them from the program, so this is the same generator,
written out here; a test holds it to the program's.
"""

from __future__ import annotations

import numpy as np

HEAD = 4096
BIGRAM_SHARE = 0.7


def batch(vocab: int, seq: int, rows: int, seed: int, step: int):
    """(tokens, labels) int32 arrays (rows, seq) of step ``step`` (0-based)."""
    table_rng = np.random.default_rng(seed)
    v = min(vocab, HEAD)
    unigram = 1.0 / np.arange(1, v + 1) ** 1.1
    unigram /= unigram.sum()
    succ = table_rng.integers(0, v, size=(v, 4))
    rng = np.random.default_rng(hash((seed, step)) % (2**31))
    toks = np.empty((rows, seq + 1), np.int32)
    toks[:, 0] = rng.choice(v, size=rows, p=unigram)
    use_bigram = rng.uniform(size=(rows, seq)) < BIGRAM_SHARE
    pick = rng.integers(0, succ.shape[1], size=(rows, seq))
    iid = rng.choice(v, size=(rows, seq), p=unigram)
    for t in range(seq):
        bi = succ[toks[:, t], pick[:, t]]
        toks[:, t + 1] = np.where(use_bigram[:, t], bi, iid[:, t])
    return toks[:, :-1], toks[:, 1:]
