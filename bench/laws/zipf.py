"""Zipf-distributed token ids: rank r is drawn with probability
proportional to r**-exponent, and the ranks are mapped onto token ids by a
permutation drawn from the seed, so that which ids are frequent changes
with the seed while the law does not."""
from __future__ import annotations

import numpy as np


def generate(mix: dict, vocab_size: int, instances: int,
             seed: int) -> np.ndarray:
    """``instances`` rows of seq_len + 1 ids; any integer seed, 64-bit
    seeds included."""
    length = int(mix["seq_len"]) + 1
    s = int(seed) & (2 ** 64 - 1)
    rng = np.random.default_rng([s & 0xFFFFFFFF, s >> 32, 1])
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(mix["exponent"]))
    cdf /= cdf[-1]
    perm = rng.permutation(vocab_size).astype(np.int32)
    u = rng.random(instances * length)
    r = np.minimum(np.searchsorted(cdf, u, side="right"), vocab_size - 1)
    return perm[r].reshape(instances, length)
