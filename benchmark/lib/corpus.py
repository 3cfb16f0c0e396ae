"""The benchmark's corpus: Zipf unigrams with a first-order Markov bias.

Learnable in tens of steps (a dead optimizer shows as a flat loss, which
uniform random tokens would hide), heavy-tailed like text, and a pure
function of the seed.  Made in bulk with numpy: no Python loop over tokens.
"""

from __future__ import annotations

import numpy as np


def make_tokens(seed: int, n_tokens: int, vocab_size: int, *, zipf_a: float,
                follow_p: float) -> np.ndarray:
    """``n_tokens`` ids in ``[0, vocab_size)``.

    With probability ``follow_p`` a token is its predecessor's fixed successor
    ``(prev + 1) % vocab_size`` (the Markov bias); otherwise it is drawn from a
    Zipf law ``p(rank) ~ rank**-zipf_a`` whose ranks are mapped to ids by a
    seeded permutation."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, vocab_size + 1, dtype=np.float64) ** -float(zipf_a)
    cdf = np.cumsum(p / p.sum())
    ranks = np.minimum(np.searchsorted(cdf, rng.random(n_tokens)), vocab_size - 1)
    fresh = rng.permutation(vocab_size)[ranks]
    follow = rng.random(n_tokens) < follow_p
    follow[0] = False
    idx = np.arange(n_tokens)
    # index of the last freshly drawn token at or before each position
    last = np.maximum.accumulate(np.where(follow, 0, idx))
    return ((fresh[last] + (idx - last)) % vocab_size).astype(np.int32)


def documents(tokens: np.ndarray, doc_len: int):
    """The stream cut into documents of ``doc_len`` tokens (the last shorter)."""
    for i in range(0, len(tokens), doc_len):
        yield tokens[i:i + doc_len]


def windows(tokens: np.ndarray, seq_len: int, n: int) -> np.ndarray:
    """The first ``n`` training rows of ``seq_len + 1`` tokens, as the
    program's unpacked window sampler cuts them (stride ``seq_len``)."""
    return np.stack([tokens[i * seq_len:i * seq_len + seq_len + 1] for i in range(n)])
