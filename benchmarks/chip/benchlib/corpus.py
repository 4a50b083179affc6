"""Seeded power-law topic corpora: the benchmark's traffic generator.

The same model of data as the program's own ``data/synthetic.py``
(``make_topic_corpus``): every topic gives its r-th most frequent slot,
with Zipf(``zipf_a``) frequency, to word ``perm_t(r)``; each document
draws topic proportions from a symmetric Dirichlet(``theta_conc``) and
a length uniform over [``min_len``, ``doc_len``].  Two departures keep a
run's set-up short and its memory small: each topic's permutation is a
keyed Feistel bijection of the vocabulary evaluated per token (no
(K, V) permutation table), and documents are drawn all at once.

Everything is a function of ``seed`` (any non-negative integer).
"""

from __future__ import annotations

import numpy as np

_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer: a well-mixed 64-bit hash of ``x``."""
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def permute(values: np.ndarray, keys: np.ndarray, size: int) -> np.ndarray:
    """``perm_key(value)``: a keyed bijection of [0, size), one key per entry.

    A four-round balanced Feistel network over the smallest even number
    of bits that covers ``size``, cycle-walked back into range.
    """
    half = max(1, (int(size - 1).bit_length() + 1) // 2)
    lo_mask = np.uint64((1 << half) - 1)
    x = values.astype(np.uint64)
    k = keys.astype(np.uint64)
    todo = np.ones(x.shape, bool)
    while todo.any():
        left, right = x[todo] >> np.uint64(half), x[todo] & lo_mask
        kk = k[todo]
        for rnd in range(4):
            f = _mix((right + kk * np.uint64(4) + np.uint64(rnd)) & _M64)
            left, right = right, left ^ (f & lo_mask)
        x[todo] = (left << np.uint64(half)) | right
        todo = x >= np.uint64(size)
    return x.astype(np.int64)


def make_corpus(*, n_topics: int, vocab_size: int, n_docs: int, doc_len: int,
                theta_conc: float, zipf_a: float, min_len: int, seed: int,
                lengths: np.ndarray | None = None
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(tokens (D, L) int32, mask (D, L) bool, topics (D, L) int32) drawn
    from ``seed``: ``topics`` are the generating topic of each token.
    ``lengths`` fixes the documents' lengths instead of drawing them."""
    rng = np.random.default_rng(seed)
    topic_keys = rng.integers(0, 2**62, size=n_topics, dtype=np.uint64)
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    zipf_cdf = np.cumsum(ranks ** (-zipf_a))
    zipf_cdf /= zipf_cdf[-1]

    drawn = rng.integers(min_len, doc_len + 1, size=n_docs)
    lengths = drawn if lengths is None else np.asarray(lengths)
    theta = rng.standard_gamma(theta_conc, size=(n_docs, n_topics))
    theta /= theta.sum(axis=1, keepdims=True)
    # Per-document categorical draws by one search over the concatenated
    # CDFs (row d's CDF shifted by d keeps the whole array sorted).
    cdf = np.cumsum(theta, axis=1)
    cdf[:, -1] = 1.0
    offs = np.arange(n_docs, dtype=np.float64)[:, None]
    u = rng.random((n_docs, doc_len))
    flat = np.searchsorted((cdf + offs).ravel(), (u + offs).ravel(),
                           side="right")
    z = np.minimum(flat.reshape(n_docs, doc_len)
                   - np.arange(n_docs)[:, None] * n_topics, n_topics - 1)
    rank = np.minimum(np.searchsorted(zipf_cdf, rng.random((n_docs, doc_len)),
                                      side="right"), vocab_size - 1)
    words = permute(rank.ravel(), topic_keys[z.ravel()], vocab_size)
    mask = np.arange(doc_len)[None, :] < lengths[:, None]
    tokens = np.where(mask, words.reshape(n_docs, doc_len), 0)
    return (tokens.astype(np.int32), mask,
            np.where(mask, z, 0).astype(np.int32))
