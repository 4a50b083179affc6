"""Synthetic data: power-law topic-model corpora and LM token streams.

The topic-model generator follows the paper's data regime: Zipf-distributed
word frequencies inside each topic (the power-law the PDP models), Dirichlet
document-topic mixtures, shardable into per-client document shards.  Every
topic shares one Zipf profile under its own vocabulary permutation, so a
word draw is one inverse-CDF search of the shared profile: a corpus of
millions of tokens over a vocabulary of 10^5 types and 10^3 topics takes
seconds.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class CorpusConfig:
    n_topics: int = 16
    vocab_size: int = 2048
    n_docs: int = 1024
    doc_len: int = 128          # padded length; actual lengths vary
    theta_conc: float = 0.2     # document Dirichlet
    zipf_a: float = 1.2         # within-topic word-frequency power law
    min_len_frac: float = 0.5
    seed: int = 0


def make_topic_corpus(cfg: CorpusConfig):
    """Returns (tokens (D, L) int32, mask (D, L) bool, true_phi (K, V))."""
    rng = np.random.default_rng(cfg.seed)
    k, v = cfg.n_topics, cfg.vocab_size

    # Power-law topics: each topic permutes a Zipf profile over a random
    # subset ordering of the vocabulary (overlapping supports) — topic t
    # gives its r-th most frequent slot to word perm[t, r].
    ranks = np.arange(1, v + 1, dtype=np.float64)
    zipf = ranks ** (-cfg.zipf_a)
    zipf = zipf / zipf.sum()
    perm = np.stack([rng.permutation(v).astype(np.int32) for _ in range(k)])
    phi = np.zeros((k, v))
    np.put_along_axis(phi, perm, zipf[None, :], axis=1)
    cdf = np.cumsum(zipf)

    tokens = np.zeros((cfg.n_docs, cfg.doc_len), np.int32)
    mask = np.zeros((cfg.n_docs, cfg.doc_len), bool)
    min_len = max(1, int(cfg.doc_len * cfg.min_len_frac))
    for d in range(cfg.n_docs):
        length = rng.integers(min_len, cfg.doc_len + 1)
        theta = rng.dirichlet(np.full(k, cfg.theta_conc))
        zs = rng.choice(k, size=length, p=theta)
        # Inverse-CDF draw of each token's Zipf rank, mapped through its
        # topic's permutation: O(log V) per token, no per-topic tables.
        rank = np.searchsorted(cdf, rng.random(length), side="right")
        tokens[d, :length] = perm[zs, np.minimum(rank, v - 1)]
        mask[d, :length] = True
    return tokens, mask, phi


def shard_corpus(tokens, mask, n_shards: int):
    """Split documents into per-client shards (paper §5.2 data layout)."""
    d = tokens.shape[0]
    per = d // n_shards
    return [(tokens[i * per:(i + 1) * per], mask[i * per:(i + 1) * per])
            for i in range(n_shards)]


# ---------------------------------------------------------------------------
# LM token stream (for the assigned-architecture trainer)
# ---------------------------------------------------------------------------

def lm_batches(vocab_size: int, batch: int, seq_len: int, n_batches: int,
               seed: int = 0, kind: str = "markov", noise: float = 0.1):
    """Synthetic language streams without external data.

    kind="affine": next = (3·cur + 1) mod V with ``noise`` random tokens —
      near-deterministic, learnable to ~1-2 nats within tens of steps (used
      by convergence tests / examples).
    kind="markov": sparse random 2nd-order Markov chain — harder, used for
      longer training runs.
    """
    rng = np.random.default_rng(seed)
    if kind == "affine":
        for _ in range(n_batches):
            out = np.zeros((batch, seq_len), np.int64)
            out[:, 0] = rng.integers(0, vocab_size, size=batch)
            flip = rng.random((batch, seq_len)) < noise
            rnd = rng.integers(0, vocab_size, size=(batch, seq_len))
            for t in range(1, seq_len):
                nxt = (out[:, t - 1] * 3 + 1) % vocab_size
                out[:, t] = np.where(flip[:, t], rnd[:, t], nxt)
            yield {"tokens": out.astype(np.int32)}
        return
    branch = 8
    # successor table: each (context hash) -> `branch` candidate tokens.
    # Context count scales with vocab so small test vocabularies stay
    # learnable within tens of steps.
    n_ctx = min(1 << 16, 4 * vocab_size)
    succ = rng.integers(0, vocab_size, size=(n_ctx, branch), dtype=np.int64)

    def hash_ctx(a, b):
        return ((a * 1000003) ^ b) % n_ctx

    for i in range(n_batches):
        out = np.zeros((batch, seq_len), np.int64)
        out[:, 0] = rng.integers(0, vocab_size, size=batch)
        out[:, 1] = rng.integers(0, vocab_size, size=batch)
        choice = rng.integers(0, branch, size=(batch, seq_len))
        for t in range(2, seq_len):
            ctx = hash_ctx(out[:, t - 2], out[:, t - 1])
            out[:, t] = succ[ctx, choice[:, t]]
        yield {"tokens": out.astype(np.int32)}
