"""Data pipeline + optimizer tests."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests.hypothesis_compat import given, settings, st

from repro.data.synthetic import (CorpusConfig, lm_batches, make_topic_corpus,
                                  shard_corpus)
from repro.optim import adamw


class TestCorpus:
    def test_shapes_and_mask(self):
        cfg = CorpusConfig(n_topics=4, vocab_size=64, n_docs=16, doc_len=24)
        tokens, mask, phi = make_topic_corpus(cfg)
        assert tokens.shape == (16, 24)
        assert mask.shape == (16, 24)
        assert phi.shape == (4, 64)
        assert tokens.min() >= 0 and tokens.max() < 64
        # masked positions are contiguous prefixes
        for d in range(16):
            lens = mask[d].sum()
            assert mask[d, :lens].all() and not mask[d, lens:].any()

    def test_power_law_marginals(self):
        """Word frequencies must be heavy-tailed (the PDP's motivation)."""
        cfg = CorpusConfig(n_topics=4, vocab_size=256, n_docs=256,
                           doc_len=64, zipf_a=1.2)
        tokens, mask, _ = make_topic_corpus(cfg)
        counts = np.bincount(tokens[mask], minlength=256)
        counts = np.sort(counts)[::-1].astype(float)
        top10 = counts[:10].sum() / counts.sum()
        assert top10 > 0.25, f"not heavy-tailed: top-10 share {top10:.3f}"

    def test_words_follow_true_phi(self):
        """With one topic every token is a draw from ``true_phi[0]``: the
        empirical word distribution matches it (total variation within a
        few multiples of its sampling noise, ~sqrt(V/N)/2 ≈ 0.03 here)."""
        cfg = CorpusConfig(n_topics=1, vocab_size=64, n_docs=256,
                           doc_len=64)
        tokens, mask, phi = make_topic_corpus(cfg)
        emp = np.bincount(tokens[mask], minlength=64) / mask.sum()
        assert 0.5 * np.abs(emp - phi[0]).sum() < 0.08

    def test_sharding_partition(self):
        cfg = CorpusConfig(n_topics=4, vocab_size=64, n_docs=16, doc_len=8)
        tokens, mask, _ = make_topic_corpus(cfg)
        shards = shard_corpus(tokens, mask, 4)
        assert len(shards) == 4
        rebuilt = np.concatenate([t for t, _ in shards])
        np.testing.assert_array_equal(rebuilt, tokens[:16])

    def test_lm_batches_learnable_stream(self):
        batches = list(lm_batches(64, 4, 16, 3, kind="affine", noise=0.0))
        assert len(batches) == 3
        t = batches[0]["tokens"]
        # noise=0: exact affine recurrence
        np.testing.assert_array_equal(t[:, 1:], (t[:, :-1] * 3 + 1) % 64)


class TestAdamW:
    def test_descends_quadratic(self):
        params = {"w": jnp.asarray([3.0, -2.0])}
        state = adamw.init(params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            params, state = adamw.update(params, grads, state, lr=5e-2,
                                         weight_decay=0.0)
        assert float(jnp.abs(params["w"]).max()) < 1e-2

    def test_weight_decay_only_on_matrices(self):
        params = {"w": jnp.ones((2, 2)), "b": jnp.ones((2,))}
        state = adamw.init(params)
        grads = jax.tree.map(jnp.zeros_like, params)
        p2, _ = adamw.update(params, grads, state, lr=0.1, weight_decay=0.5)
        assert float(p2["w"].max()) < 1.0          # decayed
        np.testing.assert_array_equal(np.asarray(p2["b"]), 1.0)  # not decayed

    @given(st.floats(1e-5, 1e-2), st.integers(1, 50), st.integers(60, 200))
    @settings(max_examples=20, deadline=None)
    def test_schedule_bounds(self, peak, warmup, total):
        for s in [0, warmup, (warmup + total) // 2, total, total + 10]:
            lr = float(adamw.cosine_schedule(jnp.asarray(s), peak_lr=peak,
                                             warmup=warmup, total=total))
            assert 0.0 <= lr <= peak * (1 + 1e-6)
        # end of schedule: min_ratio * peak
        end = float(adamw.cosine_schedule(jnp.asarray(total), peak_lr=peak,
                                          warmup=warmup, total=total))
        assert end == pytest.approx(0.1 * peak, rel=1e-3)

    def test_grad_clip_engages(self):
        params = {"w": jnp.zeros((4,))}
        state = adamw.init(params)
        huge = {"w": jnp.full((4,), 1e6)}
        p_clip, _ = adamw.update(params, huge, state, lr=1.0, grad_clip=1.0,
                                 weight_decay=0.0)
        # post-clip step is bounded by lr·(1/sqrt(v̂)·m̂) ≈ lr
        assert float(jnp.abs(p_clip["w"]).max()) <= 1.0 + 1e-5
