"""The yardstick on the CPU: work counts, peaks, the corpus generator and
the references' comparisons."""

from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import corpus
from chiptest import CHIP, load_run


def test_sweep_work_hand_counted():
    run = load_run(CHIP.parents[1])
    # tokens=10, rows=3, K=4, mh_steps=2.  LDA: E=4.
    #   ops   = 10*(2*4 + 20*2) + 3*2*4            = 480 + 24 = 504
    #   bytes = 3*4*4*4 + 4*4 + 10*(4*4 + 5*2*4 + 12) = 192 + 16 + 680
    lda = run.load_module("work", "mhw_sweep_fused").work(
        tokens=10, rows=3, n_topics=4, mh_steps=2)
    assert lda == (504.0, 888.0)


def test_peaks_keyed_by_device_kind():
    peaks = json.loads((CHIP / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]


def test_corpus_is_a_function_of_the_seed():
    kw = dict(n_topics=8, vocab_size=1000, n_docs=20, doc_len=16,
              theta_conc=0.2, zipf_a=1.2, min_len=8)
    a = corpus.make_corpus(seed=2**33 + 1, **kw)
    b = corpus.make_corpus(seed=2**33 + 1, **kw)
    c = corpus.make_corpus(seed=2**33 + 2, **kw)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    tokens, mask, topics = a
    assert tokens.max() < 1000 and topics.max() < 8
    assert (mask.sum(1) >= 8).all() and (mask.sum(1) <= 16).all()
    fixed = corpus.make_corpus(seed=3, lengths=np.full(20, 11), **kw)
    assert (fixed[1].sum(1) == 11).all()


@pytest.mark.parametrize("size", [1000, 1 << 10, 131072])
def test_permute_is_a_bijection(size):
    p = corpus.permute(np.arange(size), np.full(size, 77, np.uint64), size)
    assert np.array_equal(np.sort(p), np.arange(size))


def _state(model, seed=0, docs=12, length=300):
    """A corpus whose head word repeats far past bfloat16's exact
    integers (256), and a random state."""
    rng = np.random.default_rng(seed)
    tokens = np.where(rng.random((docs, length)) < 0.5, 0,
                      rng.integers(0, model["vocab_size"], (docs, length)))
    tokens = tokens.astype(np.int32)
    mask = np.ones_like(tokens, bool)
    state = {"z": rng.integers(0, model["n_topics"], tokens.shape).astype(
        np.int32)}
    return tokens, mask, state


def test_control_fails_and_reference_passes():
    """The reference in the program's place passes the comparisons; in
    bfloat16 its kept counts fail ``count_gap``; a state left unchanged
    reads a ``gibbs_gap`` and ``stuck_docs`` of 1."""
    run = load_run(CHIP.parents[1])
    ref = run.load_module("refs", "lda")
    model = {"n_topics": 2, "vocab_size": 32, "alpha": 0.1, "beta": 0.01}
    tokens, mask, old = _state(model)
    new, kept = ref.control_round(model, tokens, mask, old, 5, jnp.float32)
    assert ref.count_gap(model, tokens, mask, kept) == 0.0
    rd = ref.round_readings(model, tokens, mask, old, new)
    assert rd["gibbs_gap"] < 0.2 and abs(rd["stuck_docs"]) < 1e-6
    _, kept16 = ref.control_round(model, tokens, mask, old, 5, jnp.bfloat16)
    assert ref.count_gap(model, tokens, mask, kept16) >= 1.0
    same = ref.round_readings(model, tokens, mask, old, old)
    assert same["gibbs_gap"] == pytest.approx(1.0)
    assert same["stuck_docs"] == pytest.approx(1.0)
    altered = dict(ref.consistent_kept(model, tokens, mask, new))
    altered["z"] = altered["z"].copy()
    altered["z"][0, 0] = (altered["z"][0, 0] + 1) % model["n_topics"]
    assert ref.count_gap(model, tokens, mask, altered) >= 1.0


def test_log_joint_hand_computed():
    """K=2, V=2, α=0.5, β=1; n_wk = [[1, 0], [0, 1]] so φ = [[2/3, 1/3],
    [1/3, 2/3]]; one document, words (0, 1), topics (0, 0):
    log φ_00 + log φ_10 + log Γ(1) − log Γ(3) + log Γ(2.5) + log Γ(0.5)
    − 2 log Γ(0.5) = log(2/9) − log 2 + log(1.5 · 0.5)."""
    from math import log
    ref = load_run(CHIP.parents[1]).load_module("refs", "lda")
    model = {"n_topics": 2, "vocab_size": 2, "alpha": 0.5, "beta": 1.0}
    stats = {"n_wk": np.eye(2, dtype=np.float32),
             "n_k": np.ones(2, np.float32)}
    got = ref.log_joint(model, stats, [np.array([0, 1])], [np.array([0, 0])])
    assert got == pytest.approx(log(2 / 9) - log(2) + log(0.75), rel=1e-12)


def _serve_case(n_docs=40):
    """A small served set: frozen statistics and held-out documents."""
    ref = load_run(CHIP.parents[1]).load_module("refs", "lda")
    model = {"n_topics": 8, "vocab_size": 64, "alpha": 0.1, "beta": 0.01}
    tokens, mask, topics = corpus.make_corpus(
        n_topics=8, vocab_size=64, n_docs=n_docs + 16, doc_len=48,
        theta_conc=0.2, zipf_a=1.2, min_len=32, seed=9)
    stats = ref.frozen_stats(model, tokens[:n_docs], mask[:n_docs],
                             topics[:n_docs])
    docs = [tokens[i][mask[i]] for i in range(n_docs, n_docs + 16)]
    return ref, model, stats, docs


def test_serve_readings_separate_control():
    ref, model, stats, docs = _serve_case()
    good = ref.fold_in(model, stats, docs, 1, 5, jnp.float32)
    served = [(d, z, th) for d, (z, th) in zip(docs, good)]
    rd = ref.serve_readings(model, stats, served, seed=2, n_sweeps=5)
    assert rd["theta_gap"] <= 1e-7 and rd["foldin_ppl_ratio"] < 1.25
    low = ref.fold_in(model, stats, docs, 1, 5, jnp.bfloat16)
    rd16 = ref.serve_readings(
        model, stats, [(d, z, np.asarray(th, np.float32))
                       for d, (z, th) in zip(docs, low)], seed=2, n_sweeps=5)
    assert rd16["theta_gap"] > 1e-4


@pytest.mark.parametrize("fault", ["chain_unchanged", "conditional_no_doc"])
def test_serve_fault_fails_logp_gap(fault):
    """A chain left at its initial draws, or one whose conditional leaves
    out the document term, reads a ``foldin_logp_gap`` over the cell's
    limit; the exact fold-in reads under it."""
    ref, model, stats, docs = _serve_case()
    limit = json.loads((CHIP / "limits" / "lda_k1024_serve_open.json")
                       .read_text())["foldin_logp_gap"]
    if fault == "chain_unchanged":
        rng = np.random.default_rng(1)
        zs = [rng.integers(0, model["n_topics"], len(d)) for d in docs]
    else:
        zs = [z for z, _ in ref.fold_in(dict(model, alpha=1e6), stats, docs,
                                        1, 5, jnp.float32)]
    good = [z for z, _ in ref.fold_in(model, stats, docs, 1, 5, jnp.float32)]

    def gap(zs):
        return ref.serve_readings(
            model, stats, [(d, z, ref.theta_of(model, z, len(z)))
                           for d, z in zip(docs, zs)],
            seed=2, n_sweeps=5)["foldin_logp_gap"]
    assert gap(good) < limit < gap(zs)
