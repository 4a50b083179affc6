"""Fold-in serving engine tests (DESIGN.md §14): slot lifecycle,
continuous batching, the bit-exact determinism contract vs the training
code path, batch-composition independence, and the compiled per-chunk
uniform draw."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.array import ArrayImpl

from repro.core import family as fam_mod
from repro.data import segment
from repro.data.synthetic import CorpusConfig, make_topic_corpus
from repro.engine import Trainer, TrainerConfig
from repro.kernels import ops
from repro.serve import (FoldInEngine, InferRequest, ServeConfig,
                         fold_in_perplexity, freeze, from_trainer,
                         reference_fold_in, result_checksum)
from repro.serve.engine import InferResult

MAX_LEN = 32
FAMILIES = ("lda", "pdp", "hdp")


@pytest.fixture(scope="module", params=FAMILIES)
def snapshot(request):
    """A lightly-trained frozen snapshot per family: a few in-process
    sweeps over a tiny corpus, then freeze(cfg, shared)."""
    fam = fam_mod.get(request.param)
    cfg = fam.config_cls(n_topics=4, vocab_size=64)
    tokens, mask, _ = make_topic_corpus(CorpusConfig(
        n_topics=4, vocab_size=64, n_docs=24, doc_len=16, seed=1))
    local, shared = fam.init_state(cfg, tokens, mask,
                                   jax.random.PRNGKey(0))
    for i in range(3):
        tables, stale = fam.build_alias(cfg, shared)
        local, deltas = fam.sweep(cfg, local, shared, tables, stale,
                                  tokens, mask,
                                  jax.random.fold_in(
                                      jax.random.PRNGKey(9), i),
                                  method="mhw")
        shared = fam.apply_delta(shared, deltas)
        shared = fam.project(shared)
    return freeze(cfg, shared)


def make_reqs(snap, n, seed=0, min_len=3, max_len=MAX_LEN):
    rng = np.random.default_rng(seed)
    return [InferRequest(
        uid=i,
        tokens=rng.integers(0, snap.vocab_size,
                            size=int(rng.integers(min_len, max_len + 1))
                            ).astype(np.int32),
        seed=100 + i) for i in range(n)]


def scfg(**kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("n_sweeps", 3)
    return ServeConfig(**kw)


# ---------------------------------------------------------------------------
# Lifecycle
# ---------------------------------------------------------------------------

def test_engine_completes_all_requests(snapshot):
    """More requests than slots: continuous batching must serve all of
    them with well-formed results."""
    eng = FoldInEngine(snapshot, scfg())
    reqs = make_reqs(snapshot, 7)
    results = eng.run(reqs)
    assert sorted(results) == list(range(7))
    k = snapshot.n_topics
    for req in reqs:
        res = results[req.uid]
        assert res.n_sweeps == 3
        assert res.theta.shape == (k,)
        assert np.isclose(res.theta.sum(), 1.0, atol=1e-4)
        assert res.assignments.shape == (len(req.tokens),)
        assert ((res.assignments >= 0)
                & (res.assignments
                   < snapshot.family.n_outcomes(snapshot.cfg))).all()
    assert eng.docs_admitted == eng.docs_harvested == 7
    assert eng.free_slots() == 4


def test_admit_step_harvest_cycle(snapshot):
    eng = FoldInEngine(snapshot, scfg(max_slots=2, n_sweeps=2))
    reqs = make_reqs(snapshot, 3)
    assert eng.admit(reqs[0])
    assert eng.admit(reqs[1])
    assert not eng.admit(reqs[2])          # grid full → False, not an error
    assert eng.free_slots() == 0
    assert eng.harvest() == []             # nothing mixed yet
    eng.step()
    assert eng.harvest() == []             # age 1 < n_sweeps 2
    eng.step()
    done = eng.harvest()
    assert sorted(r.uid for r in done) == [0, 1]
    assert eng.free_slots() == 2           # slots recycled
    assert eng.admit(reqs[2])


def test_admit_validation(snapshot):
    eng = FoldInEngine(snapshot, scfg())
    with pytest.raises(ValueError, match="empty"):
        eng.admit(InferRequest(uid=0, tokens=np.zeros(0, np.int32)))
    with pytest.raises(ValueError, match="max_len"):
        eng.admit(InferRequest(
            uid=1, tokens=np.zeros(MAX_LEN + 1, np.int32)))
    with pytest.raises(ValueError, match="vocab"):
        eng.admit(InferRequest(
            uid=2, tokens=np.asarray([snapshot.vocab_size], np.int32)))
    # nothing was admitted by the failed attempts
    assert eng.free_slots() == 4


# ---------------------------------------------------------------------------
# The §14 determinism contract
# ---------------------------------------------------------------------------

def test_fold_in_bit_identical_to_trainer_path(snapshot):
    """Acceptance: a document folded in through the batched engine is
    bit-identical — assignments AND theta — to the same document swept
    through the training path (``ModelFamily.sweep_sorted`` with
    ``fold_in=True``) with pushes disabled."""
    eng = FoldInEngine(snapshot, scfg())
    reqs = make_reqs(snapshot, 5, seed=11)
    results = eng.run(reqs)
    for req in reqs:
        _, theta, z = reference_fold_in(
            snapshot, req.tokens, req.seed, n_sweeps=3, max_len=MAX_LEN)
        res = results[req.uid]
        np.testing.assert_array_equal(res.assignments, z)
        np.testing.assert_array_equal(res.theta, theta)
        ref = InferResult(uid=req.uid, theta=theta, assignments=z,
                          n_sweeps=3)
        assert result_checksum(ref) == result_checksum(res)


def test_batch_composition_independence(snapshot):
    """The same (tokens, seed) request gives bit-identical results alone,
    with batch-mates, and under a different admission order — the chain
    is a pure function of (snapshot, tokens, seed)."""
    reqs = make_reqs(snapshot, 4, seed=23)

    solo = FoldInEngine(snapshot, scfg()).run([reqs[0]])
    pooled = FoldInEngine(snapshot, scfg()).run(reqs)
    reordered = FoldInEngine(snapshot, scfg(max_slots=2)).run(
        list(reversed(reqs)))

    for res_set in (pooled, reordered):
        np.testing.assert_array_equal(solo[0].assignments,
                                      res_set[0].assignments)
        np.testing.assert_array_equal(solo[0].theta, res_set[0].theta)
    for uid in range(4):
        assert (result_checksum(pooled[uid])
                == result_checksum(reordered[uid]))


def test_seed_changes_chain(snapshot):
    """Different request seeds must decorrelate the chains (the uniforms
    really are drawn per request, not per batch)."""
    toks = make_reqs(snapshot, 1, seed=5, min_len=MAX_LEN)[0].tokens
    a = FoldInEngine(snapshot, scfg()).run(
        [InferRequest(uid=0, tokens=toks, seed=1)])[0]
    b = FoldInEngine(snapshot, scfg()).run(
        [InferRequest(uid=0, tokens=toks, seed=2)])[0]
    assert not np.array_equal(a.assignments, b.assignments)


# ---------------------------------------------------------------------------
# The compiled per-chunk uniform draw
# ---------------------------------------------------------------------------

def per_slot_uniforms(eng, requests, c, lay):
    """The eager per-slot draw the compiled one replaces: each live slot's
    streams drawn under its own key at its single-document layout width,
    mapped through the inverse of its single-document sorted order,
    concatenated slot-major and gathered into the batched sorted order."""
    fam, cfg, l = eng.fam, eng.cfg, eng.scfg.max_len
    bounds = segment.chunk_bounds(l, max(1, min(cfg.sorted_chunks, l)))
    clen = bounds[c + 1] - bounds[c]
    e_out, mh = fam.n_outcomes(cfg), cfg.mh_steps
    cols = []
    for slot in eng._slots:
        if slot is None:
            cols.append((np.zeros((mh, clen), np.int32),)
                        + tuple(np.full((mh, clen), 0.5, np.float32)
                                for _ in range(4)))
            continue
        req = requests[slot.uid]
        row_tok = np.zeros((1, l), np.int32)
        row_tok[0, :len(req.tokens)] = req.tokens
        row_mask = np.zeros((1, l), bool)
        row_mask[0, :len(req.tokens)] = True
        lay1 = fam.build_sorted_layouts(cfg, jnp.asarray(row_tok),
                                        jnp.asarray(row_mask))[c]
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(req.seed), slot.age), c)
        u = ops._step_uniforms(key, e_out, mh, int(lay1.rows.shape[0]))
        inv = np.empty(clen, np.int64)
        inv[np.asarray(lay1.order)] = np.arange(clen)
        cols.append(tuple(np.asarray(a)[:, inv] for a in u))
    order_b = np.asarray(lay.order)
    pad = int(lay.rows.shape[0]) - order_b.shape[0]
    out = []
    for i in range(5):
        g = np.concatenate([col[i] for col in cols], axis=1)[:, order_b]
        fill = 0 if i == 0 else 0.5
        out.append(np.concatenate(
            [g, np.full((mh, pad), fill, g.dtype)], axis=1))
    return out


@pytest.mark.parametrize("max_len,tile_b", [(MAX_LEN, None), (30, None),
                                            (30, 4)],
                         ids=["equal_chunks", "unequal_chunks",
                              "padded_width"])
def test_chunk_uniforms_match_per_slot_draws(snapshot, max_len, tile_b):
    """The compiled draw is bit-identical to the eager per-slot draw, on
    every chunk, with empty slots, mixed ages and mixed lengths: chunks of
    8 positions, of 8, 7, 7, 8 (max_len 30), and of widths padded past
    the chunk length (single-document tile 4 over 7 positions → 8)."""
    snap = snapshot if tile_b is None else freeze(
        dataclasses.replace(snapshot.cfg, tile_b=tile_b), snapshot.shared)
    eng = FoldInEngine(snap, scfg(max_slots=4, max_len=max_len,
                                  n_sweeps=2))
    reqs = {r.uid: r for r in make_reqs(snap, 4, seed=31, max_len=max_len)}

    def check():
        lays = eng.fam.build_sorted_layouts(eng.cfg, eng._tokens, eng._mask)
        for c, lay in enumerate(lays):
            got = eng._chunk_uniforms(c, lay, 0)
            want = per_slot_uniforms(eng, reqs, c, lay)
            for g, w in zip(got, want):
                assert g.dtype == w.dtype
                np.testing.assert_array_equal(np.asarray(g), w)

    assert eng.admit(reqs[0]) and eng.admit(reqs[1])
    check()                                  # ages 0, 0; two empty slots
    eng.step()
    assert eng.admit(reqs[2])
    check()                                  # ages 1, 1, 0; one empty
    eng.step()
    assert sorted(r.uid for r in eng.harvest()) == [0, 1]
    assert eng.admit(reqs[3])
    check()                                  # slot 0 age 0, slot 2 age 1


def test_uniform_traces_one_per_chunk_shape(snapshot):
    """Changing live sets, lengths and ages never retrace the draw: after
    many admit/step/harvest cycles there is one trace per distinct chunk
    shape (max_len 30: chunks of 8 and 7 positions → 2)."""
    eng = FoldInEngine(snapshot, scfg(max_slots=3, max_len=30, n_sweeps=2))
    assert eng.uniform_traces == 0
    eng.run(make_reqs(snapshot, 7, seed=41, min_len=1, max_len=30))
    assert eng.sweeps_run >= 6
    assert eng.uniform_traces == 2
    eng.run(make_reqs(snapshot, 2, seed=42, max_len=30))
    assert eng.uniform_traces == 2


def test_step_makes_no_device_to_host_transfer(snapshot, monkeypatch):
    """A step queues its work and returns: no stream data comes back to
    the host.  The CPU backend hands an array to numpy through the buffer
    protocol, which the transfer guard does not see, so numpy's
    conversions and the array's own host value are made to raise too."""
    eng = FoldInEngine(snapshot, scfg())
    for req in make_reqs(snapshot, 3, seed=51):
        assert eng.admit(req)
    eng.step()                               # compile outside the guard

    def refuse(convert):
        def guarded(a, *args, **kw):
            if isinstance(a, jax.Array):
                raise AssertionError("device-to-host copy inside step")
            return convert(a, *args, **kw)
        return guarded

    monkeypatch.setattr(np, "asarray", refuse(np.asarray))
    monkeypatch.setattr(np, "array", refuse(np.array))
    monkeypatch.setattr(ArrayImpl, "_value",
                        property(refuse(ArrayImpl._value.fget)))
    with jax.transfer_guard_device_to_host("disallow"):
        assert eng.step() == 3
    monkeypatch.undo()
    assert eng.harvest() == [] and eng.sweeps_run == 2


# ---------------------------------------------------------------------------
# Quality plumbing
# ---------------------------------------------------------------------------

def test_fold_in_perplexity_finite(snapshot):
    n, length = 4, 12
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, snapshot.vocab_size, (n, length)
                          ).astype(np.int32)
    mask = np.ones((n, length), bool)
    eng = FoldInEngine(snapshot, scfg())
    results = eng.run([InferRequest(uid=i, tokens=tokens[i], seed=i)
                       for i in range(n)])
    thetas = np.stack([results[i].theta for i in range(n)])
    ppl = fold_in_perplexity(snapshot, thetas, tokens, mask)
    # uniform-random tokens score worse than the vocab size on a peaked
    # model — only finiteness and a loose ceiling are meaningful here
    assert np.isfinite(ppl) and 1.0 < ppl < snapshot.vocab_size ** 2


QUALITY_TOL = 1.25


def test_fold_in_perplexity_within_tolerance_of_training_eval():
    """Held-out documents folded in through the engine score a perplexity
    at most 1.25x the training-time evaluator's (``family.perplexity``)
    on the same documents.  A fold-in chain that drifted from the model
    fails here even while it stays deterministic."""
    n_train, held_out, doc_len = 64, 12, 48
    tokens, mask, _ = make_topic_corpus(CorpusConfig(
        n_topics=8, vocab_size=400, n_docs=n_train + held_out,
        doc_len=doc_len, seed=0))
    fam = fam_mod.get("lda")
    cfg = fam.config_cls(n_topics=8, vocab_size=400)
    trainer = Trainer(cfg, tokens[:n_train], mask[:n_train],
                      config=TrainerConfig(n_clients=1),
                      key=jax.random.PRNGKey(0))
    for _ in range(3):
        trainer.step()
    snap = from_trainer(trainer)

    ho_tokens = np.asarray(tokens[n_train:])
    ho_mask = np.asarray(mask[n_train:], bool)
    lens = ho_mask.sum(axis=1)
    reqs = [InferRequest(uid=i, tokens=ho_tokens[i, :lens[i]],
                         seed=5000 + i) for i in range(held_out)]
    results = FoldInEngine(snap, ServeConfig(
        max_slots=4, max_len=doc_len, n_sweeps=4)).run(reqs)
    thetas = np.stack([results[i].theta for i in range(held_out)])
    fold_ppl = fold_in_perplexity(snap, thetas, ho_tokens, ho_mask)
    eval_ppl = float(fam.perplexity(cfg, snap.shared, ho_tokens, ho_mask,
                                    jax.random.PRNGKey(123)))
    assert fold_ppl <= QUALITY_TOL * eval_ppl, (fold_ppl, eval_ppl)
