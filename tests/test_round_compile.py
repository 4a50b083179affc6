"""Compiled sync rounds (engine.round): compile stability, parity with the
Python reference loop, and incremental alias maintenance.

The contracts of the fused round engine (DESIGN.md §8):

1. one trace per (family, layout) — per-round cadence (round index, failure
   mask, projection flag) enters traced, so steady-state rounds never
   retrace;
2. the compiled round reproduces the PR-2 Python reference loop bit-exactly
   on the count statistics (identical RNG keying, integer-valued fp32);
3. delta-driven incremental alias rebuilds preserve the sufficient-
   statistics conservation contract exactly and stay perplexity-par with
   full per-round rebuilds (the alias table is only an MH proposal — extra
   staleness may slow mixing but must not bias the counts);
4. a partial rebuild over every row is bit-identical to a full rebuild
   (the gather → fused build kernel → scatter path vs. the dense path).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import family as family_mod
from repro.core import ps
from repro.core.fault import FaultPlan
from repro.engine import Trainer, TrainerConfig
from tests.conftest import make_family_cfg, make_synthetic_corpus

VOCAB = 64


def _cfg(name, k=4):
    return make_family_cfg(name, n_topics=k, vocab_size=VOCAB)


@pytest.fixture(scope="module")
def corpus():
    return make_synthetic_corpus(n_topics=4, vocab=VOCAB, n_docs=16,
                                 doc_len=12, seed=3)


@pytest.mark.parametrize("layout", ["scan", "sorted"])
@pytest.mark.parametrize("name", ["lda", "pdp", "hdp"])
def test_compiled_round_traces_once(name, layout, corpus):
    """Trace-counter guard: after the first round compiles, ≥3 further
    rounds (spanning projection cadence and a failure-injection window)
    must not retrace the round function."""
    tokens, mask, _ = corpus
    trainer = Trainer(_cfg(name), tokens, mask, config=TrainerConfig(
        layout=layout, n_clients=2, tau=2, project_every=2,
        fault_plan=FaultPlan.crash(1, 2, 3)))
    trainer.step()
    assert trainer.round_traces >= 1
    traced_once = trainer.round_traces
    for _ in range(3):
        trainer.step()
    trainer._sync()
    assert trainer.round_traces == traced_once
    assert trainer.consistency_error() == 0.0


@pytest.mark.parametrize("name", ["lda", "pdp", "hdp"])
def test_compiled_round_matches_python_loop(name, corpus):
    """The compiled round and the PR-2 reference loop share RNG keying and
    op order, so the integer count statistics must match bit-exactly (and
    the remaining shared stats to float tolerance)."""
    tokens, mask, _ = corpus
    trainers = {
        compiled: Trainer(_cfg(name), tokens, mask, config=TrainerConfig(
            n_clients=2, tau=2, compiled=compiled,
            fault_plan=FaultPlan.crash(0, 1, 2)))
        for compiled in (True, False)}
    for _ in range(3):
        for t in trainers.values():
            t.step()
    trainers[True]._sync()
    fam = trainers[True].family
    stats = {c: fam.stats_dict(t.shared) for c, t in trainers.items()}
    for n in fam.conserved_stats:
        np.testing.assert_array_equal(stats[True][n], stats[False][n],
                                      err_msg=n)
    for n in stats[True]:
        np.testing.assert_allclose(stats[True][n], stats[False][n],
                                   rtol=1e-6, err_msg=n)
    for t in trainers.values():
        assert t.consistency_error() == 0.0


def test_compiled_round_matches_python_loop_with_filter(corpus):
    """Same parity contract under a top-k communication filter with
    error-feedback residuals (both paths route through the shared
    filter_push, with identical keying)."""
    tokens, mask, _ = corpus
    spec = ps.FilterSpec(kind="topk", k_rows=8, random_rows=4)
    trainers = {
        compiled: Trainer(_cfg("lda"), tokens, mask, config=TrainerConfig(
            n_clients=2, filter=spec, compiled=compiled))
        for compiled in (True, False)}
    for _ in range(3):
        for t in trainers.values():
            t.step()
    trainers[True]._sync()
    np.testing.assert_array_equal(trainers[True].shared.n_wk,
                                  trainers[False].shared.n_wk)
    for c in range(2):
        np.testing.assert_array_equal(
            trainers[True].residuals[c]["n_wk"],
            trainers[False].residuals[c]["n_wk"])


@pytest.mark.parametrize("name", ["lda", "pdp", "hdp"])
def test_incremental_alias_conserves_and_stays_perplexity_par(name, corpus):
    """Incremental (delta-driven) alias rebuilds keep the exact count-
    conservation contract and stay within 2% seed-averaged perplexity of
    per-round full rebuilds — the table is an MH proposal, so partial
    staleness must not bias the chain."""
    tokens, mask, _ = corpus
    ppl = {}
    for mode in ("full", "incremental"):
        kw = (dict(alias_rebuild_threshold=0.0, alias_rebuild_rows=32,
                   alias_full_rebuild_every=100)
              if mode == "incremental" else {})
        ppls = []
        for seed in (0, 1, 2, 3, 4):
            t = Trainer(_cfg(name), tokens, mask,
                        config=TrainerConfig(n_clients=2, **kw),
                        key=jax.random.PRNGKey(seed))
            for _ in range(5):
                t.step()
            t._sync()
            assert t.consistency_error() == 0.0
            ppls.append(t.perplexity())
        ppl[mode] = sum(ppls) / len(ppls)
    rel = abs(ppl["incremental"] - ppl["full"]) / ppl["full"]
    assert rel < 0.02, ppl


@pytest.mark.parametrize("name", ["lda", "pdp", "hdp"])
def test_partial_rebuild_all_rows_equals_full_build(name, corpus):
    """rebuild_alias_rows over every row == build_alias, bit-for-bit: the
    gather → fused build-from-stats kernel → scatter path and the dense
    path must agree exactly (same op order by construction)."""
    tokens, mask, _ = corpus
    fam = family_mod.get(name)
    cfg = _cfg(name)
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    loc, sh = fam.init_state(cfg, tokens, mask, jax.random.PRNGKey(0))
    tables, stale = fam.build_alias(cfg, sh)
    _, d = fam.sweep(cfg, loc, sh, tables, stale, tokens, mask,
                     jax.random.PRNGKey(1))
    sh = fam.apply_delta(sh, d)

    t_full, s_full = fam.build_alias(cfg, sh)
    rows = jnp.arange(cfg.vocab_size, dtype=jnp.int32)
    t_inc, s_inc = fam.rebuild_alias_rows(
        cfg, sh, tables, stale, rows, jnp.ones_like(rows, bool))
    np.testing.assert_array_equal(t_full.prob, t_inc.prob)
    np.testing.assert_array_equal(t_full.alias, t_inc.alias)
    np.testing.assert_array_equal(t_full.mass, t_inc.mass)
    np.testing.assert_array_equal(s_full, s_inc)

    # Sub-selection with a validity mask: invalid rows keep their resident
    # (stale) entries, valid rows get the fresh build.
    sub = jnp.array([3, 9, 11, 40], jnp.int32)
    valid = jnp.array([True, False, True, False])
    t_sub, s_sub = fam.rebuild_alias_rows(cfg, sh, tables, stale, sub, valid)
    np.testing.assert_array_equal(t_sub.prob[3], t_full.prob[3])
    np.testing.assert_array_equal(t_sub.prob[9], tables.prob[9])
    np.testing.assert_array_equal(s_sub[11], s_full[11])
    np.testing.assert_array_equal(s_sub[40], stale[40])


def test_incremental_requires_compiled(corpus):
    tokens, mask, _ = corpus
    with pytest.raises(ValueError, match="compiled"):
        Trainer(_cfg("lda"), tokens, mask, config=TrainerConfig(
            compiled=False, alias_rebuild_threshold=0.0))
