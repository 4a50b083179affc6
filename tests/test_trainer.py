"""engine.Trainer: the unified driver over the ModelFamily registry.

The acceptance contract of the API redesign:

1. every family runs through both layouts with bit-exact
   sufficient-statistics conservation (single-client AND multi-client
   dense sync — integer-valued fp32 counts are exact);
2. multi-client bounded-staleness rounds (tau > 1) are perplexity-matched
   between the sorted fast path and the scan oracle;
3. the Trainer lifecycle knobs (alias cadence, filters + error feedback,
   failure injection, projection cadence) work for any family.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.core import ps
from repro.core.fault import FaultPlan
from repro.engine import Trainer, TrainerConfig
from tests.conftest import make_family_cfg, make_synthetic_corpus

VOCAB = 96


def _cfg(name, k=8):
    return make_family_cfg(name, n_topics=k, vocab_size=VOCAB)


@pytest.fixture(scope="module")
def corpus():
    return make_synthetic_corpus(n_topics=6, vocab=VOCAB, n_docs=48,
                                 doc_len=24, seed=7)


@pytest.mark.parametrize("layout", ["scan", "sorted"])
@pytest.mark.parametrize("name", ["lda", "pdp", "hdp"])
def test_trainer_all_families_both_layouts(name, layout, corpus):
    """Every family × layout: rounds run, perplexity improves from the
    first eval to the last, and the maintained shared statistics equal the
    statistics recomputed from the assignments bit-exactly."""
    tokens, mask, _ = corpus
    trainer = Trainer(_cfg(name), tokens, mask, config=TrainerConfig(
        layout=layout, n_clients=2, tau=1))
    res = trainer.run(4, eval_every=3, eval_docs=24)
    assert all(np.isfinite(res.perplexities))
    assert res.perplexities[-1] < res.perplexities[0]
    assert trainer.consistency_error() == 0.0
    assert res.violations[-1] == 0.0


@pytest.mark.parametrize("name", ["lda", "pdp", "hdp"])
def test_trainer_sorted_vs_scan_multiclient_tau2(name, corpus):
    """Distributed-round parity under the new API: multi-client runs with
    tau=2 local sweeps (bounded staleness) reach matching perplexity in
    either layout, and both conserve the sufficient statistics exactly."""
    tokens, mask, _ = corpus
    finals = {}
    for layout in ("scan", "sorted"):
        ppls = []
        for seed in (0, 1):
            trainer = Trainer(_cfg(name), tokens, mask,
                              config=TrainerConfig(layout=layout,
                                                   n_clients=2, tau=2),
                              key=jax.random.PRNGKey(seed))
            res = trainer.run(4, eval_every=10, eval_docs=24)
            assert trainer.consistency_error() == 0.0
            ppls.append(res.perplexities[-1])
        finals[layout] = sum(ppls) / len(ppls)
    rel = abs(finals["sorted"] - finals["scan"]) / finals["scan"]
    assert rel < 0.08, finals


def test_trainer_alias_cadence_and_projection_off(corpus):
    """alias_refresh_every > 1 reuses stale tables between rounds (the l/n
    rule of §3.3) and project_every=0 disables projection.  Rebuilds are
    observed through the Trainer's build counter (the table buffers
    themselves now ride through the compiled round's donated server
    state, so object identity no longer tracks reuse)."""
    tokens, mask, _ = corpus
    trainer = Trainer(_cfg("lda"), tokens, mask, config=TrainerConfig(
        n_clients=2, alias_refresh_every=3, project_every=0))
    trainer.step()
    assert trainer.alias_builds == 1        # round 0: built
    trainer.step()
    trainer.step()
    assert trainer.alias_builds == 1        # rounds 1, 2: reused
    trainer.step()
    assert trainer.alias_builds == 2        # round 3: rebuilt
    trainer._sync()
    assert trainer.consistency_error() == 0.0


def test_trainer_filter_with_error_feedback_converges(corpus):
    """A top-k communication filter with error-feedback residuals keeps the
    run finite and converging (mass withheld is carried, never dropped)."""
    tokens, mask, _ = corpus
    spec = ps.FilterSpec(kind="topk", k_rows=VOCAB // 8,
                         random_rows=VOCAB // 16)
    trainer = Trainer(_cfg("lda"), tokens, mask, config=TrainerConfig(
        n_clients=4, filter=spec))
    res = trainer.run(6, eval_every=5, eval_docs=24)
    assert all(np.isfinite(res.perplexities))
    assert res.perplexities[-1] < res.perplexities[0]


def test_trainer_failure_injection(corpus):
    """A client failing for a window of rounds (§5.4) must not derail the
    run: perplexity stays finite and the system keeps converging."""
    tokens, mask, _ = corpus
    trainer = Trainer(_cfg("hdp"), tokens, mask, config=TrainerConfig(
        n_clients=4, fault_plan=FaultPlan.crash(1, 1, 3)))
    res = trainer.run(5, eval_every=4, eval_docs=24)
    assert all(np.isfinite(res.perplexities))
    assert res.perplexities[-1] < res.perplexities[0]


def test_trainer_hdp_local_polytope_maintained(corpus):
    """The HDP table-count constraints (1 ≤ m_dk ≤ n_dk when n_dk > 0,
    m_dk = 0 otherwise) hold on every client after each round — the
    regression for the constraints the old adapter silently dropped."""
    tokens, mask, _ = corpus
    trainer = Trainer(_cfg("hdp"), tokens, mask,
                      config=TrainerConfig(n_clients=2, tau=2))
    for _ in range(3):
        trainer.step()
        for loc in trainer.locals_:
            assert float(trainer.family.count_local_violations(loc)) == 0.0


@pytest.mark.parametrize("name", ["lda", "pdp"])
def test_trainer_sweep_grid_counters(name, corpus):
    """The static counters of the fused sweep's grid equal what the hoisted
    layouts give by hand: (batch tiles + vocab tiles) work-list entries
    times K tiles per chunk, and the pairs in the vocab windows."""
    import dataclasses
    tokens, mask, _ = corpus
    cfg = dataclasses.replace(_cfg(name), tile_v=16, tile_k=4)
    trainer = Trainer(cfg, tokens, mask, config=TrainerConfig(
        layout="sorted", n_clients=2))
    n_ktiles = trainer.family.n_outcomes(cfg) // 4
    lays = [lay for ls in trainer.layouts for lay in ls]
    assert len(lays) == 2 * cfg.sorted_chunks
    steps = sum((np.asarray(lay.vstart).size + VOCAB // 16) * n_ktiles
                for lay in lays)
    live = sum(int(np.asarray(lay.vcount).sum()) for lay in lays)
    assert trainer.sweep_grid_steps == steps
    assert trainer.sweep_live_pairs == live
    assert 0 < live <= steps // n_ktiles
    scan = Trainer(cfg, tokens, mask, config=TrainerConfig(n_clients=2))
    assert scan.sweep_grid_steps == scan.sweep_live_pairs == 0


def test_trainer_rejects_bad_config(corpus):
    tokens, mask, _ = corpus
    with pytest.raises(ValueError, match="layout"):
        Trainer(_cfg("lda"), tokens, mask,
                config=TrainerConfig(layout="diagonal"))
    with pytest.raises(ValueError, match="sorted"):
        Trainer(_cfg("lda"), tokens, mask,
                config=TrainerConfig(layout="sorted", method="exact"))
    with pytest.raises(TypeError):
        Trainer(object(), tokens, mask)