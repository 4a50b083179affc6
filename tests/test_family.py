"""The ModelFamily protocol + registry (repro.core.family).

Guards the API-unification contract:

1. registry completeness — every family resolves by name and by config
   type, and declares the full protocol surface;
2. rule provenance — each registered family's rules are *identical* to the
   canonical ``projection.*_RULES`` / ``*_AGGREGATES`` (the regression for
   the old ``_HDPAdapter`` that hand-copied an ad-hoc subset), and the
   shared/local split drops nothing;
3. local projection — HDP's 1 ≤ m_dk ≤ n_dk table-count polytope is
   actually enforced on client state;
4. dense-proposal factorization — shapes and mass-consistency of the
   ``dense_probs`` / ``sparse_prior`` / alias-table hooks for every family.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import alias as alias_mod
from repro.core import family, hdp, projection
from tests.conftest import make_family_cfg, make_synthetic_corpus

CANONICAL = {
    "lda": (projection.LDA_RULES, projection.LDA_AGGREGATES),
    "pdp": (projection.PDP_RULES, projection.PDP_AGGREGATES),
    "hdp": (projection.HDP_RULES, projection.HDP_AGGREGATES),
}


def _cfg(name):
    return make_family_cfg(name, n_topics=8, vocab_size=64)


def test_registry_names_and_config_resolution():
    assert set(family.names()) >= {"lda", "pdp", "hdp"}
    for name in ("lda", "pdp", "hdp"):
        fam = family.get(name)
        assert fam.name == name
        assert family.family_of(_cfg(name)) is fam
    with pytest.raises(KeyError, match="unknown model family"):
        family.get("nope")
    with pytest.raises(TypeError):
        family.family_of(object())


@pytest.mark.parametrize("name", ["lda", "pdp", "hdp"])
def test_rules_match_projection_canon(name):
    """Regression for the old ad-hoc ``_HDPAdapter`` rules: the registry
    must source each family's rules/aggregates verbatim from
    ``repro.core.projection`` and the shared/local split must cover every
    rule — nothing silently dropped in distributed rounds."""
    fam = family.get(name)
    rules, aggregates = CANONICAL[name]
    assert fam.rules == rules
    assert fam.aggregates == aggregates
    assert set(fam.shared_rules) | set(fam.local_rules) == set(rules), \
        "a projection rule is neither shared nor local — it would be dropped"
    # shared/local operand sets really are disjoint responsibilities
    for r in fam.shared_rules:
        names = {r.a} | ({r.b} if r.b else set())
        assert names <= set(fam.shared_stats)
    for r in fam.local_rules:
        names = {r.a} | ({r.b} if r.b else set())
        assert names <= set(fam.local_stats)


def test_hdp_local_rules_cover_table_polytope():
    """HDP's 1 ≤ m_dk ≤ n_dk constraints (hdp.py docstring) live on local
    state and must be in local_rules."""
    fam = family.get("hdp")
    kinds = {(r.kind, r.a, r.b) for r in fam.local_rules}
    assert ("pos_link", "m_dk", "n_dk") in kinds
    assert ("le", "m_dk", "n_dk") in kinds


def test_hdp_local_project_enforces_polytope():
    fam = family.get("hdp")
    n_dk = jnp.asarray([[3.0, 0.0, 5.0], [1.0, 2.0, 0.0]])
    m_dk = jnp.asarray([[7.0, 2.0, 0.0], [-1.0, 1.0, 4.0]])  # all violated
    local = hdp.LocalState(z=jnp.zeros((2, 4), jnp.int32), n_dk=n_dk,
                           m_dk=m_dk)
    assert float(fam.count_local_violations(local)) > 0
    fixed = fam.local_project(local)
    assert float(fam.count_local_violations(fixed)) == 0.0
    np.testing.assert_array_equal(np.asarray(fixed.n_dk), np.asarray(n_dk))
    m = np.asarray(fixed.m_dk)
    n = np.asarray(n_dk)
    assert (m[n > 0] >= 1).all() and (m[n == 0] == 0).all() \
        and (m <= n).all()


def test_lda_pdp_local_project_identity():
    """Families without local rules pass client state through untouched."""
    tokens, mask, _ = make_synthetic_corpus(4, 64, 8, 12, seed=0)
    for name in ("lda", "pdp"):
        fam = family.get(name)
        assert fam.local_rules == ()
        local, _ = fam.init_state(_cfg(name), tokens, mask,
                                  jax.random.PRNGKey(0))
        out = fam.local_project(local)
        for a, b in zip(jax.tree.leaves(local), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", ["lda", "pdp", "hdp"])
def test_dense_proposal_factorization_shapes(name):
    """dense_probs is (V, E); alias mass matches its row sums;
    sparse_prior is (E,); doc_sparse_logp/accept_ratio behave generically."""
    fam = family.get(name)
    cfg = _cfg(name)
    tokens, mask, _ = make_synthetic_corpus(4, 64, 12, 10, seed=1)
    _, shared = fam.init_state(cfg, tokens, mask, jax.random.PRNGKey(0))
    e = fam.n_outcomes(cfg)
    assert e == (2 * cfg.n_topics if name == "pdp" else cfg.n_topics)

    dp = fam.dense_probs(cfg, shared)
    assert dp.shape == (cfg.vocab_size, e)
    tables, stale = fam.build_alias(cfg, shared)
    np.testing.assert_array_equal(np.asarray(stale), np.asarray(dp))
    np.testing.assert_allclose(np.asarray(tables.mass),
                               np.asarray(dp.sum(-1)), rtol=1e-5)
    assert tables.prob.shape == (cfg.vocab_size, e)

    prior = fam.sparse_prior(cfg, shared)
    assert prior.shape == (e,)
    assert bool(jnp.all(prior > 0))
    lm = fam.language_model(cfg, shared)
    assert lm.shape == (cfg.vocab_size, cfg.n_topics)

    doc = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (5, e)))
    out = jax.random.randint(jax.random.PRNGKey(2), (5,), 0, e)
    lp = fam.doc_sparse_logp(cfg, shared, doc, out)
    expect = jnp.log(doc[jnp.arange(5), out] + prior[out] + 1e-30)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(expect), rtol=1e-6)
    # accept_ratio is eq. 7 in log space
    a = fam.accept_ratio(jnp.asarray(1.0), jnp.asarray(0.5),
                         jnp.asarray(0.25), jnp.asarray(0.75))
    assert float(a) == pytest.approx(1.0 - 0.5 + 0.25 - 0.75)


@pytest.mark.parametrize("beta", [None, 0.0], ids=["beta", "zero_row"])
@pytest.mark.parametrize("name", ["lda", "hdp"])
def test_lm_build_alias_equals_reference(name, beta):
    """The LM-dense families' full build (the fused row-tile kernel)
    equals the plain reference, core.alias.build over dense_probs, bit for
    bit — prob, alias, mass — and its stale matrix is dense_probs.  Row 0
    holds no tokens; with β = 0 its dense row is all zero and takes the
    uniform fallback."""
    fam = family.get(name)
    cfg = _cfg(name)
    if beta is not None:
        cfg = dataclasses.replace(cfg, beta=beta)
    tokens, mask, _ = make_synthetic_corpus(8, 64, 24, 10, seed=3)
    tokens = jnp.where(tokens == 0, 1, tokens)
    _, shared = fam.init_state(cfg, tokens, mask, jax.random.PRNGKey(0))
    assert bool(jnp.all(shared.n_k > 0))
    dp = fam.dense_probs(cfg, shared)
    want = alias_mod.build(dp)
    tables, stale = fam.build_alias(cfg, shared)
    for got, ref in zip((*tables, stale), (*want, dp)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    if beta == 0.0:
        assert float(tables.mass[0]) == 0.0
        np.testing.assert_array_equal(np.asarray(tables.alias[0]),
                                      np.arange(cfg.n_topics))
        np.testing.assert_array_equal(np.asarray(tables.prob[0]),
                                      np.ones(cfg.n_topics))


@pytest.mark.parametrize("name", ["lda", "pdp", "hdp"])
def test_family_sweep_and_apply_delta(name):
    """Protocol sweep returns the declared delta dict; apply_delta keeps
    the C2 aggregates consistent with their source matrices."""
    fam = family.get(name)
    cfg = _cfg(name)
    tokens, mask, _ = make_synthetic_corpus(4, 64, 12, 10, seed=2)
    local, shared = fam.init_state(cfg, tokens, mask, jax.random.PRNGKey(0))
    tables, stale = fam.build_alias(cfg, shared)
    local2, deltas = fam.sweep(cfg, local, shared, tables, stale, tokens,
                               mask, jax.random.PRNGKey(1))
    assert set(deltas) == set(fam.delta_names)
    shared2 = fam.apply_delta(shared, deltas)
    stats = fam.stats_dict(shared2)
    for agg in fam.aggregates:
        if agg.src in stats and agg.out in stats:
            np.testing.assert_allclose(
                np.asarray(stats[agg.out]),
                np.asarray(stats[agg.src].sum(agg.axis)), atol=1e-3)
    # count-conserved stats stay consistent through sweep + apply
    counts = fam.count_stats(cfg, tokens, mask, local2)
    for n in fam.conserved_stats:
        np.testing.assert_array_equal(np.asarray(counts[n]),
                                      np.asarray(stats[n]))
