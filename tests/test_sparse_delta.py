"""Sparse delta exchange — core pytree boundary (DESIGN.md §12).

The contract under test: ``to_sparse_delta`` keeps every row non-zero in
*any* statistic, ``from_sparse_delta`` reconstructs the dense pytree
bit-for-bit, and ``ParameterServer.push_sparse`` therefore lands on the
exact bytes of the dense ``push`` — sparsity is an encoding, never an
approximation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import alias as alias_mod
from repro.core import family as family_mod
from repro.core import ps
from repro.core import server as server_mod
from repro.data.synthetic import CorpusConfig, make_topic_corpus
from repro.engine import round as round_mod
from tests.conftest import make_family_cfg, make_synthetic_corpus

VOCAB = 64


@pytest.fixture(scope="module")
def corpus():
    return make_synthetic_corpus(n_topics=4, vocab=VOCAB, n_docs=16,
                                 doc_len=12, seed=3)


# Above this vocabulary the full alias build takes minutes on CPU, so the
# sweep draws from uniform alias tables (a valid table per row) over the
# real dense term: that moves which rows a delta touches, not how a
# delta is encoded or pushed.
_FULL_BUILD_MAX_V = 8192


def _sweep_deltas(name, corpus, key=0, *, vocab=VOCAB, n_topics=4):
    """One real sweep's dense deltas (the thing a client would push)."""
    tokens, mask, _ = corpus
    fam = family_mod.get(name)
    cfg = make_family_cfg(name, n_topics=n_topics, vocab_size=vocab)
    local, shared = fam.init_state(cfg, tokens, mask, jax.random.PRNGKey(0))
    if vocab <= _FULL_BUILD_MAX_V:
        tables, stale = fam.build_alias(cfg, shared)
    else:
        tables = alias_mod.AliasTable(
            prob=jnp.ones((vocab, n_topics), jnp.float32),
            alias=jnp.broadcast_to(jnp.arange(n_topics, dtype=jnp.int32),
                                   (vocab, n_topics)),
            mass=jnp.full((vocab,), float(n_topics), jnp.float32))
        stale = fam.dense_probs(cfg, shared)
    _, deltas = fam.sweep(cfg, local, shared, tables, stale, tokens, mask,
                          jax.random.PRNGKey(key), method="mhw",
                          layout="scan")
    return fam, cfg, shared, deltas


# ---------------------------------------------------------------------------
# to/from roundtrip
# ---------------------------------------------------------------------------

def test_roundtrip_bitexact_multi_stat():
    rng = np.random.default_rng(0)
    a = np.zeros((10, 4), np.float32)
    b = np.zeros((10, 3), np.float32)
    a[[1, 7]] = rng.normal(size=(2, 4)).astype(np.float32)
    b[[2, 7]] = rng.normal(size=(2, 3)).astype(np.float32)
    sp = ps.to_sparse_delta({"a": a, "b": b})
    # Union of non-zero rows across stats, ascending and unique.
    np.testing.assert_array_equal(np.asarray(sp.rows), [1, 2, 7])
    out = ps.from_sparse_delta(sp, 10)
    np.testing.assert_array_equal(np.asarray(out["a"]), a)
    np.testing.assert_array_equal(np.asarray(out["b"]), b)


def test_roundtrip_zero_delta_is_empty():
    sp = ps.to_sparse_delta({"a": np.zeros((6, 2), np.float32)})
    assert sp.rows.size == 0
    out = ps.from_sparse_delta(sp, 6)
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.zeros((6, 2), np.float32))


def test_roundtrip_negative_and_tiny_values_survive():
    a = np.zeros((8, 2), np.float32)
    a[3] = [-1.0, np.float32(1e-30)]   # subnormal-ish values stay exact
    a[5] = [0.0, -0.0]                 # -0.0 row: non-zero by bit, but
    #                                    np.any(v != 0) treats -0.0 == 0 —
    #                                    dropping it is still bit-exact
    #                                    for the *sum* (0 + -0 == 0).
    sp = ps.to_sparse_delta({"a": a})
    np.testing.assert_array_equal(np.asarray(sp.rows), [3])
    out = np.asarray(ps.from_sparse_delta(sp, 8)["a"])
    np.testing.assert_array_equal(out[3], a[3])


@pytest.mark.parametrize("name", ["lda", "pdp"])
def test_roundtrip_real_sweep_deltas(name, corpus):
    _, _, _, deltas = _sweep_deltas(name, corpus)
    dense = {n: np.asarray(v) for n, v in deltas.items()
             if np.asarray(v).shape[:1] == (VOCAB,)}
    sp = ps.to_sparse_delta(dense)
    assert 0 < sp.rows.size < VOCAB  # genuinely sparse on this corpus
    out = ps.from_sparse_delta(sp, VOCAB)
    for n, v in dense.items():
        np.testing.assert_array_equal(np.asarray(out[n]), v, err_msg=n)


# ---------------------------------------------------------------------------
# push_sparse == push on the core server
# ---------------------------------------------------------------------------

# (family, server shards, V, K); the last case is the largest point of
# the (V, K) scale ladder on its zipf corpus.
PUSH_CASES = [pytest.param(name, n_shards, VOCAB, 4,
                           id=f"{n_shards}-{name}")
              for n_shards in (1, 3) for name in ("lda", "pdp")] + [
    pytest.param("lda", 3, 65536, 256, id="3-lda-v65536-k256")]


@pytest.mark.parametrize("name,n_shards,vocab,n_topics", PUSH_CASES)
def test_push_sparse_bitexact_with_push(name, n_shards, vocab, n_topics,
                                        corpus):
    if vocab != VOCAB:
        corpus = make_topic_corpus(CorpusConfig(
            n_topics=8, vocab_size=vocab, n_docs=48, doc_len=16, seed=5))
    fam, cfg, shared, deltas = _sweep_deltas(name, corpus, vocab=vocab,
                                             n_topics=n_topics)
    # Every pushed delta is a (V, ...) row stat: aggregates (n_k, m_k, …)
    # are re-derived by apply_delta (the C2 rule), never shipped.
    assert all(np.asarray(v).shape[:1] == (vocab,) for v in deltas.values())

    sp = ps.to_sparse_delta(deltas)
    assert sp.rows.size < vocab  # the sparse frame ships fewer rows

    srv = server_mod.make_server(fam, vocab, n_shards=n_shards)
    s_dense = srv.push(srv.init_state(shared, n_clients=1), deltas)
    s_sparse = srv.push_sparse(srv.init_state(shared, n_clients=1), sp)

    a = fam.stats_dict(srv.snapshot(s_dense))
    b = fam.stats_dict(srv.snapshot(s_sparse))
    for n in a:
        np.testing.assert_array_equal(np.asarray(a[n]), np.asarray(b[n]),
                                      err_msg=n)


def test_filter_push_sparse_matches_filter_push(corpus):
    """The filtered wire path: filter_push then sparsify == the sparse
    helper, and densifying recovers the filtered send exactly."""
    fam, cfg, shared, deltas = _sweep_deltas("lda", corpus)
    spec = ps.FilterSpec()
    key = jax.random.PRNGKey(7)
    sent, residual = round_mod.filter_push(fam, deltas, spec, key)
    sp, residual2 = round_mod.filter_push_sparse(fam, deltas, spec, key)
    if residual is None:
        assert residual2 is None
    else:
        jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                       np.asarray(y)),
            residual, residual2)
    dense = ps.from_sparse_delta(sp, VOCAB)
    for n, v in sent.items():
        if np.asarray(v).shape[:1] == (VOCAB,):
            np.testing.assert_array_equal(np.asarray(dense[n]),
                                          np.asarray(v), err_msg=n)
