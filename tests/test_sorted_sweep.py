"""Tests for the token-sorted, tile-skipping MHW pipeline.

Three layers of guarantees:

1. kernel exactness — the tile-skipping kernels must match their pure-jnp
   oracles bit-for-bit given the same uniforms, including streams whose
   vocab tiles are mostly empty (the skip path);
2. sweep consistency — the sorted sweep's sufficient statistics stay
   consistent with its assignments (a permutation-consistent no-op when
   nothing moves);
3. statistical equivalence — sorted and scan layouts reach the same
   perplexity after 5 sweeps within tolerance (the acceptance bar of the
   sorted relaxation: speed must not trade correctness).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import family, lda, mhw, pdp, stirling
from repro.data import segment
from repro.kernels import alias_build, alias_sample, mhw_fused, ops, ref
from tests.conftest import make_family_cfg, make_synthetic_corpus


def _sorted_rows(key, b, lo, hi, v, n_pad=0):
    """Sorted row stream concentrated in [lo, hi) with trailing sentinels."""
    rows = jax.random.randint(key, (b - n_pad,), lo, hi, jnp.int32)
    rows = jnp.sort(rows)
    return jnp.concatenate([rows, jnp.full((n_pad,), v, jnp.int32)])


def _windows(rows, v, tile_v, tile_b):
    rs = np.asarray(rows).reshape(-1, tile_b)
    has = rs[:, 0] < v
    last = np.max(np.where(rs < v, rs, -1), axis=1)
    vstart = np.where(has, rs[:, 0] // tile_v, 0).astype(np.int32)
    vcount = np.where(has, last // tile_v - vstart + 1, 0).astype(np.int32)
    return jnp.asarray(vstart), jnp.asarray(vcount)


def _case(*dims, tile_k=None, fold_in=None, tables=None):
    """A fused-kernel case: its dims, then ``tile_k``, then ``fold_in``
    and the kind of statistics (``tables``, "gamma" unless set) where the
    test takes them.  Named by its dims, plus ``tk<n>``, ``fold_in`` and
    ``tables`` when set, so the untiled training cases keep their ids."""
    flags = () if fold_in is None else (fold_in, tables or "gamma")
    tags = [str(d) for d in dims] + ([f"tk{tile_k}"] if tile_k else []) \
        + (["fold_in"] if fold_in else []) + ([tables] if tables else [])
    return pytest.param(*dims, tile_k, *flags, id="-".join(tags))


@pytest.mark.parametrize("v,k,b,tile_v,tile_b,lo,hi,n_pad", [
    (64, 32, 512, 16, 128, 0, 64, 0),      # dense occupancy
    (128, 16, 256, 16, 64, 32, 48, 0),     # one narrow band: most tiles empty
    (64, 8, 256, 8, 64, 0, 9, 37),         # skewed + trailing padding
])
def test_alias_sample_sorted_exact(v, k, b, tile_v, tile_b, lo, hi, n_pad):
    """Tile-skipping draws equal the oracle, draws in skipped tiles and all."""
    key = jax.random.PRNGKey(v + b)
    p = jax.random.gamma(key, 0.3, (v, k)) + 1e-4
    prob, al, _ = alias_build.alias_build(p, tile_r=8)
    rows = _sorted_rows(jax.random.fold_in(key, 1), b, lo, hi, v, n_pad)
    slot = jax.random.randint(jax.random.fold_in(key, 2), (b,), 0, k, jnp.int32)
    coin = jax.random.uniform(jax.random.fold_in(key, 3), (b,))
    vstart, vcount = _windows(rows, v, tile_v, tile_b)
    out_k = alias_sample.alias_sample_sorted(prob, al, rows, slot, coin,
                                             vstart, vcount, tile_v=tile_v,
                                             tile_b=tile_b)
    out_r = ref.alias_sample_sorted_ref(prob, al, rows, slot, coin)
    assert bool(jnp.all(out_k == out_r))


def _work_rows(case, v, b):
    key = jax.random.PRNGKey(b)
    if case == "padding-tail":
        return _sorted_rows(key, b, 0, v, v, n_pad=200)
    if case == "one-vocab-tile":
        return _sorted_rows(key, b, 16, 24, v)
    if case == "spans-every-tile":
        return jnp.sort(jnp.arange(b, dtype=jnp.int32) % v)
    return jnp.full((b,), v, jnp.int32)          # all padding


@pytest.mark.parametrize("case,b,tile_b", [
    ("padding-tail", 512, 64),
    ("one-vocab-tile", 256, 64),
    ("spans-every-tile", 128, 128),
    ("all-padding", 256, 64),
])
def test_work_list(case, b, tile_b):
    """The fused kernels' grid lists every (batch tile, vocab tile) pair of
    each window once, batch-tile major; every batch tile has an entry that
    initialises its output; and no non-live entry moves a table block, so
    the pipeline copies nothing for it."""
    v, tile_v, nk = 64, 8, 4
    nb, nv = b // tile_b, v // tile_v
    vstart, vcount = _windows(_work_rows(case, v, b), v, tile_v, tile_b)
    pb, pt, live, first = (np.asarray(a) for a in
                           mhw_fused.work_list(vstart, vcount, nv))
    vs, vc = np.asarray(vstart), np.asarray(vcount)
    n = mhw_fused.n_pairs(nb, nv)
    assert pb.shape == pt.shape == live.shape == first.shape == (n,)

    want = [(bi, t) for bi in range(nb) for t in range(vs[bi], vs[bi] + vc[bi])]
    got = [(int(b_), int(t)) for b_, t, lv in zip(pb, pt, live) if lv]
    assert got == want
    assert len(got) <= nb + nv - 1 <= n
    # one first entry per batch tile, ahead of its other entries
    assert sorted(pb[first == 1].tolist()) == list(range(nb))
    for bi in range(nb):
        assert first[np.flatnonzero(pb == bi)[0]] == 1
    assert np.all(np.diff(pb) >= 0)

    # each step's table block, through the kernels' own index map
    vmapk = mhw_fused._index_maps(nk)[3]
    prev = None
    for p in range(n):
        for ki in range(nk):
            blk = tuple(int(x) for x in vmapk(p, ki, pb, pt, live, first))
            if not live[p] and prev is not None:
                assert blk == prev, (p, ki)
            prev = blk


def _stage_case(kind, key, shape):
    """(blocks, E) for a staging case: table blocks as a live step holds
    them, and the outcome count that bounds integer (alias) entries."""
    u = jax.random.uniform(key, shape)
    sign = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5,
                                          shape), -1.0, 1.0)
    zero = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.1, shape)
    if kind == "f32-signed-wide":      # both signs over 2^-100 … 2^100
        mant = 1.0 + jax.random.uniform(jax.random.fold_in(key, 3), shape)
        x = sign * mant * jnp.exp2(jnp.round(u * 200.0 - 100.0))
        return [jnp.where(zero, 0.0, x)], 1024
    if kind == "counts-2^24":          # integer-valued counts up to 2^24 − 1
        x = jnp.floor(u * 2.0 ** 24)
        return [x.at[0, :2].set(jnp.array([2.0 ** 24 - 1, 0.0]))], 1024
    if kind == "stale-1e-7":           # the stale table's magnitudes
        return [u * 2e-7 + 1e-9], 1024
    if kind.startswith("alias-E"):     # indices up to E − 1
        e = int(kind[len("alias-E"):])
        x = jnp.floor(u * e).astype(jnp.int32)
        return [x.at[0, 0].set(e - 1)], e
    if kind == "pdp-log-factors":      # negative finite log factors
        return [-jnp.exp(u * 30.0 - 20.0)], 2048
    assert kind == "live-step"         # n_wk, stale, prob, alias together
    ks = jax.random.split(key, 4)
    return [jnp.floor(jax.random.gamma(ks[0], 0.3, shape) * 1e4),
            jax.random.uniform(ks[1], shape) * 1e-6,
            jax.random.uniform(ks[2], shape),
            jax.random.randint(ks[3], shape, 0, 1024, jnp.int32)], 1024


@pytest.mark.parametrize("kind", [
    "f32-signed-wide", "counts-2^24", "stale-1e-7", "alias-E1024",
    "alias-E2048", "pdp-log-factors", "live-step"])
def test_stage_rows_exact(kind):
    """A live step's single-pass bf16 staging returns ``table[rows]`` bit
    for bit: each output has one non-zero product and each bf16 part is
    exact.  Rows outside the vocab tile stage zeros."""
    tile_v, c, b = 64, 16, 48
    key = jax.random.PRNGKey(len(kind))
    blocks, e = _stage_case(kind, key, (tile_v, c))
    rows = jax.random.randint(jax.random.fold_in(key, 7), (b,), 0, tile_v,
                              jnp.int32).at[:3].set(jnp.array([0, 63, 64]))

    def kernel(rows_ref, *refs):
        _, onehot = mhw_fused._tile_onehot(rows_ref[0], 0, tile_v)
        staged = mhw_fused._stage_rows(
            onehot, [r[...] for r in refs[:len(blocks)]], e)
        for o_ref, got in zip(refs[len(blocks):], staged):
            o_ref[...] = got

    outs = pl.pallas_call(
        kernel, interpret=True,
        out_shape=[jax.ShapeDtypeStruct((b, c), x.dtype) for x in blocks],
    )(rows.reshape(1, b), *blocks)
    in_tile = np.asarray(rows < tile_v)[:, None]
    for blk, got in zip(blocks, outs):
        want = np.where(in_tile, np.asarray(blk)[np.minimum(rows, 63)], 0)
        assert got.dtype == blk.dtype
        np.testing.assert_array_equal(np.asarray(got).view(np.int32),
                                      want.astype(blk.dtype).view(np.int32))


@pytest.mark.parametrize("prior_kind", ["lda", "hdp"])
@pytest.mark.parametrize("v,k,b,tile_v,tile_b,lo,hi,n_pad,steps,tile_k,"
                         "fold_in,tables", [
    _case(60, 16, 384, 12, 128, 0, 60, 0, 2, fold_in=False),
    # most vocab tiles empty
    _case(120, 32, 256, 12, 64, 24, 60, 0, 3, fold_in=False),
    _case(60, 16, 256, 12, 64, 0, 7, 61, 2, fold_in=False),  # skew + padding
    # K-tiled over mostly-empty vocab tiles with all-padding batch tiles
    _case(240, 32, 512, 12, 64, 24, 60, 200, 2, tile_k=8, fold_in=False),
    # serving: folded-in documents, own token removed from ndk only
    _case(120, 32, 256, 12, 64, 24, 60, 70, 3, tile_k=16, fold_in=True),
    # integer-valued counts near 2^24 and a stale table near 1e-7: the
    # widest values the staging's bf16 parts must carry exactly
    _case(120, 32, 256, 12, 64, 24, 60, 0, 2, tile_k=16, fold_in=False,
          tables="counts-2^24"),
])
def test_mhw_fused_kernel_vs_oracle(v, k, b, tile_v, tile_b, lo, hi, n_pad,
                                    steps, tile_k, fold_in, tables,
                                    prior_kind):
    """The fused draw+accept kernel is bit-identical to mhw.sorted_chain —
    with the uniform LDA prior α·1 and a non-uniform HDP prior b1·θ0."""
    key = jax.random.PRNGKey(v * k + b)
    alpha, beta = 0.1, 0.01
    beta_bar = beta * v
    n_wk = jax.random.gamma(key, 1.0, (v, k)) * 5
    if tables == "counts-2^24":
        n_wk = jnp.floor(2.0 ** 24 - 1 - n_wk * 1e3)
    n_k = n_wk.sum(0)
    lm = (n_wk + beta) / (n_k[None, :] + beta_bar)
    if prior_kind == "lda":
        prior = jnp.full((k,), alpha, jnp.float32)
    else:  # HDP: dense term b1·θ0_t
        theta0 = jax.random.dirichlet(jax.random.fold_in(key, 9),
                                      jnp.ones((k,)))
        prior = 2.0 * theta0
    stale = prior[None, :] * lm
    if tables == "counts-2^24":
        stale = stale * (1e-7 / jnp.mean(stale))
    tabs = ops.build_tables(stale, tile_r=segment.pick_tile(v, 8))

    rows = _sorted_rows(jax.random.fold_in(key, 1), b, lo, hi, v, n_pad)
    z0 = jax.random.randint(jax.random.fold_in(key, 2), (b,), 0, k, jnp.int32)
    # raw doc rows: ≥1 at the token's own topic so the in-kernel ^{-di}
    # removal keeps the sparse weights nonnegative, as in a real sweep
    ndk = jax.random.gamma(jax.random.fold_in(key, 3), 0.5, (b, k))
    ndk = ndk.at[jnp.arange(b), z0].add(1.0)
    ks = jax.random.split(jax.random.fold_in(key, 4), 5)
    slot = jax.random.randint(ks[0], (steps, b), 0, k, jnp.int32)
    uni = [jax.random.uniform(ks[i], (steps, b)) for i in range(1, 5)]
    vstart, vcount = _windows(rows, v, tile_v, tile_b)

    out_k = mhw_fused.mhw_sweep_fused(
        tabs.prob, tabs.alias, tabs.mass, stale, n_wk, n_k, prior, rows, z0,
        ndk, slot, *uni, vstart, vcount, tile_v=tile_v, tile_b=tile_b,
        tile_k=tile_k, n_steps=steps, beta=beta, beta_bar=beta_bar,
        fold_in=fold_in)
    out_r = ref.mhw_sweep_sorted_ref(
        tabs.prob, tabs.alias, tabs.mass, stale, n_wk, n_k, prior, rows, z0,
        ndk, slot, *uni, beta=beta, beta_bar=beta_bar, fold_in=fold_in)
    assert bool(jnp.all(out_k == out_r)), \
        f"{int(jnp.sum(out_k != out_r))} of {b} draws differ"
    # padding sentinels keep their init state
    if n_pad:
        assert bool(jnp.all(out_k[-n_pad:] == z0[-n_pad:]))


@pytest.mark.parametrize("v,k,b,tile_v,tile_b,lo,hi,n_pad,steps,tile_k", [
    _case(64, 8, 384, 16, 128, 0, 64, 0, 2),
    _case(128, 8, 256, 16, 64, 32, 48, 0, 3),    # most vocab tiles empty
    _case(64, 8, 256, 16, 64, 0, 9, 47, 2),      # skew + padding
    # e-tiled over mostly-empty vocab tiles with all-padding batch tiles
    _case(256, 8, 512, 16, 64, 40, 72, 200, 2, tile_k=4),
])
def test_pdp_fused_kernel_vs_oracle(v, k, b, tile_v, tile_b, lo, hi, n_pad,
                                    steps, tile_k):
    """The fused PDP kernel (2K joint outcomes, in-VMEM Stirling factors)
    is bit-identical to pdp.sorted_chain_pdp."""
    key = jax.random.PRNGKey(v * k + b + 1)
    cfg = pdp.PDPConfig(n_topics=k, vocab_size=v, mh_steps=steps,
                        stirling_n_max=128, concentration=5.0)
    m_wk = jnp.floor(jax.random.gamma(key, 1.0, (v, k)) * 3)
    s_wk = jnp.minimum(jnp.ceil(m_wk * 0.5), m_wk)
    shared = pdp.SharedStats(m_wk=m_wk, s_wk=s_wk, m_k=m_wk.sum(0),
                             s_k=s_wk.sum(0))
    tabs, stale = pdp.build_alias(cfg, shared)
    stirl = stirling.as_jax(cfg.stirling_n_max, cfg.discount)
    prior = jnp.full((2 * k,), cfg.alpha, jnp.float32)

    rows = _sorted_rows(jax.random.fold_in(key, 1), b, lo, hi, v, n_pad)
    e0 = jax.random.randint(jax.random.fold_in(key, 2), (b,), 0, 2 * k,
                            jnp.int32)
    ndk = jnp.floor(jax.random.gamma(jax.random.fold_in(key, 3), 0.5,
                                     (b, k)) * 2)
    ndk = ndk.at[jnp.arange(b), e0 % k].add(1.0)
    ks = jax.random.split(jax.random.fold_in(key, 4), 5)
    slot = jax.random.randint(ks[0], (steps, b), 0, 2 * k, jnp.int32)
    uni = [jax.random.uniform(ks[i], (steps, b)) for i in range(1, 5)]
    vstart, vcount = _windows(rows, v, tile_v, tile_b)

    out_k = mhw_fused.pdp_sweep_fused(
        tabs.prob, tabs.alias, tabs.mass, stale, m_wk, s_wk, shared.m_k,
        shared.s_k, stirl, prior, rows, e0, ndk, slot, *uni, vstart, vcount,
        tile_v=tile_v, tile_b=tile_b, tile_k=tile_k, n_steps=steps,
        b_conc=cfg.concentration, a_disc=cfg.discount, gamma=cfg.gamma,
        gamma_bar=cfg.gamma * v)
    out_r = ref.pdp_sweep_sorted_ref(
        tabs.prob, tabs.alias, tabs.mass, stale, m_wk, s_wk, shared.m_k,
        shared.s_k, stirl, prior, rows, e0, ndk, slot, *uni,
        b=cfg.concentration, a=cfg.discount, gamma=cfg.gamma,
        gamma_bar=cfg.gamma * v)
    assert bool(jnp.all(out_k == out_r)), \
        f"{int(jnp.sum(out_k != out_r))} of {b} draws differ"
    if n_pad:
        assert bool(jnp.all(out_k[-n_pad:] == e0[-n_pad:]))
    # joint outcomes stay in range
    assert bool(jnp.all((out_k >= 0) & (out_k < 2 * k)))


def test_ops_sample_rows_sorted_statistics():
    """The tile-skipping ops wrapper draws from the right distributions
    (end-to-end through key-splitting and the segment windows)."""
    v, k = 32, 16
    key = jax.random.PRNGKey(0)
    p = jax.random.gamma(key, 0.5, (v, k)) + 1e-3
    tables = ops.build_tables(p, tile_r=8)
    # sorted stream: 4000 draws per row, plus a trailing all-padding tile
    rows = jnp.repeat(jnp.arange(v), 4000)
    rows = jnp.concatenate([rows, jnp.full((512,), v, jnp.int32)])
    vstart, vcount = _windows(rows, v, 8, 512)
    s = np.asarray(ops.sample_rows_sorted(tables, rows, vstart, vcount,
                                          jax.random.PRNGKey(1), tile_v=8,
                                          tile_b=512))
    assert (s[-512:] == 0).all(), "padding sentinels draw 0"
    s = s[:-512].reshape(v, -1)
    for r in range(0, v, 7):
        emp = np.bincount(s[r], minlength=k) / s.shape[1]
        refd = np.asarray(p[r] / p[r].sum())
        assert 0.5 * np.abs(emp - refd).sum() < 0.05


def test_mhw_fused_moves_and_respects_empty_tiles():
    """Sanity: the chain actually moves states, and a stream confined to one
    vocab tile leaves every other tile's worth of draws untouched."""
    v, k, b = 64, 16, 256
    key = jax.random.PRNGKey(0)
    n_wk = jax.random.gamma(key, 1.0, (v, k)) * 5
    n_k = n_wk.sum(0)
    stale = 0.1 * (n_wk + 0.01) / (n_k[None, :] + 0.64)
    tabs = ops.build_tables(stale, tile_r=8)
    rows = _sorted_rows(jax.random.fold_in(key, 1), b, 8, 16, v)  # tile 1 only
    z0 = jax.random.randint(jax.random.fold_in(key, 2), (b,), 0, k, jnp.int32)
    ndk = jax.random.gamma(jax.random.fold_in(key, 3), 0.5, (b, k))
    ndk = ndk.at[jnp.arange(b), z0].add(1.0)
    vstart, vcount = _windows(rows, v, 8, 64)
    np.testing.assert_array_equal(np.asarray(vcount), np.ones(4))
    np.testing.assert_array_equal(np.asarray(vstart), np.ones(4))
    prior = jnp.full((k,), 0.1, jnp.float32)
    out = ops.mhw_sweep_sorted(tabs, stale, n_wk, n_k, prior, rows, z0, ndk,
                               vstart, vcount, jax.random.fold_in(key, 4),
                               mh_steps=2, beta=0.01,
                               beta_bar=0.64, tile_v=8, tile_b=64)
    assert float(jnp.mean((out != z0).astype(jnp.float32))) > 0.2


@pytest.fixture(scope="module")
def tiny_corpus():
    return make_synthetic_corpus(n_topics=6, vocab=96, n_docs=48, doc_len=32,
                                 seed=3)


def _run_sweeps(cfg, tokens, mask, layout, seed, n_sweeps=5, lays=None):
    local, shared = lda.init_state(cfg, tokens, mask, jax.random.PRNGKey(0))
    for i in range(n_sweeps):
        tables, stale = lda.build_alias(cfg, shared)
        local, dwk, dk = lda.sweep(
            cfg, local, shared, tables, stale, tokens, mask,
            jax.random.fold_in(jax.random.PRNGKey(seed), i),
            method="mhw", layout=layout, sorted_layouts=lays)
        shared = lda.apply_delta(shared, dwk, dk)
    return local, shared


def _sweep_perplexity(cfg, tokens, mask, layout, seed, n_sweeps=5):
    """Held-out perplexity after ``n_sweeps`` single-client mhw sweeps with
    ``layout``, for any registered family; deterministic given (corpus,
    cfg, seed)."""
    fam = family.family_of(cfg)
    lays = fam.build_sorted_layouts(cfg, tokens, mask) \
        if layout == "sorted" else None
    local, shared = fam.init_state(cfg, tokens, mask, jax.random.PRNGKey(0))
    for i in range(n_sweeps):
        tables, stale = fam.build_alias(cfg, shared)
        local, deltas = fam.sweep(
            cfg, local, shared, tables, stale, tokens, mask,
            jax.random.fold_in(jax.random.PRNGKey(seed), i),
            method="mhw", layout=layout, sorted_layouts=lays)
        shared = fam.apply_delta(shared, deltas)
    return float(fam.perplexity(cfg, shared, tokens, mask,
                                jax.random.PRNGKey(9)))


def test_sorted_sweep_statistics_consistent(tiny_corpus):
    """After a sorted sweep, n_dk / the deltas agree with the assignments —
    the sort → sample → unsort round trip is permutation-consistent."""
    tokens, mask, _ = tiny_corpus
    cfg = lda.LDAConfig(n_topics=24, vocab_size=96, mh_steps=2)
    local, shared = lda.init_state(cfg, tokens, mask, jax.random.PRNGKey(0))
    tables, stale = lda.build_alias(cfg, shared)
    local2, dwk, dk = lda.sweep(cfg, local, shared, tables, stale, tokens,
                                mask, jax.random.PRNGKey(1), method="mhw",
                                layout="sorted")
    # counts derived from z must equal the incrementally-updated counts
    np.testing.assert_allclose(np.asarray(lda.count_dk(cfg, local2.z, mask)),
                               np.asarray(local2.n_dk), atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(lda.count_wk(cfg, tokens, local2.z, mask)),
        np.asarray(shared.n_wk + dwk), atol=1e-4)
    # masked positions never move
    m = np.asarray(mask)
    np.testing.assert_array_equal(np.asarray(local2.z)[~m],
                                  np.asarray(local.z)[~m])
    # delta mass is conserved (a sweep moves topics, not tokens)
    assert abs(float(dk.sum())) < 1e-3


def test_sorted_matches_scan_perplexity():
    """Acceptance bar: sorted and scan layouts agree on held-out perplexity
    after 5 sweeps on the synthetic power-law corpus, within 2%.

    Averaged over 3 paired sweep-RNG seeds: a single 5-sweep run on this
    corpus carries ~±1.5% MC noise (seed-to-seed spread of the *scan* path
    alone), which would swamp the ~1% systematic effect of the sorted
    relaxation.  Deterministic given the fixed keys.
    """
    from repro.data.synthetic import CorpusConfig, make_topic_corpus
    ccfg = CorpusConfig(n_topics=8, vocab_size=300, n_docs=64, doc_len=48,
                        seed=5)
    tokens, mask, _ = make_topic_corpus(ccfg)
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    cfg = lda.LDAConfig(n_topics=64, vocab_size=300, mh_steps=2)
    means = {
        layout: sum(_sweep_perplexity(cfg, tokens, mask, layout, seed)
                    for seed in (2, 3, 4)) / 3
        for layout in ("scan", "sorted")
    }
    rel = abs(means["sorted"] - means["scan"]) / means["scan"]
    assert rel < 0.02, means


def test_sorted_sweep_with_hoisted_layouts_matches_inline(tiny_corpus):
    """Prebuilt chunk layouts (the production path) give bit-identical
    sweeps to the build-inside-sweep convenience path."""
    tokens, mask, _ = tiny_corpus
    cfg = lda.LDAConfig(n_topics=16, vocab_size=96, mh_steps=2)
    lays = lda.build_sorted_layouts(cfg, tokens, mask)
    l_inline, _ = _run_sweeps(cfg, tokens, mask, "sorted", seed=4, n_sweeps=2)
    l_hoist, _ = _run_sweeps(cfg, tokens, mask, "sorted", seed=4, n_sweeps=2,
                             lays=lays)
    np.testing.assert_array_equal(np.asarray(l_inline.z),
                                  np.asarray(l_hoist.z))


def test_sorted_requires_mhw():
    tokens = jnp.zeros((4, 8), jnp.int32)
    mask = jnp.ones((4, 8), bool)
    cfg = lda.LDAConfig(n_topics=4, vocab_size=16)
    local, shared = lda.init_state(cfg, tokens, mask, jax.random.PRNGKey(0))
    tables, stale = lda.build_alias(cfg, shared)
    with pytest.raises(ValueError, match="sorted"):
        lda.sweep(cfg, local, shared, tables, stale, tokens, mask,
                  jax.random.PRNGKey(1), method="exact", layout="sorted")


# ---------------------------------------------------------------------------
# Sorted layout for every family through the ModelFamily protocol
# ---------------------------------------------------------------------------

def _family_cfg(name):
    return make_family_cfg(name, n_topics=12, vocab_size=96)


@pytest.mark.parametrize("name", ["lda", "pdp", "hdp"])
def test_family_sorted_sweep_statistics_consistent(name, tiny_corpus):
    """After a sorted sweep of any family, the maintained sufficient
    statistics agree bit-exactly with the statistics recomputed from the
    final assignments — the sort → sample → unsort round trip is
    permutation-consistent, as in the scan layout."""
    tokens, mask, _ = tiny_corpus
    fam = family.get(name)
    cfg = _family_cfg(name)
    local, shared = fam.init_state(cfg, tokens, mask, jax.random.PRNGKey(0))
    tables, stale = fam.build_alias(cfg, shared)
    local2, deltas = fam.sweep(cfg, local, shared, tables, stale, tokens,
                               mask, jax.random.PRNGKey(1), method="mhw",
                               layout="sorted")
    counts = fam.count_stats(cfg, tokens, mask, local2)
    stats = fam.stats_dict(shared)
    for n in fam.conserved_stats:
        np.testing.assert_array_equal(np.asarray(counts[n]),
                                      np.asarray(stats[n] + deltas[n]))
    # n_dk consistent with assignments
    n_dk = jnp.einsum(
        "dl,dlk->dk", mask.astype(jnp.float32),
        jax.nn.one_hot(local2.z, cfg.n_topics, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(n_dk), np.asarray(local2.n_dk),
                               atol=1e-4)
    # masked positions never move; the sweep moved something; mass conserved
    m = np.asarray(mask)
    np.testing.assert_array_equal(np.asarray(local2.z)[~m],
                                  np.asarray(local.z)[~m])
    assert float(jnp.mean((local2.z != local.z)[mask].astype(jnp.float32))) \
        > 0.1
    for n in fam.delta_names:
        assert abs(float(deltas[n].sum())) < 1e-3 or n == "s_wk"


@pytest.mark.parametrize("name", ["pdp", "hdp"])
def test_family_sorted_matches_scan_perplexity(name):
    """Acceptance bar extended to PDP/HDP: sorted and scan layouts agree on
    held-out perplexity after 4 single-client sweeps, seed-averaged (same
    protocol as the LDA test above)."""
    from repro.data.synthetic import CorpusConfig, make_topic_corpus
    ccfg = CorpusConfig(n_topics=8, vocab_size=240, n_docs=48, doc_len=32,
                        seed=5)
    tokens, mask, _ = make_topic_corpus(ccfg)
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    cfg = make_family_cfg(name, n_topics=16, vocab_size=240)
    means = {
        layout: sum(_sweep_perplexity(cfg, tokens, mask, layout, seed,
                                      n_sweeps=4)
                    for seed in (2, 3)) / 2
        for layout in ("scan", "sorted")
    }
    rel = abs(means["sorted"] - means["scan"]) / means["scan"]
    assert rel < 0.05, means


# ---------------------------------------------------------------------------
# K-tiling: the tile_k staging axis (DESIGN.md §12)
# ---------------------------------------------------------------------------

def _mhw_inputs(v=60, k=16, b=256, lo=0, hi=60, steps=2, n_pad=0):
    key = jax.random.PRNGKey(v * k + b)
    alpha, beta = 0.1, 0.01
    beta_bar = beta * v
    n_wk = jax.random.gamma(key, 1.0, (v, k)) * 5
    n_k = n_wk.sum(0)
    prior = jnp.full((k,), alpha, jnp.float32)
    stale = prior[None, :] * (n_wk + beta) / (n_k[None, :] + beta_bar)
    tabs = ops.build_tables(stale, tile_r=segment.pick_tile(v, 8))
    rows = _sorted_rows(jax.random.fold_in(key, 1), b, lo, hi, v, n_pad)
    z0 = jax.random.randint(jax.random.fold_in(key, 2), (b,), 0, k,
                            jnp.int32)
    ndk = jax.random.gamma(jax.random.fold_in(key, 3), 0.5, (b, k))
    ndk = ndk.at[jnp.arange(b), z0].add(1.0)
    ks = jax.random.split(jax.random.fold_in(key, 4), 5)
    slot = jax.random.randint(ks[0], (steps, b), 0, k, jnp.int32)
    uni = [jax.random.uniform(ks[i], (steps, b)) for i in range(1, 5)]
    return (tabs, stale, n_wk, n_k, prior, rows, z0, ndk, slot, uni,
            beta, beta_bar, steps)


@pytest.mark.parametrize("tile_k,v,lo,hi,n_pad", [
    pytest.param(4, 60, 0, 60, 0, id="4"),
    pytest.param(8, 60, 0, 60, 0, id="8"),
    pytest.param(16, 60, 0, 60, 0, id="16"),
    # mostly-empty vocab tiles and two all-padding batch tiles
    pytest.param(4, 240, 48, 72, 150, id="4-sparse-padded"),
])
def test_mhw_fused_tile_k_bitexact(tile_k, v, lo, hi, n_pad):
    """The K-staging grid axis is pure data movement: for any tile_k the
    fused kernel's draws equal the untiled kernel's and the oracle's,
    bit for bit."""
    (tabs, stale, n_wk, n_k, prior, rows, z0, ndk, slot, uni,
     beta, beta_bar, steps) = _mhw_inputs(v=v, lo=lo, hi=hi, n_pad=n_pad)
    vstart, vcount = _windows(rows, v, 12, 64)

    def run(tk):
        return mhw_fused.mhw_sweep_fused(
            tabs.prob, tabs.alias, tabs.mass, stale, n_wk, n_k, prior,
            rows, z0, ndk, slot, *uni, vstart, vcount, tile_v=12,
            tile_b=64, n_steps=steps, beta=beta, beta_bar=beta_bar,
            tile_k=tk)

    out_r = ref.mhw_sweep_sorted_ref(
        tabs.prob, tabs.alias, tabs.mass, stale, n_wk, n_k, prior, rows,
        z0, ndk, slot, *uni, beta=beta, beta_bar=beta_bar)
    assert bool(jnp.all(run(tile_k) == out_r))
    assert bool(jnp.all(run(tile_k) == run(None)))


@pytest.mark.parametrize("tile_k,v,lo,hi,n_pad", [
    pytest.param(2, 64, 0, 64, 0, id="2"),
    pytest.param(4, 64, 0, 64, 0, id="4"),
    pytest.param(8, 64, 0, 64, 0, id="8"),
    # mostly-empty vocab tiles and two all-padding batch tiles
    pytest.param(4, 256, 40, 72, 150, id="4-sparse-padded"),
])
def test_pdp_fused_tile_k_bitexact(tile_k, v, lo, hi, n_pad):
    """Same staging argument for the PDP kernel's 2K joint-outcome axis
    (e-tiles stage always, K-side stats only for the first nk tiles)."""
    k, b, steps = 8, 256, 2
    key = jax.random.PRNGKey(v * k + b + 1)
    cfg = pdp.PDPConfig(n_topics=k, vocab_size=v, mh_steps=steps,
                        stirling_n_max=128, concentration=5.0)
    m_wk = jnp.floor(jax.random.gamma(key, 1.0, (v, k)) * 3)
    s_wk = jnp.minimum(jnp.ceil(m_wk * 0.5), m_wk)
    shared = pdp.SharedStats(m_wk=m_wk, s_wk=s_wk, m_k=m_wk.sum(0),
                             s_k=s_wk.sum(0))
    tabs, stale = pdp.build_alias(cfg, shared)
    stirl = stirling.as_jax(cfg.stirling_n_max, cfg.discount)
    prior = jnp.full((2 * k,), cfg.alpha, jnp.float32)
    rows = _sorted_rows(jax.random.fold_in(key, 1), b, lo, hi, v, n_pad)
    e0 = jax.random.randint(jax.random.fold_in(key, 2), (b,), 0, 2 * k,
                            jnp.int32)
    ndk = jnp.floor(jax.random.gamma(jax.random.fold_in(key, 3), 0.5,
                                     (b, k)) * 2)
    ndk = ndk.at[jnp.arange(b), e0 % k].add(1.0)
    ks = jax.random.split(jax.random.fold_in(key, 4), 5)
    slot = jax.random.randint(ks[0], (steps, b), 0, 2 * k, jnp.int32)
    uni = [jax.random.uniform(ks[i], (steps, b)) for i in range(1, 5)]
    vstart, vcount = _windows(rows, v, 16, 64)

    def run(tk):
        return mhw_fused.pdp_sweep_fused(
            tabs.prob, tabs.alias, tabs.mass, stale, m_wk, s_wk,
            shared.m_k, shared.s_k, stirl, prior, rows, e0, ndk, slot,
            *uni, vstart, vcount, tile_v=16, tile_b=64, n_steps=steps,
            b_conc=cfg.concentration, a_disc=cfg.discount,
            gamma=cfg.gamma, gamma_bar=cfg.gamma * v, tile_k=tk)

    out_r = ref.pdp_sweep_sorted_ref(
        tabs.prob, tabs.alias, tabs.mass, stale, m_wk, s_wk, shared.m_k,
        shared.s_k, stirl, prior, rows, e0, ndk, slot, *uni,
        b=cfg.concentration, a=cfg.discount, gamma=cfg.gamma,
        gamma_bar=cfg.gamma * v)
    assert bool(jnp.all(run(tile_k) == out_r))
    assert bool(jnp.all(run(tile_k) == run(None)))


@pytest.mark.parametrize("name", ["lda", "pdp"])
def test_family_sweep_sorted_tile_k_bitexact(name, tiny_corpus):
    """cfg.tile_k is representation only: the full sorted sweep produces
    byte-identical deltas with and without K-tiling."""
    import dataclasses
    tokens, mask, _ = tiny_corpus
    fam = family.get(name)
    deltas = {}
    for tk in (None, 4):
        cfg = dataclasses.replace(_family_cfg(name), tile_v=12, tile_k=tk)
        local, shared = fam.init_state(cfg, tokens, mask,
                                       jax.random.PRNGKey(0))
        tables, stale = fam.build_alias(cfg, shared)
        _, deltas[tk] = fam.sweep_sorted(cfg, local, shared, tables,
                                         stale, tokens, mask,
                                         jax.random.PRNGKey(1), None)
    for n in deltas[None]:
        np.testing.assert_array_equal(np.asarray(deltas[None][n]),
                                      np.asarray(deltas[4][n]), err_msg=n)
