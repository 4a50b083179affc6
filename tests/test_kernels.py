"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracles."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import alias as alias_mod
from repro.kernels import alias_build, alias_sample, mh_accept, ops, ref
from tests.test_alias import implied_distribution


@pytest.mark.parametrize("v,k,tile_r", [
    (16, 8, 8), (64, 32, 8), (32, 128, 4), (64, 250, 16), (8, 16, 8),
])
def test_alias_build_kernel_vs_ref(v, k, tile_r):
    p = jax.random.gamma(jax.random.PRNGKey(v * k), 0.3, (v, k)) + 1e-4
    prob_k, alias_k, mass_k = alias_build.alias_build(p, tile_r=tile_r)
    prob_r, alias_r, mass_r = ref.alias_build_ref(p)
    np.testing.assert_allclose(np.asarray(mass_k), np.asarray(mass_r), rtol=1e-6)
    # Tables may differ structurally (stack processing order), so compare the
    # *distributions they encode* — the semantic contract.
    tk = alias_mod.AliasTable(prob_k, alias_k, mass_k)
    tr = alias_mod.AliasTable(prob_r, alias_r, mass_r)
    target = np.asarray(p / p.sum(-1, keepdims=True))
    np.testing.assert_allclose(implied_distribution(tk), target, atol=2e-5)
    np.testing.assert_allclose(implied_distribution(tr), target, atol=2e-5)


def _lm_stats(key, v, k, dtype=jnp.float32):
    n_wk = jnp.floor(jax.random.gamma(key, 1.0, (v, k)) * 4).astype(dtype)
    n_wk = n_wk.at[1].set(0)          # a type with no tokens yet
    prior = 0.05 + jax.random.uniform(jax.random.fold_in(key, 1), (k,))
    return n_wk, n_wk.astype(jnp.float32).sum(0), prior


def _assert_same(got, want):
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_alias_build_fused_kernel(dtype):
    """The fused full build (dense term computed in-register from the raw
    statistics, any input dtype) equals core.alias.build over the dense
    term and writes that dense term as the stale snapshot, bit for bit."""
    v, k = 32, 16
    n_wk, n_k, prior = _lm_stats(jax.random.PRNGKey(0), v, k, dtype)
    kw = dict(beta=0.01, beta_bar=0.01 * v)
    got = alias_build.alias_build_fused(n_wk, n_k, prior, **kw)
    want = ref.alias_build_fused_ref(n_wk.astype(jnp.float32), n_k, prior,
                                     **kw)
    _assert_same(got, want)
    target = np.asarray(want[3] / want[3].sum(-1, keepdims=True))
    np.testing.assert_allclose(
        implied_distribution(alias_mod.AliasTable(*got[:3])), target,
        atol=2e-5)


@pytest.mark.parametrize("v,k,b,tile_v,tile_b", [
    (64, 32, 2048, 16, 256),
    (16, 8, 128, 16, 128),
    (128, 64, 512, 32, 512),
    (64, 250, 1024, 8, 64),
])
def test_alias_sample_kernel_exact(v, k, b, tile_v, tile_b):
    """Given identical uniforms the kernel must match the oracle exactly."""
    key = jax.random.PRNGKey(b)
    p = jax.random.gamma(key, 0.3, (v, k)) + 1e-4
    prob, al, _ = alias_build.alias_build(p, tile_r=min(8, v))
    rows = jax.random.randint(jax.random.fold_in(key, 1), (b,), 0, v, jnp.int32)
    slot = jax.random.randint(jax.random.fold_in(key, 2), (b,), 0, k, jnp.int32)
    coin = jax.random.uniform(jax.random.fold_in(key, 3), (b,))
    out_k = alias_sample.alias_sample(prob, al, rows, slot, coin,
                                      tile_v=tile_v, tile_b=tile_b)
    out_r = ref.alias_sample_ref(prob, al, rows, slot, coin)
    assert bool(jnp.all(out_k == out_r))


@pytest.mark.parametrize("b,tile_b", [(4096, 512), (128, 128), (1024, 256)])
def test_mh_accept_kernel_exact(b, tile_b):
    key = jax.random.PRNGKey(b)
    k = 32
    z = jax.random.randint(jax.random.fold_in(key, 0), (b,), 0, k, jnp.int32)
    cand = jax.random.randint(jax.random.fold_in(key, 1), (b,), 0, k, jnp.int32)
    lps = [jax.random.normal(jax.random.fold_in(key, i), (b,)) for i in range(2, 6)]
    u = jax.random.uniform(jax.random.fold_in(key, 6), (b,))
    out_k = mh_accept.mh_accept(z, cand, *lps, u, tile_b=tile_b)
    out_r = ref.mh_accept_ref(z, cand, *lps, u)
    assert bool(jnp.all(out_k == out_r))


def test_ops_sample_rows_statistics():
    """End-to-end kernel path draws match the target distribution."""
    key = jax.random.PRNGKey(0)
    v, k = 16, 32
    p = jax.random.gamma(key, 0.5, (v, k)) + 1e-3
    tables = ops.build_tables(p, tile_r=8)
    rows = jnp.repeat(jnp.arange(v), 4000)
    s = np.asarray(ops.sample_rows(tables, rows, jax.random.PRNGKey(1),
                                   tile_v=8, tile_b=4000)).reshape(v, -1)
    for r in range(0, v, 5):
        emp = np.bincount(s[r], minlength=k) / s.shape[1]
        refd = np.asarray(p[r] / p[r].sum())
        assert 0.5 * np.abs(emp - refd).sum() < 0.05


@pytest.mark.parametrize("tile_k", [4, 8, 16])
def test_alias_build_tile_k_bitexact(tile_k):
    """The 2-phase K-tiled alias build (stage → build-on-scratch → flush)
    equals the untiled single-phase kernel bit for bit."""
    v, k = 32, 16
    p = jax.random.gamma(jax.random.PRNGKey(7), 0.5, (v, k)) + 1e-4
    want = alias_build.alias_build(p, tile_r=8)
    got = alias_build.alias_build(p, tile_r=8, tile_k=tile_k)
    for a, b in zip(want, got):
        assert bool(jnp.all(a == b))


@pytest.mark.parametrize("tile_r", [8, 16, 32])
def test_alias_build_fused_tile_r_bitexact(tile_r):
    """Full and gathered builds at every row tile equal the reference bit
    for bit — so equal each other — with V a multiple of no tile but 8
    (the last tile is padded) and an all-zero dense row (β = 0, a type
    with no tokens), which takes the uniform fallback."""
    v, k = 40, 16
    n_wk, n_k, prior = _lm_stats(jax.random.PRNGKey(11), v, k)
    kw = dict(beta=0.0, beta_bar=0.0)
    want = ref.alias_build_fused_ref(n_wk, n_k, prior, **kw)
    _assert_same(alias_build.alias_build_fused(n_wk, n_k, prior,
                                               tile_r=tile_r, **kw), want)
    rows = jax.random.permutation(jax.random.PRNGKey(12), v)[:27]
    got = alias_build.alias_build_gather_fused(n_wk, n_k, prior, rows,
                                               tile_r=tile_r, **kw)
    _assert_same(got, [w[rows] for w in want])
    assert float(want[2][1]) == 0.0
    np.testing.assert_array_equal(np.asarray(want[0][1]), np.ones(k))
    np.testing.assert_array_equal(np.asarray(want[1][1]), np.arange(k))


@pytest.mark.parametrize("k,rows,tile_r", [
    (1024, 131072, 64), (1024, 64, 64), (1024, 20, 24), (128, 131072, 512),
    (16, 40, 40), (16, 3, 8), (4096, 131072, 16), (8192, 64, 8),
])
def test_pick_tile_r(k, rows, tile_r):
    """Row tile from K (lanes padded to 128) and the rows to build."""
    assert alias_build.pick_tile_r(k, rows) == tile_r
