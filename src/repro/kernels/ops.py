"""Jit'd public wrappers around the Pallas kernels.

Whether a kernel is interpreted is the platform's decision
(``repro.kernels.backend``): interpreted on CPU, lowered to Mosaic on TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.alias import AliasTable
from repro.kernels import alias_build as _build
from repro.kernels import alias_sample as _sample
from repro.kernels import mh_accept as _accept
from repro.kernels import mhw_fused as _fused


def build_tables(p: jax.Array, *, tile_r: int = 8) -> AliasTable:
    """Kernel-backed replacement for ``repro.core.alias.build`` (2-D input)."""
    prob, alias, mass = _build.alias_build(p, tile_r=tile_r)
    return AliasTable(prob=prob, alias=alias, mass=mass)


def build_tables_fused(n_wk: jax.Array, n_k: jax.Array, prior: jax.Array,
                       *, beta: float, beta_bar: float
                       ) -> tuple[AliasTable, jax.Array]:
    """Full alias build from raw statistics for the LM-dense families
    (prior_e · (n_wk+β)/(n_k+β̄)); also returns the dense term the kernel
    computed, the stale matrix the sampler's MH correction reads."""
    prob, alias, mass, dense = _build.alias_build_fused(
        n_wk, n_k, prior, beta=beta, beta_bar=beta_bar)
    return AliasTable(prob=prob, alias=alias, mass=mass), dense


def build_tables_rows(p_rows: jax.Array, *, tile_r: int = 8) -> AliasTable:
    """Alias build over a compacted (R, K) block of gathered changed rows
    (the incremental producer's generic path; see alias_build_rows)."""
    prob, alias, mass = _build.alias_build_rows(p_rows, tile_r=tile_r)
    return AliasTable(prob=prob, alias=alias, mass=mass)


def build_tables_gather_fused(n_wk: jax.Array, n_k: jax.Array,
                              prior: jax.Array, rows: jax.Array, *,
                              beta: float, beta_bar: float
                              ) -> tuple[AliasTable, jax.Array]:
    """Gather → fused dense-term + alias build over changed rows only, for
    the LM-dense families (prior_e · (n_wk+β)/(n_k+β̄)).  Returns the
    compacted sub-table plus the matching dense rows for the stale-snapshot
    scatter (``repro.core.alias.update_rows``)."""
    prob, alias, mass, dense = _build.alias_build_gather_fused(
        n_wk, n_k, prior, rows, beta=beta, beta_bar=beta_bar)
    return AliasTable(prob=prob, alias=alias, mass=mass), dense


def sample_rows(tables: AliasTable, rows: jax.Array, key: jax.Array, *,
                tile_v: int = 64, tile_b: int = 1024) -> jax.Array:
    """Kernel-backed replacement for ``repro.core.alias.sample_rows``."""
    k = tables.prob.shape[-1]
    k_slot, k_coin = jax.random.split(key)
    slot = jax.random.randint(k_slot, rows.shape, 0, k, dtype=jnp.int32)
    coin = jax.random.uniform(k_coin, rows.shape)
    return _sample.alias_sample(
        tables.prob, tables.alias, rows, slot, coin, tile_v=tile_v,
        tile_b=tile_b)


def sample_rows_sorted(tables: AliasTable, rows: jax.Array,
                       vstart: jax.Array, vcount: jax.Array, key: jax.Array,
                       *, tile_v: int = _sample.DEFAULT_TILE_V,
                       tile_b: int = _sample.DEFAULT_TILE_B) -> jax.Array:
    """Tile-skipping draws over a token-sorted stream (``segment`` layout).

    ``rows`` must be ascending with padding sentinels ≥ V at the end;
    ``vstart``/``vcount`` come from ``segment.build_layout``.  Padding
    positions return 0.
    """
    k = tables.prob.shape[-1]
    k_slot, k_coin = jax.random.split(key)
    slot = jax.random.randint(k_slot, rows.shape, 0, k, dtype=jnp.int32)
    coin = jax.random.uniform(k_coin, rows.shape)
    return _sample.alias_sample_sorted(
        tables.prob, tables.alias, rows, slot, coin, vstart, vcount,
        tile_v=tile_v, tile_b=tile_b)


def _step_uniforms(key: jax.Array, n_outcomes: int, mh_steps: int, b: int):
    """The five per-MH-step uniform streams every fused sorted chain uses."""
    ks = jax.random.split(key, 5)
    slot = jax.random.randint(ks[0], (mh_steps, b), 0, n_outcomes,
                              dtype=jnp.int32)
    return (slot,) + tuple(jax.random.uniform(ks[i], (mh_steps, b))
                           for i in range(1, 5))


def mhw_sweep_sorted(tables: AliasTable, stale: jax.Array, n_wk: jax.Array,
                     n_k: jax.Array, prior: jax.Array, rows: jax.Array,
                     z0: jax.Array, ndk: jax.Array, vstart: jax.Array,
                     vcount: jax.Array, key: jax.Array, *, mh_steps: int,
                     beta: float, beta_bar: float, tile_v: int, tile_b: int,
                     tile_k: int | None = None,
                     uniforms: tuple[jax.Array, ...] | None = None,
                     fold_in: bool = False) -> jax.Array:
    """Fused sorted-layout MHW chain for the lm families (LDA: prior = α·1,
    HDP: prior = b1·θ0): draws the per-step uniforms and runs
    ``kernels.mhw_fused.mhw_sweep_fused`` (see that module's docstring).

    ``uniforms`` overrides the ``_step_uniforms`` draw with caller-supplied
    ``(slot, coin, u_mix, u_sparse, u_acc)`` streams, each ``(mh_steps, b)``
    in sorted-stream order; ``key`` is then unused.  The serving engine uses
    this to keep each document's chain a pure function of its own request
    seed regardless of which slots it shares a batch with.  ``fold_in``:
    the documents are not counted in ``n_wk``/``n_k`` (serving).
    """
    k = tables.prob.shape[-1]
    b = rows.shape[0]
    if uniforms is None:
        uniforms = _step_uniforms(key, k, mh_steps, b)
    slot, coin, u_mix, u_sparse, u_acc = uniforms
    return _fused.mhw_sweep_fused(
        tables.prob, tables.alias, tables.mass, stale, n_wk, n_k, prior,
        rows, z0, ndk, slot, coin, u_mix, u_sparse, u_acc, vstart, vcount,
        tile_v=tile_v, tile_b=tile_b, tile_k=tile_k, n_steps=mh_steps,
        beta=beta, beta_bar=beta_bar, fold_in=fold_in)


def pdp_sweep_sorted(tables: AliasTable, stale: jax.Array, m_wk: jax.Array,
                     s_wk: jax.Array, m_k: jax.Array, s_k: jax.Array,
                     stirl: jax.Array, prior: jax.Array, rows: jax.Array,
                     e0: jax.Array, ndk: jax.Array, vstart: jax.Array,
                     vcount: jax.Array, key: jax.Array, *, mh_steps: int,
                     concentration: float, discount: float, gamma: float,
                     gamma_bar: float, tile_v: int, tile_b: int,
                     tile_k: int | None = None,
                     uniforms: tuple[jax.Array, ...] | None = None,
                     fold_in: bool = False) -> jax.Array:
    """Fused sorted-layout MHW chain for PDP's joint 2K outcome space:
    draws the per-step uniforms (slot over [0, 2K)) and runs
    ``kernels.mhw_fused.pdp_sweep_fused``.  ``uniforms`` overrides the
    draw exactly as in :func:`mhw_sweep_sorted`."""
    e_out = tables.prob.shape[-1]
    b = rows.shape[0]
    if uniforms is None:
        uniforms = _step_uniforms(key, e_out, mh_steps, b)
    slot, coin, u_mix, u_sparse, u_acc = uniforms
    return _fused.pdp_sweep_fused(
        tables.prob, tables.alias, tables.mass, stale, m_wk, s_wk, m_k, s_k,
        stirl, prior, rows, e0, ndk, slot, coin, u_mix, u_sparse, u_acc,
        vstart, vcount, tile_v=tile_v, tile_b=tile_b, tile_k=tile_k,
        n_steps=mh_steps, b_conc=concentration, a_disc=discount,
        gamma=gamma, gamma_bar=gamma_bar, fold_in=fold_in)


def mh_accept(z, cand, log_p_z, log_p_cand, log_q_z, log_q_cand, key, *,
              tile_b: int = 4096):
    """Kernel-backed fused MH accept step."""
    u = jax.random.uniform(key, z.shape)
    return _accept.mh_accept(
        z, cand, log_p_z, log_p_cand, log_q_z, log_q_cand, u,
        tile_b=tile_b)
