"""Pitman-Yor Topic Model / Poisson-Dirichlet Process sampler (paper §2.2).

The language model of each topic is drawn from PDP(b, a, ψ0) with a shared
base distribution ψ0 ~ Dir(γ); the power-law discount ``a`` gives natural-
language word frequencies.  The collapsed sampler tracks, per (word w,
topic t):

  m_wk — number of times dish w served in restaurant t  (customer counts)
  s_wk — number of tables serving dish w in restaurant t (table counts)

and per token an auxiliary indicator r_di ∈ {0,1} (did this token open a new
table).  The joint conditional over (t, r) is given by paper eqs. (5)-(6)
with generalized-Stirling-number ratios; like LDA it splits into a sparse
(n_dt) and a dense (α_t) part, so the same MHW machinery applies with a
state space of 2K outcomes (paper: "a twice as large space of state
variables").  Outcomes are encoded e = t + K·r throughout.

Constraints between the shared statistics (0 ≤ s_wk ≤ m_wk, m_wk > 0 ⇒
s_wk ≥ 1, aggregates m_k = Σ_w m_wk) are exactly the polytope the paper's
projection step (§5.5, our ``repro.core.projection``) maintains under
relaxed consistency.

Two sweep layouts (DESIGN.md §5): ``layout="scan"`` is the sequential
position scan (correctness oracle); ``layout="sorted"`` routes the shard
through the generic token-sorted tile-skipping pipeline of
``repro.core.family`` / ``repro.kernels.mhw_fused`` over the 2K outcome
space, with :func:`sorted_chain_pdp` as the kernel's bit-exact pure-jnp
oracle.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import alias as alias_mod
from repro.core import mhw, stirling

Array = jax.Array


@dataclass(frozen=True)
class PDPConfig:
    n_topics: int
    vocab_size: int
    alpha: float = 0.1      # document Dirichlet
    discount: float = 0.1   # a — power-law discount
    concentration: float = 10.0  # b
    gamma: float = 0.5      # base-distribution Dirichlet ψ0 ~ Dir(γ)
    mh_steps: int = 2
    stirling_n_max: int = 512
    # Driver-side cadence + sorted-layout tile geometry (see LDAConfig for
    # the knob semantics; tiles here cover the 2K joint outcome space).
    alias_refresh_every: int = 1
    tile_v: int | None = None
    tile_b: int | None = None
    tile_k: int | None = None
    sorted_chunks: int = 4


class SharedStats(NamedTuple):
    m_wk: Array  # (V, K) customer counts
    s_wk: Array  # (V, K) table counts
    m_k: Array   # (K,) aggregates (C2 rule: derived)
    s_k: Array   # (K,)


class LocalState(NamedTuple):
    z: Array     # (D, L) topic assignment
    r: Array     # (D, L) table-open indicator
    n_dk: Array  # (D, K) doc-topic counts


def init_state(cfg: PDPConfig, tokens: Array, mask: Array, key: Array
               ) -> tuple[LocalState, SharedStats]:
    d, l = tokens.shape
    kz, kr = jax.random.split(key)
    z = jnp.where(mask, jax.random.randint(kz, (d, l), 0, cfg.n_topics, jnp.int32), 0)
    # Initialize every first occurrence as a table opener; statistically any
    # consistent init works.  Simplest consistent choice: each token opens a
    # table with prob 0.5, then repair s<=m / m>0=>s>=1 via projection logic.
    r = jnp.where(mask, jax.random.bernoulli(kr, 0.5, (d, l)).astype(jnp.int32), 0)
    m_wk = _count(cfg, tokens, z, mask, jnp.ones_like(r))
    s_wk = _count(cfg, tokens, z, mask, r)
    s_wk = jnp.where(m_wk > 0, jnp.maximum(s_wk, 1.0), 0.0)
    s_wk = jnp.minimum(s_wk, m_wk)
    n_dk = jnp.einsum("dl,dlk->dk", mask.astype(jnp.float32),
                      jax.nn.one_hot(z, cfg.n_topics, dtype=jnp.float32))
    return (LocalState(z=z, r=r, n_dk=n_dk),
            SharedStats(m_wk=m_wk, s_wk=s_wk, m_k=m_wk.sum(0), s_k=s_wk.sum(0)))


def _count(cfg, tokens, z, mask, weight):
    w = tokens.reshape(-1)
    t = z.reshape(-1)
    val = (mask.reshape(-1) * weight.reshape(-1)).astype(jnp.float32)
    return jnp.zeros((cfg.vocab_size, cfg.n_topics), jnp.float32).at[w, t].add(val)


def log_factors(table: Array, m_wk_row: Array, s_wk_row: Array,
                m_k: Array, s_k: Array, *, b: float, a: float, gamma: float,
                gamma_bar: float) -> tuple[Array, Array]:
    """Per-token log factors f(t, r) excluding the (α_t + n_dt) factor.

    Implements paper eqs. (5) and (6) for every topic t, given the gathered
    (-di corrected) rows for the token's word.  Shapes: (..., K).
    Returns (log_f_r0, log_f_r1).

    Module-level with scalar hyperparameters so the fused sorted kernel
    (``kernels.mhw_fused``) and the oracle (:func:`sorted_chain_pdp`) call
    the *same* function on tile values — bit-exactness by construction.
    """
    log_denom = jnp.log(b + m_k)
    # r = 0: existing table
    #   (m_tw + 1 - s_tw)/(m_tw + 1) * S^{m+1}_{s} / S^{m}_{s} / (b + m_t)
    occ = jnp.maximum(m_wk_row + 1.0 - s_wk_row, 0.0)
    log_f0 = (jnp.log(occ + 1e-30) - jnp.log(m_wk_row + 1.0)
              + stirling.log_ratio_same(table, m_wk_row, s_wk_row) - log_denom)
    # r = 1: open a new table
    #   (b + a s_t)/(b + m_t) * (s_tw+1)/(m_tw+1) * (γ + s_tw)/(γ̄ + s_t)
    #   * S^{m+1}_{s+1} / S^{m}_{s}
    log_f1 = (jnp.log(b + a * s_k) - log_denom
              + jnp.log(s_wk_row + 1.0) - jnp.log(m_wk_row + 1.0)
              + jnp.log(gamma + s_wk_row) - jnp.log(gamma_bar + s_k)
              + stirling.log_ratio_incr(table, m_wk_row, s_wk_row))
    return log_f0, log_f1


def _log_factors(cfg: PDPConfig, table: Array, m_wk_row: Array,
                 s_wk_row: Array, m_k: Array, s_k: Array
                 ) -> tuple[Array, Array]:
    """Config-bound wrapper around :func:`log_factors`."""
    return log_factors(table, m_wk_row, s_wk_row, m_k, s_k,
                       b=cfg.concentration, a=cfg.discount, gamma=cfg.gamma,
                       gamma_bar=cfg.gamma * cfg.vocab_size)


def own_contrib(k_topics: int, e0: Array, real: Array
                ) -> tuple[Array, Array]:
    """^{-di} one-hot contributions for joint outcomes e = t + K·r.

    Returns (own_t, own_r): (B, K) float32 — the token's own customer and
    table contribution, zeroed for padding lanes.  Shared by the fused
    kernel and the oracle (same ops, same bits).
    """
    z0 = e0 % k_topics
    r0 = e0 // k_topics
    karange = jax.lax.broadcasted_iota(jnp.int32, (1, k_topics), 1)
    own_t = ((karange == z0[:, None]).astype(jnp.float32)
             * real.astype(jnp.float32)[:, None])
    own_r = own_t * (r0[:, None] > 0).astype(jnp.float32)
    return own_t, own_r


def corrected_rows(m_row_raw: Array, s_row_raw: Array, own_t: Array,
                   own_r: Array) -> tuple[Array, Array]:
    """Apply the ^{-di} removal + CRP bookkeeping repair to gathered rows:
    a removed non-opener cannot leave a table-less dish; a removed opener of
    an empty dish removes its table."""
    m_row = m_row_raw - own_t
    s_row = s_row_raw - own_r
    s_row = jnp.where(m_row > 0, jnp.maximum(s_row, 1.0), 0.0)
    s_row = jnp.minimum(s_row, m_row)
    return m_row, s_row


def fresh_log_factors(table: Array, m_wk: Array, s_wk: Array, m_k: Array,
                      s_k: Array, *, b: float, a: float, gamma: float,
                      gamma_bar: float) -> Array:
    """(V, 2K) fresh log factors of every token-type row with no token
    removed — the values :func:`sorted_chain_pdp` computes for every
    column except a token's own topic (subtracting a zero own
    contribution leaves every operand bit-identical)."""
    m_row, s_row = corrected_rows(m_wk, s_wk, 0.0, 0.0)
    log_f0, log_f1 = log_factors(table, m_row, s_row, m_k[None, :],
                                 s_k[None, :], b=b, a=a, gamma=gamma,
                                 gamma_bar=gamma_bar)
    return jnp.concatenate([log_f0, log_f1], axis=-1)


def own_log_factors(table: Array, m_wk: Array, s_wk: Array, m_k: Array,
                    s_k: Array, rows: Array, e0: Array, *, b: float,
                    a: float, gamma: float, gamma_bar: float
                    ) -> tuple[Array, Array]:
    """Each sorted token's own-topic log factors (r = 0, r = 1) after the
    ^{-di} removal: (B,) each — the own-topic columns of
    :func:`sorted_chain_pdp`, evaluated per token (rows ≥ V are padding
    and remove nothing)."""
    v, k_topics = m_wk.shape
    real = (rows < v).astype(jnp.float32)
    r = jnp.clip(rows, 0, v - 1)
    z0 = e0 % k_topics
    own_t = real
    own_r = own_t * ((e0 // k_topics) > 0).astype(jnp.float32)
    m_row, s_row = corrected_rows(m_wk[r, z0], s_wk[r, z0], own_t, own_r)
    return log_factors(table, m_row, s_row, m_k[z0] - own_t,
                       s_k[z0] - own_r, b=b, a=a, gamma=gamma,
                       gamma_bar=gamma_bar)


def dense_probs(cfg: PDPConfig, shared: SharedStats) -> Array:
    """Dense proposal term over the joint (t, r) space: (V, 2K).

    α_t · f(t, r) for every token-type; columns [0:K] are r=0, [K:2K] r=1.
    """
    table = stirling.as_jax(cfg.stirling_n_max, cfg.discount)
    log_f0, log_f1 = _log_factors(cfg, table, shared.m_wk, shared.s_wk,
                                  shared.m_k[None, :], shared.s_k[None, :])
    return cfg.alpha * jnp.concatenate([jnp.exp(log_f0), jnp.exp(log_f1)], axis=-1)


def build_alias(cfg: PDPConfig, shared: SharedStats) -> tuple[alias_mod.AliasTable, Array]:
    dp = dense_probs(cfg, shared)
    return alias_mod.build(dp), dp


@partial(jax.jit, static_argnames=("cfg", "method", "layout"))
def sweep(
    cfg: PDPConfig,
    local: LocalState,
    shared: SharedStats,
    tables: alias_mod.AliasTable,
    stale_dense: Array,
    tokens: Array,
    mask: Array,
    key: Array,
    method: str = "mhw",
    layout: str = "scan",
    sorted_layouts: tuple | None = None,
) -> tuple[LocalState, Array, Array]:
    """One Gibbs sweep; returns new local state + (V,K) deltas for m and s.

    ``layout="sorted"`` (mhw only) runs the generic token-sorted
    tile-skipping pipeline over the 2K joint outcomes (see
    ``repro.core.family``); pass prebuilt ``sorted_layouts`` from
    ``family.get("pdp").build_sorted_layouts`` to hoist the per-shard sorts.
    """
    if layout == "sorted":
        if method != "mhw":
            raise ValueError("layout='sorted' requires method='mhw'")
        from repro.core import family as family_mod
        local2, deltas = family_mod.get("pdp").sweep_sorted(
            cfg, local, shared, tables, stale_dense, tokens, mask, key,
            sorted_layouts)
        return local2, deltas["m_wk"], deltas["s_wk"]
    if layout != "scan":
        raise ValueError(f"unknown layout {layout!r}")
    d, l = tokens.shape
    k_topics = cfg.n_topics
    table = stirling.as_jax(cfg.stirling_n_max, cfg.discount)
    m_wk, s_wk = shared.m_wk, shared.s_wk
    m_k, s_k = shared.m_k, shared.s_k

    def position_step(carry, inputs):
        n_dk = carry
        w, z_old, r_old, m, k = inputs
        docs = jnp.arange(d)
        mf = m.astype(jnp.float32)

        # --- remove own contribution (the ^{-di} correction) -------------
        n_dk_m = n_dk.at[docs, z_old].add(-mf)
        own_t = jax.nn.one_hot(z_old, k_topics) * mf[:, None]
        own_r = own_t * r_old.astype(jnp.float32)[:, None]
        m_row, s_row = corrected_rows(m_wk[w], s_wk[w], own_t, own_r)
        m_k_m = m_k[None, :] - own_t
        s_k_m = s_k[None, :] - own_r

        log_f0, log_f1 = _log_factors(cfg, table, m_row, s_row, m_k_m, s_k_m)
        log_f = jnp.concatenate([log_f0, log_f1], axis=-1)       # (D, 2K)
        # joint target over e = t + K*r:  (n_dt + α) * f(t, r)
        n_dk_ext = jnp.concatenate([n_dk_m, n_dk_m], axis=-1)

        if method == "exact":
            logits = jnp.log(n_dk_ext + cfg.alpha) + log_f
            e_new = jax.random.categorical(k, logits, axis=-1).astype(jnp.int32)
        elif method == "mhw":
            sparse_w = n_dk_ext * jnp.exp(log_f)
            prop = mhw.MixtureProposal(
                sparse_weights=sparse_w, dense_tables=tables, dense_rows=w)

            def log_p(e):
                return (jnp.log(n_dk_ext[docs, e] + cfg.alpha) + log_f[docs, e])

            e_old = z_old + k_topics * r_old
            e_new = mhw.mh_chain(k, e_old, prop, stale_dense, log_p, cfg.mh_steps)
        else:
            raise ValueError(method)

        z_new = jnp.where(m, e_new % k_topics, z_old)
        r_new = jnp.where(m, e_new // k_topics, r_old)
        n_dk_out = n_dk_m.at[docs, z_new].add(mf)
        return n_dk_out, (z_new, r_new)

    keys = jax.random.split(key, l)
    inputs = (tokens.T, local.z.T, local.r.T, mask.T, keys)
    n_dk_final, (z_t, r_t) = jax.lax.scan(position_step, local.n_dk, inputs)
    z_new, r_new = z_t.T, r_t.T

    delta_m, delta_s = deltas_from(cfg, tokens, mask, local.z, local.r,
                                   z_new, r_new)
    return (LocalState(z=z_new, r=r_new, n_dk=n_dk_final), delta_m, delta_s)


def deltas_from(cfg: PDPConfig, tokens: Array, mask: Array, z_old: Array,
                r_old: Array, z_new: Array, r_new: Array
                ) -> tuple[Array, Array]:
    """(V, K) customer/table count deltas between two assignment states."""
    w_flat = tokens.reshape(-1)
    mf = mask.reshape(-1).astype(jnp.float32)
    delta_m = (
        jnp.zeros((cfg.vocab_size, cfg.n_topics), jnp.float32)
        .at[w_flat, z_new.reshape(-1)].add(mf)
        .at[w_flat, z_old.reshape(-1)].add(-mf)
    )
    delta_s = (
        jnp.zeros((cfg.vocab_size, cfg.n_topics), jnp.float32)
        .at[w_flat, z_new.reshape(-1)].add(mf * r_new.reshape(-1))
        .at[w_flat, z_old.reshape(-1)].add(-mf * r_old.reshape(-1))
    )
    return delta_m, delta_s


def sorted_chain_pdp(prob: Array, alias: Array, mass: Array, stale: Array,
                     m_wk: Array, s_wk: Array, m_k: Array, s_k: Array,
                     stirl: Array, prior: Array, rows: Array, e0: Array,
                     ndk: Array, slot: Array, coin: Array, u_mix: Array,
                     u_sparse: Array, u_acc: Array, *, b: float, a: float,
                     gamma: float, gamma_bar: float) -> Array:
    """Whole-shard MH chain over the token-sorted stream — PDP's 2K space.

    Pure-jnp reference semantics of ``kernels.mhw_fused.pdp_sweep_fused``:
    the fresh Stirling-ratio factors, the ^{-di} correction + CRP repair and
    the chain itself (via ``mhw.mix_chain``) use the exact functions the
    kernel uses, so outputs are bit-identical given the same uniforms.

    prob/alias/stale: (V, 2K); mass: (V,); m_wk/s_wk: (V, K); m_k/s_k: (K,);
    stirl: the log-Stirling table; prior: (2K,); rows/e0: (B,) sorted
    token-types (≥V ⇒ padding, kept at e0) and joint-outcome chain init;
    ndk: (B, K) *raw* gathered doc rows; uniforms: (S, B), slot in [0, 2K).
    Returns (B,) int32 final joint outcomes.
    """
    v, k_topics = m_wk.shape
    real = rows < v
    r = jnp.clip(rows, 0, v - 1)

    own_t, own_r = own_contrib(k_topics, e0, real)
    m_row, s_row = corrected_rows(m_wk[r], s_wk[r], own_t, own_r)
    m_k_m = m_k[None, :] - own_t
    s_k_m = s_k[None, :] - own_r

    log_f0, log_f1 = log_factors(stirl, m_row, s_row, m_k_m, s_k_m,
                                 b=b, a=a, gamma=gamma, gamma_bar=gamma_bar)
    log_f = jnp.concatenate([log_f0, log_f1], axis=-1)         # (B, 2K)
    ndk_m = ndk - own_t
    ndk_ext = jnp.concatenate([ndk_m, ndk_m], axis=-1)
    sparse_w = ndk_ext * jnp.exp(log_f)

    e = mhw.mix_chain(e0, doc=ndk_ext, prior=prior, logf=log_f,
                      sparse_w=sparse_w, stale_rows=stale[r],
                      prob_rows=prob[r], alias_rows=alias[r],
                      dense_mass=mass[r], slot=slot, coin=coin, u_mix=u_mix,
                      u_sparse=u_sparse, u_acc=u_acc)
    return jnp.where(real, e, e0).astype(jnp.int32)


def apply_delta(shared: SharedStats, delta_m: Array, delta_s: Array) -> SharedStats:
    m_wk = shared.m_wk + delta_m
    s_wk = shared.s_wk + delta_s
    # C2 aggregation rule (paper Alg. 1): aggregates derived from counterparts.
    return SharedStats(m_wk=m_wk, s_wk=s_wk, m_k=m_wk.sum(0), s_k=s_wk.sum(0))


def language_model(cfg: PDPConfig, shared: SharedStats) -> Array:
    """Posterior-mean p(w|t): hierarchical CRP smoothing with base ψ0."""
    b, a = cfg.concentration, cfg.discount
    gamma_bar = cfg.gamma * cfg.vocab_size
    s_w = shared.s_wk.sum(-1)  # (V,)
    p0 = (cfg.gamma + s_w) / (gamma_bar + s_w.sum())
    direct = jnp.maximum(shared.m_wk - a * shared.s_wk, 0.0)
    back = (b + a * shared.s_k)[None, :] * p0[:, None]
    return (direct + back) / (b + shared.m_k)[None, :]


@partial(jax.jit, static_argnames=("cfg", "n_fold_sweeps"))
def perplexity(cfg: PDPConfig, shared: SharedStats, tokens: Array, mask: Array,
               key: Array, n_fold_sweeps: int = 10) -> Array:
    phi = language_model(cfg, shared)  # (V, K)
    d, l = tokens.shape
    k_init, k_sweeps = jax.random.split(key)
    z = jax.random.randint(k_init, (d, l), 0, cfg.n_topics, jnp.int32)
    onehot = jax.nn.one_hot(jnp.where(mask, z, 0), cfg.n_topics, dtype=jnp.float32)
    n_dk = jnp.einsum("dl,dlk->dk", mask.astype(jnp.float32), onehot)

    def fold_sweep(carry, k):
        z, n_dk = carry

        def pos(c, inp):
            n_dk = c
            w, z_old, m, kk = inp
            docs = jnp.arange(d)
            mf = m.astype(jnp.float32)
            n_dk_m = n_dk.at[docs, z_old].add(-mf)
            logits = jnp.log(n_dk_m + cfg.alpha) + jnp.log(phi[w] + 1e-30)
            z_new = jax.random.categorical(kk, logits, axis=-1).astype(jnp.int32)
            z_new = jnp.where(m, z_new, z_old)
            return n_dk_m.at[docs, z_new].add(mf), z_new

        keys = jax.random.split(k, l)
        n_dk2, z_t = jax.lax.scan(pos, n_dk, (tokens.T, z.T, mask.T, keys))
        return (z_t.T, n_dk2), None

    (z, n_dk), _ = jax.lax.scan(fold_sweep, (z, n_dk),
                                jax.random.split(k_sweeps, n_fold_sweeps))
    theta = (n_dk + cfg.alpha) / (n_dk.sum(-1, keepdims=True) + cfg.alpha * cfg.n_topics)
    pw = jnp.einsum("dk,dlk->dl", theta, phi[tokens])
    logp = jnp.where(mask, jnp.log(pw + 1e-30), 0.0)
    return jnp.exp(-logp.sum() / jnp.maximum(mask.sum(), 1))
