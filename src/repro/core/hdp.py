"""HDP-LDA: Hierarchical Dirichlet Process topic model (paper §2.3).

Document-side hierarchy: θ_d ~ DP(b1, θ0), θ0 ~ DP(b0, H).  We use the
truncated direct-assignment sampler of Teh et al. [20] with auxiliary table
counts, which is the scheme the paper's shared-statistics list corresponds
to (root counts + per-document table counts + word-topic counts):

  p(z_di = t | rest) ∝ (n_dt^{-di} + b1·θ0_t) · (n_wt + β)/(n_t + β̄)

  m_dk ~ CRT(n_dk, b1·θ0_k)          (Antoniak / Chinese-restaurant-table)
  θ0   ~ Dir(m_·1 + b0/K, …, m_·K + b0/K)

The conditional again splits into a document-sparse term (n_dt) and a dense
term (b1·θ0_t · LM), so MHW applies unchanged.  Shared statistics: n_wk,
n_k, m_k (aggregated table counts) and θ0; local: z, n_dk, m_dk.

Constraints under relaxed consistency: 1 ≤ m_dk ≤ n_dk whenever n_dk > 0
and m_dk = 0 otherwise — maintained by ``repro.core.projection.HDP_RULES``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import alias as alias_mod
from repro.core import mhw

Array = jax.Array


@dataclass(frozen=True)
class HDPConfig:
    n_topics: int           # truncation level K
    vocab_size: int
    b0: float = 1.0         # root DP concentration
    b1: float = 1.0         # document DP concentration
    beta: float = 0.01      # topic-word Dirichlet
    mh_steps: int = 2
    crt_max: int = 128      # max count for exact CRT sampling
    # Driver-side cadence + sorted-layout tile geometry (see LDAConfig for
    # the knob semantics).
    alias_refresh_every: int = 1
    tile_v: int | None = None
    tile_b: int | None = None
    tile_k: int | None = None
    sorted_chunks: int = 4


class SharedStats(NamedTuple):
    n_wk: Array   # (V, K)
    n_k: Array    # (K,)
    m_k: Array    # (K,) aggregated table counts
    theta0: Array # (K,) root topic distribution


class LocalState(NamedTuple):
    z: Array      # (D, L)
    n_dk: Array   # (D, K)
    m_dk: Array   # (D, K) per-document table counts


def init_state(cfg: HDPConfig, tokens: Array, mask: Array, key: Array
               ) -> tuple[LocalState, SharedStats]:
    d, l = tokens.shape
    kz, kt = jax.random.split(key)
    z = jnp.where(mask, jax.random.randint(kz, (d, l), 0, cfg.n_topics, jnp.int32), 0)
    onehot = jax.nn.one_hot(z, cfg.n_topics, dtype=jnp.float32)
    n_dk = jnp.einsum("dl,dlk->dk", mask.astype(jnp.float32), onehot)
    w = tokens.reshape(-1)
    n_wk = (jnp.zeros((cfg.vocab_size, cfg.n_topics), jnp.float32)
            .at[w, z.reshape(-1)].add(mask.reshape(-1).astype(jnp.float32)))
    m_dk = jnp.minimum(n_dk, 1.0)  # one table per occupied (d, k) to start
    m_k = m_dk.sum(0)
    theta0 = (m_k + cfg.b0 / cfg.n_topics) / (m_k.sum() + cfg.b0)
    return (LocalState(z=z, n_dk=n_dk, m_dk=m_dk),
            SharedStats(n_wk=n_wk, n_k=n_wk.sum(0), m_k=m_k, theta0=theta0))


def language_model(cfg: HDPConfig, shared: SharedStats) -> Array:
    beta_bar = cfg.beta * cfg.vocab_size
    return (shared.n_wk + cfg.beta) / (shared.n_k[None, :] + beta_bar)


def dense_probs(cfg: HDPConfig, shared: SharedStats) -> Array:
    """Dense term b1·θ0_t · (n_wt+β)/(n_t+β̄): (V, K) rows per token-type."""
    return cfg.b1 * shared.theta0[None, :] * language_model(cfg, shared)


def build_alias(cfg: HDPConfig, shared: SharedStats):
    """Alias tables over the dense term, and the dense term itself: the
    LM-dense families' full build."""
    from repro.core import family as family_mod
    return family_mod.get("hdp").build_alias(cfg, shared)


@partial(jax.jit, static_argnames=("cfg", "method", "layout"))
def sweep(
    cfg: HDPConfig,
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
    """One Gibbs sweep over z. Returns (local', delta_wk, delta_k).

    ``layout="sorted"`` (mhw only) runs the generic token-sorted
    tile-skipping pipeline with the HDP dense term b1·θ0_t as the
    per-topic prior vector (``repro.core.family``); pass prebuilt
    ``sorted_layouts`` from ``family.get("hdp").build_sorted_layouts``
    to hoist the per-shard sorts out of the sweep.
    """
    if layout == "sorted":
        if method != "mhw":
            raise ValueError("layout='sorted' requires method='mhw'")
        from repro.core import family as family_mod
        local2, deltas = family_mod.get("hdp").sweep_sorted(
            cfg, local, shared, tables, stale_dense, tokens, mask, key,
            sorted_layouts)
        return local2, deltas["n_wk"], deltas["n_wk"].sum(0)
    if layout != "scan":
        raise ValueError(f"unknown layout {layout!r}")
    d, l = tokens.shape
    beta_bar = cfg.beta * cfg.vocab_size
    n_wk, n_k, theta0 = shared.n_wk, shared.n_k, shared.theta0

    def position_step(carry, inputs):
        n_dk = carry
        w, z_old, m, k = inputs
        docs = jnp.arange(d)
        mf = m.astype(jnp.float32)

        n_dk_m = n_dk.at[docs, z_old].add(-mf)
        own = jax.nn.one_hot(z_old, cfg.n_topics) * mf[:, None]
        lm_fresh = (n_wk[w] - own + cfg.beta) / (n_k[None, :] - own + beta_bar)

        if method == "exact":
            logits = (jnp.log(n_dk_m + cfg.b1 * theta0[None, :])
                      + jnp.log(lm_fresh + 1e-30))
            z_new = jax.random.categorical(k, logits, axis=-1).astype(jnp.int32)
        elif method == "mhw":
            sparse_w = n_dk_m * lm_fresh
            prop = mhw.MixtureProposal(
                sparse_weights=sparse_w, dense_tables=tables, dense_rows=w)

            def log_p(t):
                return (jnp.log(n_dk_m[docs, t] + cfg.b1 * theta0[t] + 1e-30)
                        + jnp.log(lm_fresh[docs, t] + 1e-30))

            z_new = mhw.mh_chain(k, z_old, prop, stale_dense, log_p, cfg.mh_steps)
        else:
            raise ValueError(method)

        z_new = jnp.where(m, z_new, z_old)
        return n_dk_m.at[docs, z_new].add(mf), z_new

    keys = jax.random.split(key, l)
    n_dk_final, z_t = jax.lax.scan(position_step, local.n_dk,
                                   (tokens.T, local.z.T, mask.T, keys))
    z_new = z_t.T

    w_flat = tokens.reshape(-1)
    mf = mask.reshape(-1).astype(jnp.float32)
    delta_wk = (
        jnp.zeros((cfg.vocab_size, cfg.n_topics), jnp.float32)
        .at[w_flat, z_new.reshape(-1)].add(mf)
        .at[w_flat, local.z.reshape(-1)].add(-mf)
    )
    return (LocalState(z=z_new, n_dk=n_dk_final, m_dk=local.m_dk),
            delta_wk, delta_wk.sum(0))


@partial(jax.jit, static_argnames=("cfg",))
def resample_tables(cfg: HDPConfig, local: LocalState, shared: SharedStats,
                    key: Array) -> tuple[LocalState, Array]:
    """Antoniak step: m_dk ~ CRT(n_dk, b1 θ0_k); returns new local + m_k.

    CRT(n, c) = Σ_{j=0}^{n-1} Bernoulli(c / (c + j)); exact for n ≤ crt_max,
    clamped above (error O(1) tables on O(100+) counts — below sampler noise).
    """
    d = local.n_dk.shape[0]
    c = cfg.b1 * shared.theta0  # (K,)
    j = jnp.arange(cfg.crt_max, dtype=jnp.float32)  # (J,)
    p = c[None, :, None] / (c[None, :, None] + j[None, None, :])  # (1, K, J)
    u = jax.random.uniform(key, (d, cfg.n_topics, cfg.crt_max))
    n = jnp.clip(local.n_dk, 0, cfg.crt_max)
    active = j[None, None, :] < n[:, :, None]
    m_dk = jnp.sum((u < p) & active, axis=-1).astype(jnp.float32)
    # CRT(n,c) >= 1 whenever n >= 1 (the j=0 Bernoulli has p=1).
    m_dk = jnp.where(local.n_dk > 0, jnp.maximum(m_dk, 1.0), 0.0)
    return LocalState(z=local.z, n_dk=local.n_dk, m_dk=m_dk), m_dk.sum(0)


@partial(jax.jit, static_argnames=("cfg",))
def resample_theta0(cfg: HDPConfig, m_k: Array, key: Array) -> Array:
    """θ0 ~ Dir(m_k + b0/K)."""
    conc = m_k + cfg.b0 / cfg.n_topics
    g = jax.random.gamma(key, conc)
    return g / g.sum()


def apply_delta(cfg: HDPConfig, shared: SharedStats, delta_wk: Array,
                delta_k: Array, m_k: Array | None = None,
                theta0: Array | None = None) -> SharedStats:
    return SharedStats(
        n_wk=shared.n_wk + delta_wk,
        n_k=shared.n_k + delta_k,
        m_k=shared.m_k if m_k is None else m_k,
        theta0=shared.theta0 if theta0 is None else theta0,
    )


@partial(jax.jit, static_argnames=("cfg", "n_fold_sweeps"))
def perplexity(cfg: HDPConfig, shared: SharedStats, tokens: Array, mask: Array,
               key: Array, n_fold_sweeps: int = 10) -> Array:
    phi = language_model(cfg, shared)
    d, l = tokens.shape
    k_init, k_sweeps = jax.random.split(key)
    z = jax.random.randint(k_init, (d, l), 0, cfg.n_topics, jnp.int32)
    onehot = jax.nn.one_hot(jnp.where(mask, z, 0), cfg.n_topics, dtype=jnp.float32)
    n_dk = jnp.einsum("dl,dlk->dk", mask.astype(jnp.float32), onehot)
    prior = cfg.b1 * shared.theta0

    def fold_sweep(carry, k):
        z, n_dk = carry

        def pos(c, inp):
            n_dk = c
            w, z_old, m, kk = inp
            docs = jnp.arange(d)
            mf = m.astype(jnp.float32)
            n_dk_m = n_dk.at[docs, z_old].add(-mf)
            logits = jnp.log(n_dk_m + prior[None, :]) + jnp.log(phi[w] + 1e-30)
            z_new = jax.random.categorical(kk, logits, axis=-1).astype(jnp.int32)
            z_new = jnp.where(m, z_new, z_old)
            return n_dk_m.at[docs, z_new].add(mf), z_new

        keys = jax.random.split(k, l)
        n_dk2, z_t = jax.lax.scan(pos, n_dk, (tokens.T, z.T, mask.T, keys))
        return (z_t.T, n_dk2), None

    (z, n_dk), _ = jax.lax.scan(fold_sweep, (z, n_dk),
                                jax.random.split(k_sweeps, n_fold_sweeps))
    theta = (n_dk + prior[None, :]) / (n_dk.sum(-1, keepdims=True) + prior.sum())
    pw = jnp.einsum("dk,dlk->dl", theta, phi[tokens])
    logp = jnp.where(mask, jnp.log(pw + 1e-30), 0.0)
    return jnp.exp(-logp.sum() / jnp.maximum(mask.sum(), 1))
