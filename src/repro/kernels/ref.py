"""Pure-jnp oracles for the Pallas kernels.

Each kernel in this package has a reference implementation here; tests sweep
shapes/dtypes and assert allclose between the kernel (interpret=True on CPU)
and these oracles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import alias as alias_mod
from repro.core import mhw as mhw_mod


def alias_build_ref(p: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Reference alias-table construction: (prob, alias, mass) per row."""
    t = alias_mod.build(p)
    return t.prob, t.alias, t.mass


def dense_probs_ref(n_wk: jax.Array, n_k: jax.Array, prior: jax.Array,
                    beta: float, beta_bar: float) -> jax.Array:
    """Dense term of the LM-dense families, prior_e·((n_wk+β)/(n_k+β̄)),
    in the families' ``dense_probs`` order — fused into alias_build_fused."""
    return prior[None, :] * ((n_wk + beta) / (n_k[None, :] + beta_bar))


def alias_build_fused_ref(n_wk, n_k, prior, beta, beta_bar):
    """Oracle for the fused dense-term + alias-table build:
    (prob, alias, mass, dense)."""
    dp = dense_probs_ref(n_wk, n_k, prior, beta, beta_bar)
    return (*alias_build_ref(dp), dp)


def alias_sample_ref(prob: jax.Array, alias: jax.Array, rows: jax.Array,
                     slot: jax.Array, coin: jax.Array) -> jax.Array:
    """Reference O(1) alias draws with *given* uniforms.

    rows: (B,) table-row per draw; slot: (B,) int in [0,K); coin: (B,) in
    [0,1).  Deterministic given the uniforms, so kernel vs oracle compare
    exactly.
    """
    p = prob[rows, slot]
    a = alias[rows, slot]
    return jnp.where(coin < p, slot, a).astype(jnp.int32)


def alias_sample_sorted_ref(prob: jax.Array, alias: jax.Array,
                            rows: jax.Array, slot: jax.Array,
                            coin: jax.Array) -> jax.Array:
    """Reference for the tile-skipping sorted sampler: same draws as
    :func:`alias_sample_ref` for in-vocab rows, 0 for padding sentinels
    (rows ≥ V), matching the kernel's zero-initialized output blocks."""
    v = prob.shape[0]
    r = jnp.clip(rows, 0, v - 1)
    draw = alias_sample_ref(prob, alias, r, slot, coin)
    return jnp.where(rows < v, draw, 0).astype(jnp.int32)


def mhw_sweep_sorted_ref(prob, alias, mass, stale, n_wk, n_k, prior, rows,
                         z0, ndk, slot, coin, u_mix, u_sparse, u_acc, *,
                         beta, beta_bar, fold_in=False):
    """Oracle for ``kernels.mhw_fused.mhw_sweep_fused`` (lm families:
    LDA with prior = α·1, HDP with prior = b1·θ0) — delegates to the
    pure-jnp chain semantics owned by ``repro.core.mhw``."""
    return mhw_mod.sorted_chain(prob, alias, mass, stale, n_wk, n_k, prior,
                                rows, z0, ndk, slot, coin, u_mix, u_sparse,
                                u_acc, beta=beta, beta_bar=beta_bar,
                                fold_in=fold_in)


def pdp_sweep_sorted_ref(prob, alias, mass, stale, m_wk, s_wk, m_k, s_k,
                         stirl, prior, rows, e0, ndk, slot, coin, u_mix,
                         u_sparse, u_acc, *, b, a, gamma, gamma_bar):
    """Oracle for ``kernels.mhw_fused.pdp_sweep_fused`` — delegates to the
    pure-jnp chain semantics owned by ``repro.core.pdp``."""
    from repro.core import pdp as pdp_mod
    return pdp_mod.sorted_chain_pdp(prob, alias, mass, stale, m_wk, s_wk,
                                    m_k, s_k, stirl, prior, rows, e0, ndk,
                                    slot, coin, u_mix, u_sparse, u_acc,
                                    b=b, a=a, gamma=gamma,
                                    gamma_bar=gamma_bar)


def mh_accept_ref(z: jax.Array, cand: jax.Array, log_p_z: jax.Array,
                  log_p_cand: jax.Array, log_q_z: jax.Array,
                  log_q_cand: jax.Array, u: jax.Array) -> jax.Array:
    """Reference MH accept step (paper eq. 7) with given uniforms."""
    log_ratio = log_p_cand - log_p_z + log_q_z - log_q_cand
    accept = jnp.log(u + 1e-30) < log_ratio
    return jnp.where(accept, cand, z).astype(jnp.int32)
