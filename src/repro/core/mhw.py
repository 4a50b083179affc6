"""Metropolis-Hastings-Walker sampling (paper §3).

The MHW sampler draws from a slowly-changing categorical distribution ``p``
in amortized O(1) by treating a *stale* snapshot ``q`` of ``p`` (stored as an
alias table) as a stationary MH proposal and correcting with accept/reject:

    Pr{move i -> j} = min(1, q(i) p(j) / (q(j) p(i)))          (paper eq. 7)

For topic models the proposal is the paper's sparse+dense mixture (eq. 4):
a document-sparse term sampled exactly and a corpus-dense term sampled from
the stale alias table; acceptance only needs *point* evaluations of p and q,
which cost O(1) gathers.

This module is generic over the model family.  Every family factors its
conditional as

    p(e) ∝ (doc_e + prior_e) · f_e          (the ``ModelFamily`` protocol's
                                             dense-proposal factorization)

with ``doc`` the document-sparse counts over E outcomes (E = K topics for
LDA/HDP, 2K joint (topic, table-indicator) outcomes for PDP), ``prior`` the
per-outcome prior mass (α for LDA, b1·θ0_t for HDP) and ``f`` the fresh
corpus factor (the LM row for LDA/HDP, the Stirling-ratio factor for PDP).
The stale dense term ``prior·f_stale`` lives in the alias table.

Two layouts are supported (DESIGN.md §5):

* position-scan — :func:`mh_chain` runs inside each family's sequential
  position scan (one chain per document per position);
* token-sorted — :func:`mix_chain` is the single pure-jnp chain semantics
  over the sorted stream of ``repro.data.segment``, shared bit-for-bit by
  the per-family oracles (:func:`sorted_chain`, ``pdp.sorted_chain_pdp``)
  and the fused Pallas kernels (``repro.kernels.mhw_fused``), which must
  match them bit-exactly given the same uniforms.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import alias as alias_mod

Array = jax.Array


class MixtureProposal(NamedTuple):
    """The paper's sparse+dense proposal for one batch of tokens.

    sparse_weights: (B, K) unnormalized sparse-term weights (e.g. n_dk rows).
      Zero rows are fine (coin then always selects dense).
    dense_tables: per-row alias tables, (R, K).
    dense_rows:  (B,) row index (token-type) into ``dense_tables`` per token.
    """

    sparse_weights: Array
    dense_tables: alias_mod.AliasTable
    dense_rows: Array

    def sample(self, key: Array) -> Array:
        """Draw one proposal per token: (B,) int32."""
        b = self.sparse_weights.shape[0]
        k_coin, k_sparse, k_dense = jax.random.split(key, 3)
        sparse_mass = jnp.sum(self.sparse_weights, axis=-1)
        dense_mass = self.dense_tables.mass[self.dense_rows]
        total = sparse_mass + dense_mass
        pick_sparse = jax.random.uniform(k_coin, (b,)) * total < sparse_mass
        # Sparse draw: vectorized categorical over K lanes (TPU analogue of
        # the O(k_d) sparse walk; see DESIGN.md §2).
        gumbel = jax.random.gumbel(k_sparse, self.sparse_weights.shape)
        logw = jnp.log(self.sparse_weights + 1e-30)
        sparse_draw = jnp.argmax(logw + gumbel, axis=-1).astype(jnp.int32)
        dense_draw = alias_mod.sample_rows(self.dense_tables, self.dense_rows, k_dense)
        return jnp.where(pick_sparse, sparse_draw, dense_draw)

    def log_q(self, outcome: Array, dense_probs: Array) -> Array:
        """Unnormalized log proposal density at ``outcome`` (B,).

        ``dense_probs`` is the (R, K) *stale* unnormalized dense distribution
        the alias tables were built from (needed for point evaluation — the
        table itself only supports sampling).
        """
        b = jnp.arange(outcome.shape[0])
        sparse_val = self.sparse_weights[b, outcome]
        dense_val = dense_probs[self.dense_rows, outcome]
        return jnp.log(sparse_val + dense_val + 1e-30)


def accept_log_ratio(log_p_cand: Array, log_p_cur: Array,
                     log_q_cur: Array, log_q_cand: Array) -> Array:
    """Paper eq. 7 in log space: log [p(j) q(i)] − log [p(i) q(j)].

    The single acceptance rule every family and every layout uses — the
    ``ModelFamily.accept_ratio`` protocol hook resolves here.
    """
    return log_p_cand - log_p_cur + log_q_cur - log_q_cand


def mh_chain(
    key: Array,
    init: Array,
    proposal: MixtureProposal,
    dense_probs: Array,
    log_p: Callable[[Array], Array],
    n_steps: int,
) -> Array:
    """Run ``n_steps`` of stationary-proposal MH for a batch of tokens.

    init: (B,) current states (e.g. current topic assignments).
    log_p: maps (B,) outcomes -> (B,) unnormalized log target density.
    Returns the final (B,) states.
    """

    def step(carry, k):
        z = carry
        k_prop, k_acc = jax.random.split(k)
        cand = proposal.sample(k_prop)
        log_ratio = accept_log_ratio(
            log_p(cand), log_p(z),
            proposal.log_q(z, dense_probs), proposal.log_q(cand, dense_probs))
        accept = jnp.log(jax.random.uniform(k_acc, z.shape) + 1e-30) < log_ratio
        return jnp.where(accept, cand, z), accept

    keys = jax.random.split(key, n_steps)
    z, accepts = jax.lax.scan(step, init, keys)
    return z


def mh_chain_with_stats(key, init, proposal, dense_probs, log_p, n_steps):
    """Like mh_chain but also returns the mean acceptance rate (diagnostics)."""

    def step(carry, k):
        z = carry
        k_prop, k_acc = jax.random.split(k)
        cand = proposal.sample(k_prop)
        log_ratio = accept_log_ratio(
            log_p(cand), log_p(z),
            proposal.log_q(z, dense_probs), proposal.log_q(cand, dense_probs))
        accept = jnp.log(jax.random.uniform(k_acc, z.shape) + 1e-30) < log_ratio
        return jnp.where(accept, cand, z), jnp.mean(accept.astype(jnp.float32))

    keys = jax.random.split(key, n_steps)
    z, rates = jax.lax.scan(step, init, keys)
    return z, jnp.mean(rates)


# ---------------------------------------------------------------------------
# Token-sorted layout (DESIGN.md §5) — oracle semantics for the fused kernels
# ---------------------------------------------------------------------------

_EPS = 1e-30


def _gather_k(mat: Array, idx: Array) -> Array:
    """mat: (B, E) or (1, E), idx: (B,) int → (B,) mat[b, idx[b]].

    Written as a one-hot select-and-sum over the E lanes — the form Mosaic
    lowers inside the fused kernels — and exact, because exactly one term
    of each row's sum is non-zero."""
    e = mat.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], e), 1)
    hit = lane == idx.astype(jnp.int32)[:, None]
    return jnp.sum(jnp.where(hit, mat, jnp.zeros((), mat.dtype)), axis=-1)


def cumsum_lanes(x: Array) -> Array:
    """Inclusive prefix sum over the last axis by log-step doubling
    (Hillis-Steele), built from lane rolls — the kernels and the oracles
    share it, so both add in the same order (Mosaic has no cumsum)."""
    e = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    shift = 1
    while shift < e:
        x = x + jnp.where(lane >= shift, jnp.roll(x, shift, axis=-1),
                          jnp.zeros((), x.dtype))
        shift *= 2
    return x


def doc_sparse_logp(doc: Array, prior: Array, outcome: Array) -> Array:
    """log of the document-sparse target factor log(doc_e + prior_e) at
    ``outcome``: doc (B, E), prior (E,) or (1, E), outcome (B,) → (B,).

    THE single implementation — :func:`mix_chain` (and through it every
    oracle and fused kernel) and the ``ModelFamily.doc_sparse_logp``
    protocol hook all resolve here, so the target math cannot fork.
    """
    return jnp.log(_gather_k(doc, outcome)
                   + _gather_k(prior.reshape(1, -1), outcome) + _EPS)


def mix_chain(z0: Array, *, doc: Array, prior: Array, logf: Array,
              sparse_w: Array, stale_rows: Array, prob_rows: Array,
              alias_rows: Array, dense_mass: Array, slot: Array, coin: Array,
              u_mix: Array, u_sparse: Array, u_acc: Array) -> Array:
    """The single whole-stream MH chain over E outcomes, given uniforms.

    The bit-exactness contract of the sorted pipeline: every family's
    pure-jnp oracle AND every fused Pallas kernel call this function on the
    same values, so kernel and oracle cannot drift.

    Target (eq. 4 factorization): p(e) ∝ (doc_e + prior_e) · f_e with
    log f supplied as ``logf``; proposal q(e) ∝ sparse_w_e + stale_e.

    z0: (B,) chain init over outcomes.
    doc/logf/sparse_w/stale_rows/prob_rows/alias_rows: (B, E) per-token rows
      (own-token ^{-di} removal already applied by the caller).
    prior: (E,) or (1, E) per-outcome prior mass (α·1 for LDA/PDP, b1·θ0
      for HDP).
    dense_mass: (B,) stale dense-term mass per token's row.
    slot/coin/u_mix/u_sparse/u_acc: (S, B) per-step uniforms (slot int32 in
      [0, E)).  Returns (B,) int32 final states.
    """
    e_outcomes = doc.shape[-1]
    cdf = cumsum_lanes(sparse_w)
    # Static slices throughout (lax.index_in_dim): Mosaic lowers those,
    # not the dynamic_slice that negative or array indexing emits.
    sparse_mass = jax.lax.index_in_dim(cdf, e_outcomes - 1, 1, keepdims=False)
    step = functools.partial(jax.lax.index_in_dim, axis=0, keepdims=False)

    def log_p(t):
        return doc_sparse_logp(doc, prior, t) + _gather_k(logf, t)

    def log_q(t):
        return jnp.log(_gather_k(sparse_w, t) + _gather_k(stale_rows, t)
                       + _EPS)

    z = z0
    lp_z = log_p(z)
    lq_z = log_q(z)
    for s in range(slot.shape[0]):
        slot_s = step(slot, s)
        dense_draw = jnp.where(step(coin, s) < _gather_k(prob_rows, slot_s),
                               slot_s, _gather_k(alias_rows, slot_s))
        target = step(u_sparse, s) * sparse_mass
        sparse_draw = jnp.clip(
            jnp.sum((cdf <= target[:, None]).astype(jnp.int32), axis=-1),
            0, e_outcomes - 1)
        pick_sparse = (step(u_mix, s) * (sparse_mass + dense_mass)
                       < sparse_mass)
        cand = jnp.where(pick_sparse, sparse_draw, dense_draw).astype(jnp.int32)
        lp_c = log_p(cand)
        lq_c = log_q(cand)
        accept = (jnp.log(step(u_acc, s) + _EPS)
                  < accept_log_ratio(lp_c, lp_z, lq_z, lq_c))
        z = jnp.where(accept, cand, z)
        lp_z = jnp.where(accept, lp_c, lp_z)
        lq_z = jnp.where(accept, lq_c, lq_z)
    return z.astype(jnp.int32)


def sorted_chain(prob: Array, alias: Array, mass: Array, stale: Array,
                 n_wk: Array, n_k: Array, prior: Array, rows: Array,
                 z0: Array, ndk: Array, slot: Array, coin: Array,
                 u_mix: Array, u_sparse: Array, u_acc: Array, *, beta: float,
                 beta_bar: float, fold_in: bool = False) -> Array:
    """Whole-shard MH chain over the token-sorted stream — lm families.

    Pure-jnp reference semantics of ``kernels.mhw_fused.mhw_sweep_fused``
    for the families whose fresh factor is the language-model row
    (n_wk − own + β)/(n_k − own + β̄): LDA (prior = α·1) and HDP-LDA
    (prior = b1·θ0).  Delegates the chain itself to :func:`mix_chain`, which
    the kernel also calls — bit-identical outputs given the same uniforms.
    ``rows`` entries ≥ V are padding and keep ``z0``.

    prob/alias/stale/n_wk: (V, K); mass: (V,); n_k/prior: (K,); rows/z0:
    (B,); ndk: (B, K) *raw* gathered doc rows (the ^{-di} own-token removal
    happens here, as in the kernel); slot/coin/u_mix/u_sparse/u_acc:
    (S, B) per-step uniforms.  ``fold_in`` (serving): the documents are not
    counted in n_wk / n_k, so the own-token removal touches ndk only.
    Returns (B,) int32.
    """
    v, k_topics = prob.shape
    real = rows < v
    r = jnp.clip(rows, 0, v - 1)

    karange = jax.lax.broadcasted_iota(jnp.int32, (1, k_topics), 1)
    own = ((karange == z0[:, None]) & real[:, None]).astype(jnp.float32)
    ndk = ndk - own
    rows_wk = n_wk[r]
    own_wk = 0.0 if fold_in else own
    lm = (rows_wk - own_wk + beta) / (n_k[None, :] - own_wk + beta_bar)

    z = mix_chain(z0, doc=ndk, prior=prior, logf=jnp.log(lm + _EPS),
                  sparse_w=ndk * lm, stale_rows=stale[r], prob_rows=prob[r],
                  alias_rows=alias[r], dense_mass=mass[r], slot=slot,
                  coin=coin, u_mix=u_mix, u_sparse=u_sparse, u_acc=u_acc)
    return jnp.where(real, z, z0).astype(jnp.int32)
