"""Collapsed Gibbs sampling for Latent Dirichlet Allocation (paper §2.1).

Two samplers are provided, matching the paper's own experimental comparison:

* ``method="exact"`` — the full-conditional collapsed Gibbs sampler (the
  "YahooLDA" baseline of the paper: SparseLDA-style sampling; on TPU the
  sparse bucket walk becomes a dense K-lane categorical, see DESIGN.md §2).
* ``method="mhw"`` — AliasLDA: the Metropolis-Hastings-Walker sampler of
  paper §3.  The conditional is split per eq. (4) into a document-sparse
  term (kept exact) and a corpus-dense term `α_t · (n_wt+β)/(n_t+β̄)`
  approximated by a *stale* alias table, corrected by MH accept/reject.

Layout conventions
------------------
Documents are padded to a fixed length L with ``mask`` marking real tokens.
Two sweep layouts are provided (DESIGN.md §5):

* ``layout="scan"`` — the token sweep scans positions (so the per-document
  counts ``n_dk`` stay exact, as in a sequential Gibbs sweep) and
  vectorizes across documents — the TPU analogue of the paper's per-client
  multithreaded sampler, which is likewise relaxed *between* documents.
  This is the correctness oracle.
* ``layout="sorted"`` (``method="mhw"`` only) — the paper's word-major
  order: the flat token stream is sorted by token-type
  (``repro.data.segment``) and the whole shard runs as one fused
  tile-skipping Pallas chain (``repro.kernels.mhw_fused``), each token
  proposing against the sweep-start counts minus its own contribution
  (Jacobi-style within the sweep, like the paper's per-word relaxation).
  Each ``n_wk`` row is touched once per resident tile pair instead of once
  per scan position.

Sufficient statistics:
  n_dk (D, K) — document-topic counts, client-local (paper §5.2).
  n_wk (V, K) — word-topic counts, shared via the parameter server.
  n_k  (K,)   — topic totals, shared (aggregation parameter).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import alias as alias_mod
from repro.core import mhw
from repro.data import segment

Array = jax.Array


@dataclass(frozen=True)
class LDAConfig:
    n_topics: int
    vocab_size: int
    alpha: float = 0.1
    beta: float = 0.01
    mh_steps: int = 2
    # How many Gibbs sweeps an alias table is reused for before rebuild
    # (the l/n refresh of paper §3.3); used by the driver, not the sweep.
    alias_refresh_every: int = 1
    # Tile sizes for the sorted-layout kernels; tile_v=None sizes vocab
    # tiles from a VMEM budget (segment.pick_tile_vmem) — small models fit
    # in one tile, production vocabularies tile down and skip.  tile_b
    # trades skip granularity against grid size: smaller batch tiles span
    # fewer vocab tiles (more programs skipped) but launch more programs;
    # None sizes it from K and the VMEM budget (segment.pick_tile_b).
    tile_v: int | None = None
    tile_b: int | None = None
    # K-tile size for the staging axis of the fused kernels (None = full
    # K, the untiled path).  Must divide K.  With it set, table VMEM
    # residency is (tile_v, tile_k) and the budget-derived tile_v stops
    # shrinking as K grows (segment.pick_tile_vmem).
    tile_k: int | None = None
    # Sequential position-chunks per sorted sweep: each chunk is one fused
    # word-major kernel launch, with n_dk refreshed between chunks so the
    # within-document Gauss-Seidel effect of the scan layout is mostly
    # retained (1 = fully parallel Jacobi sweep).
    sorted_chunks: int = 4


class SharedStats(NamedTuple):
    """Statistics synchronized through the parameter server."""

    n_wk: Array  # (V, K) float32
    n_k: Array   # (K,)  float32


class LocalState(NamedTuple):
    """Client-local sampler state."""

    z: Array     # (D, L) int32 topic assignments (padded)
    n_dk: Array  # (D, K) float32 doc-topic counts


def init_state(cfg: LDAConfig, tokens: Array, mask: Array, key: Array
               ) -> tuple[LocalState, SharedStats]:
    """Random topic init + consistent sufficient statistics."""
    d, l = tokens.shape
    z = jax.random.randint(key, (d, l), 0, cfg.n_topics, dtype=jnp.int32)
    z = jnp.where(mask, z, 0)
    n_dk = count_dk(cfg, z, mask)
    n_wk = count_wk(cfg, tokens, z, mask)
    return LocalState(z=z, n_dk=n_dk), SharedStats(n_wk=n_wk, n_k=n_wk.sum(0))


def count_dk(cfg: LDAConfig, z: Array, mask: Array) -> Array:
    onehot = jax.nn.one_hot(z, cfg.n_topics, dtype=jnp.float32)
    return jnp.einsum("dl,dlk->dk", mask.astype(jnp.float32), onehot)


def count_wk(cfg: LDAConfig, tokens: Array, z: Array, mask: Array) -> Array:
    w = tokens.reshape(-1)
    t = z.reshape(-1)
    m = mask.reshape(-1).astype(jnp.float32)
    return jnp.zeros((cfg.vocab_size, cfg.n_topics), jnp.float32).at[w, t].add(m)


def language_model(cfg: LDAConfig, shared: SharedStats) -> Array:
    """p(w|t) rows: (V, K) = (n_wk + β) / (n_k + β̄)."""
    beta_bar = cfg.beta * cfg.vocab_size
    return (shared.n_wk + cfg.beta) / (shared.n_k[None, :] + beta_bar)


def dense_probs(cfg: LDAConfig, shared: SharedStats) -> Array:
    """The dense proposal term α_t · (n_wt+β)/(n_t+β̄), per token-type row."""
    return cfg.alpha * language_model(cfg, shared)


def build_alias(cfg: LDAConfig, shared: SharedStats) -> tuple[alias_mod.AliasTable, Array]:
    """Build per-token-type alias tables over the (stale) dense term, and
    the dense term itself: the LM-dense families' full build."""
    from repro.core import family as family_mod
    return family_mod.get("lda").build_alias(cfg, shared)


@partial(jax.jit, static_argnames=("cfg", "method", "layout"))
def sweep(
    cfg: LDAConfig,
    local: LocalState,
    shared: SharedStats,
    tables: alias_mod.AliasTable,
    stale_dense: Array,
    tokens: Array,
    mask: Array,
    key: Array,
    method: str = "mhw",
    layout: str = "scan",
    sorted_layouts: tuple[segment.SortedLayout, ...] | None = None,
) -> tuple[LocalState, Array, Array]:
    """One Gibbs sweep over a client's shard.

    ``shared`` is the client's frozen snapshot for this sweep; ``tables`` /
    ``stale_dense`` may be *staler* (alias refresh cadence).  Returns the new
    local state plus the (V, K) and (K,) deltas to push to the server.

    ``layout="sorted"`` (mhw only) runs the fused token-sorted pipeline;
    pass prebuilt per-chunk ``sorted_layouts``
    (``segment.build_chunked_layouts``) to hoist the per-shard sorts out of
    the sweep — tokens never change between sweeps, so drivers should sort
    once and reuse.
    """
    if layout == "sorted":
        if method != "mhw":
            raise ValueError("layout='sorted' requires method='mhw'")
        return _sweep_sorted(cfg, local, shared, tables, stale_dense,
                             tokens, mask, key, sorted_layouts)
    if layout != "scan":
        raise ValueError(f"unknown layout {layout!r}")
    d, l = tokens.shape
    beta_bar = cfg.beta * cfg.vocab_size
    n_wk, n_k = shared.n_wk, shared.n_k

    def position_step(carry, inputs):
        n_dk = carry
        w, z_old, m, k = inputs  # (D,), (D,), (D,), key
        docs = jnp.arange(d)

        # Remove the token's own contribution (the ^{-di} correction) from
        # the local doc counts and the gathered word rows.
        n_dk_m = n_dk.at[docs, z_old].add(-mask_f(m))
        row_wk = n_wk[w]                                    # (D, K)
        own = jax.nn.one_hot(z_old, cfg.n_topics) * mask_f(m)[:, None]
        row_wk_m = row_wk - own
        n_k_m = n_k[None, :] - own
        lm_fresh = (row_wk_m + cfg.beta) / (n_k_m + beta_bar)  # (D, K)

        if method == "exact":
            logits = jnp.log(n_dk_m + cfg.alpha) + jnp.log(lm_fresh + 1e-30)
            z_new = jax.random.categorical(k, logits, axis=-1).astype(jnp.int32)
        elif method == "mhw":
            sparse_w = n_dk_m * lm_fresh                    # exact sparse term
            prop = mhw.MixtureProposal(
                sparse_weights=sparse_w, dense_tables=tables, dense_rows=w)

            def log_p(t):
                return (jnp.log(n_dk_m[docs, t] + cfg.alpha)
                        + jnp.log(lm_fresh[docs, t] + 1e-30))

            z_new = mhw.mh_chain(k, z_old, prop, stale_dense, log_p, cfg.mh_steps)
        else:
            raise ValueError(f"unknown method {method!r}")

        z_new = jnp.where(m, z_new, z_old)
        n_dk_out = n_dk_m.at[docs, z_new].add(mask_f(m))
        return n_dk_out, z_new

    keys = jax.random.split(key, l)
    inputs = (tokens.T, local.z.T, mask.T, keys)
    n_dk_final, z_new_t = jax.lax.scan(position_step, local.n_dk, inputs)
    z_new = z_new_t.T

    # Batched delta push (paper §5.3: whole rows of the word-topic matrix).
    w_flat = tokens.reshape(-1)
    m_flat = mask.reshape(-1).astype(jnp.float32)
    delta_wk = (
        jnp.zeros((cfg.vocab_size, cfg.n_topics), jnp.float32)
        .at[w_flat, z_new.reshape(-1)].add(m_flat)
        .at[w_flat, local.z.reshape(-1)].add(-m_flat)
    )
    delta_k = delta_wk.sum(0)
    return LocalState(z=z_new, n_dk=n_dk_final), delta_wk, delta_k


def _sweep_sorted(
    cfg: LDAConfig,
    local: LocalState,
    shared: SharedStats,
    tables: alias_mod.AliasTable,
    stale_dense: Array,
    tokens: Array,
    mask: Array,
    key: Array,
    layouts: tuple[segment.SortedLayout, ...] | None,
) -> tuple[LocalState, Array, Array]:
    """Token-sorted MHW sweep: the generic tile-skipping pipeline of
    ``repro.core.family`` instantiated for LDA (prior = α·1, fresh factor =
    the LM row).  See ``family.ModelFamily.sweep_sorted`` for the chunked
    Jacobi/Gauss-Seidel relaxation semantics."""
    from repro.core import family as family_mod
    local2, deltas = family_mod.get("lda").sweep_sorted(
        cfg, local, shared, tables, stale_dense, tokens, mask, key, layouts)
    return local2, deltas["n_wk"], deltas["n_wk"].sum(0)


def chunk_bounds(l: int, n_chunks: int) -> tuple[int, ...]:
    """Position-chunk boundaries for the sorted sweep (static per shape)."""
    return segment.chunk_bounds(l, n_chunks)


def sorted_tile_v(cfg: LDAConfig) -> int:
    """The vocab tile size the sorted sweep will use for ``cfg``.

    Hoisted layouts (``segment.build_chunked_layouts``) MUST be built with
    this exact tile size — the layout's vstart/vcount are in vocab-tile
    units and are consumed by kernels tiled with it.  Delegates to the
    family registry so the geometry cannot drift from the sweep's.
    """
    from repro.core import family as family_mod
    return family_mod.get("lda").sorted_tile_v(cfg)


def build_sorted_layouts(cfg: LDAConfig, tokens: Array, mask: Array
                         ) -> tuple[segment.SortedLayout, ...]:
    """Prebuild the per-chunk sorted layouts ``sweep(layout="sorted")``
    expects — delegates to the family registry so tile/chunk geometry
    cannot drift from what the sweep derives internally.  Build once per
    shard and reuse across sweeps (the layout depends only on tokens/mask).
    """
    from repro.core import family as family_mod
    return family_mod.get("lda").build_sorted_layouts(cfg, tokens, mask)


def mask_f(m: Array) -> Array:
    return m.astype(jnp.float32)


def apply_delta(shared: SharedStats, delta_wk: Array, delta_k: Array) -> SharedStats:
    return SharedStats(n_wk=shared.n_wk + delta_wk, n_k=shared.n_k + delta_k)


# ---------------------------------------------------------------------------
# Evaluation (paper §6, "Evaluation criteria")
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "n_fold_sweeps"))
def perplexity(
    cfg: LDAConfig,
    shared: SharedStats,
    tokens: Array,
    mask: Array,
    key: Array,
    n_fold_sweeps: int = 10,
) -> Array:
    """Held-out perplexity with fold-in estimation of θ_d.

    The language model φ is frozen from the trained statistics; θ_d is
    estimated by ``n_fold_sweeps`` Gibbs sweeps on the held-out documents,
    then π = exp(-Σ log p(w_d)/Σ N_d) with
    p(w) = Σ_t θ_dt φ_wt  (paper §6 evaluation criteria).
    """
    phi = language_model(cfg, shared)  # (V, K) — columns are p(w|t)
    d, l = tokens.shape

    k_init, k_sweeps = jax.random.split(key)
    z = jax.random.randint(k_init, (d, l), 0, cfg.n_topics, dtype=jnp.int32)
    n_dk = count_dk(cfg, jnp.where(mask, z, 0), mask)

    def fold_sweep(carry, k):
        z, n_dk = carry

        def pos(carry_in, inputs):
            n_dk = carry_in
            w, z_old, m, kk = inputs
            docs = jnp.arange(d)
            n_dk_m = n_dk.at[docs, z_old].add(-mask_f(m))
            logits = jnp.log(n_dk_m + cfg.alpha) + jnp.log(phi[w] + 1e-30)
            z_new = jax.random.categorical(kk, logits, axis=-1).astype(jnp.int32)
            z_new = jnp.where(m, z_new, z_old)
            return n_dk_m.at[docs, z_new].add(mask_f(m)), z_new

        keys = jax.random.split(k, l)
        n_dk2, z_new_t = jax.lax.scan(pos, n_dk, (tokens.T, z.T, mask.T, keys))
        return (z_new_t.T, n_dk2), None

    (z, n_dk), _ = jax.lax.scan(fold_sweep, (z, n_dk), jax.random.split(k_sweeps, n_fold_sweeps))

    theta = (n_dk + cfg.alpha) / (n_dk.sum(-1, keepdims=True) + cfg.alpha * cfg.n_topics)
    # log p(w_di) = log Σ_t θ_dt φ_w t
    pw = jnp.einsum("dk,dlk->dl", theta, phi[tokens])
    logp = jnp.where(mask, jnp.log(pw + 1e-30), 0.0)
    return jnp.exp(-logp.sum() / jnp.maximum(mask.sum(), 1))


def topics_per_word(shared: SharedStats, threshold: float = 0.5) -> Array:
    """Average number of non-zero topics across token-types (paper §6)."""
    nz = (shared.n_wk > threshold).sum(-1).astype(jnp.float32)
    seen = shared.n_wk.sum(-1) > threshold
    return jnp.where(seen, nz, 0.0).sum() / jnp.maximum(seen.sum(), 1)
