"""The ``ModelFamily`` protocol + registry: one model API for LDA/PDP/HDP.

The paper's central systems claim is that one inference stack — MHW
sampling (§3), the relaxed-consistency parameter server (§5.2-5.3) and
constraint projection (§5.5) — serves every latent-variable model family
uniformly.  This module is that claim as code: each family registers

* its **shared/local statistics** as named dicts (what the parameter
  server replicates vs. what stays client-local),
* its **projection rules and aggregates** — sourced verbatim from
  ``repro.core.projection.*_RULES`` / ``*_AGGREGATES`` and split by operand
  locality into ``shared_rules`` (applied by the distributed projection)
  and ``local_rules`` (applied to client state, e.g. HDP's
  1 ≤ m_dk ≤ n_dk table-count polytope) so no rule is silently dropped,
* its **dense-proposal factorization** (paper eq. 4): the conditional
  p(e) ∝ (doc_e + prior_e) · f_e over E outcomes, exposed through
  ``language_model`` / ``dense_probs`` / ``sparse_prior`` /
  ``doc_sparse_logp`` / ``accept_ratio`` — the hooks that let the generic
  MHW machinery (``core.mhw``, ``kernels.mhw_fused``,
  ``kernels.alias_sample``) and the token-sorted tile-skipping layout
  (``data.segment``) drive any family through one code path.

``ModelFamily.sweep_sorted`` is that one code path: the chunked
Jacobi/Gauss-Seidel sorted sweep (DESIGN.md §5.1) generic over families —
LDA and HDP share the lm kernel (per-topic prior vector), PDP runs the 2K
joint-outcome kernel.  Every family's fused kernel is validated bit-exact
against its pure-jnp oracle (tests/test_sorted_sweep.py).

Drivers — ``engine.Trainer``, ``core.distributed.make_round_fn``, the
benchmarks — consume only this protocol; they never import the model
modules directly.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core import alias as alias_mod
from repro.core import hdp, lda, pdp, projection
from repro.core import mhw as mhw_mod
from repro.core import stirling
from repro.data import segment
from repro.kernels import ops

Array = jax.Array


def _rule_names(rule: projection.Rule) -> tuple[str, ...]:
    return (rule.a,) if rule.b is None else (rule.a, rule.b)


class ModelFamily:
    """Protocol base: per-family declarations + the generic machinery.

    Subclasses declare the class attributes and the abstract hooks; the
    base class owns everything that is genuinely family-independent (rule
    splitting, projection application, the chunked sorted sweep, layout
    geometry).  All methods take the family's config dataclass explicitly —
    a family singleton is stateless and shareable.
    """

    name: str = ""
    config_cls: type = object
    shared_cls: type = object
    local_cls: type = object
    shared_stats: tuple[str, ...] = ()
    local_stats: tuple[str, ...] = ()
    # Stats replicated (not summed) when merging per-shard initializations.
    replicated_stats: tuple[str, ...] = ()
    # Stats whose count mass is conserved by sweeps: recomputing them from
    # the assignments must reproduce the maintained values bit-exactly
    # (the sorted-vs-scan sufficient-statistics parity contract).
    conserved_stats: tuple[str, ...] = ()
    delta_names: tuple[str, ...] = ()
    rules: tuple[projection.Rule, ...] = ()
    aggregates: tuple[projection.Aggregate, ...] = ()

    # ---------------------------------------------------------------- rules
    @property
    def shared_rules(self) -> tuple[projection.Rule, ...]:
        return tuple(r for r in self.rules
                     if set(_rule_names(r)) <= set(self.shared_stats))

    @property
    def local_rules(self) -> tuple[projection.Rule, ...]:
        return tuple(r for r in self.rules
                     if set(_rule_names(r)) <= set(self.local_stats))

    # ---------------------------------------------------------------- state
    def init_state(self, cfg, tokens: Array, mask: Array, key: Array):
        raise NotImplementedError

    def stats_dict(self, shared) -> dict[str, Array]:
        return dict(shared._asdict())

    def shared_from_dict(self, d: dict[str, Array]):
        return self.shared_cls(**{n: d[n] for n in self.shared_stats})

    def local_dict(self, local) -> dict[str, Array]:
        return dict(local._asdict())

    def local_from_dict(self, d: dict[str, Array]):
        return self.local_cls(**{n: d[n] for n in self.local_stats})

    # ------------------------------------- dense-proposal factorization
    def n_outcomes(self, cfg) -> int:
        """E: the size of the per-token outcome space (K, or 2K for PDP)."""
        return cfg.n_topics

    def language_model(self, cfg, shared) -> Array:
        raise NotImplementedError

    def dense_probs(self, cfg, shared) -> Array:
        """(V, E) stale dense proposal term prior_e · f_e per token-type."""
        raise NotImplementedError

    def build_alias(self, cfg, shared):
        """(alias tables, stale dense matrix) over :meth:`dense_probs`."""
        raise NotImplementedError

    def sparse_prior(self, cfg, shared) -> Array:
        """(E,) per-outcome prior mass added to the document-sparse counts
        in the target: α·1 for LDA/PDP, b1·θ0 for HDP."""
        raise NotImplementedError

    # --------------------------------------- incremental alias maintenance
    @property
    def alias_delta_stats(self) -> tuple[str, ...]:
        """Shared statistics whose per-row drift stales the alias rows —
        what the delta-driven producer watches (n_wk for the LM families;
        both m_wk and s_wk for PDP, whose dense rows depend on both)."""
        return self.delta_names

    def dense_probs_rows(self, cfg, shared, rows: Array) -> Array:
        """Gathered (R, E) dense-proposal rows for token-types ``rows`` —
        must match ``dense_probs(cfg, shared)[rows]`` bit-for-bit.  The
        default materializes the full dense term; families override with
        O(R·E) gathered math so incremental rebuild cost scales with the
        changed rows, not V."""
        return self.dense_probs(cfg, shared)[rows]

    def rebuild_alias_rows(self, cfg, shared, tables: alias_mod.AliasTable,
                           stale: Array, rows: Array, valid: Array
                           ) -> tuple[alias_mod.AliasTable, Array]:
        """Incremental alias producer (paper §5.1 / §3.3): rebuild only the
        token-type ``rows`` (gather → build-from-stats kernel → scatter into
        the resident table + stale snapshot).  Rows with ``valid=False``
        keep their current entries.  Generic path: gathered dense rows +
        the compacted-rows build kernel; the LM families override with the
        fully fused gather kernel."""
        p_rows = self.dense_probs_rows(cfg, shared, rows)
        sub = ops.build_tables_rows(p_rows)
        return alias_mod.update_rows(tables, stale, rows, valid, sub, p_rows)

    def doc_sparse_logp(self, cfg, shared, doc_rows: Array, outcome: Array
                        ) -> Array:
        """log of the document-sparse target factor at ``outcome``:
        log(doc_e + prior_e).  doc_rows: (B, E); outcome: (B,) → (B,).

        SEALED accessor, not an injection point: it resolves to
        ``mhw.doc_sparse_logp`` — the same module-level function
        ``mhw.mix_chain`` (and through it every oracle and fused kernel)
        evaluates directly, because the bit-exactness contract between
        kernels and oracles forbids virtual dispatch inside the chain.
        A family customizes its target through ``sparse_prior`` and the
        fresh-factor computation of its ``sorted_chunk``/scan sweep, never
        by overriding this method (an override would not reach the chain).
        """
        return mhw_mod.doc_sparse_logp(doc_rows,
                                       self.sparse_prior(cfg, shared),
                                       outcome)

    def accept_ratio(self, log_p_cand: Array, log_p_cur: Array,
                     log_q_cur: Array, log_q_cand: Array) -> Array:
        """MH acceptance log-ratio (paper eq. 7) — identical for every
        family; SEALED like :meth:`doc_sparse_logp`, resolving to
        ``mhw.accept_log_ratio`` (which the chain calls directly)."""
        return mhw_mod.accept_log_ratio(log_p_cand, log_p_cur,
                                        log_q_cur, log_q_cand)

    # ---------------------------------------------------------------- sweeps
    def sweep(self, cfg, local, shared, tables, stale: Array, tokens: Array,
              mask: Array, key: Array, *, method: str = "mhw",
              layout: str = "scan", sorted_layouts: tuple | None = None
              ) -> tuple[Any, dict[str, Array]]:
        """One Gibbs sweep; returns (local', {delta_name: (V, K) delta})."""
        raise NotImplementedError

    def apply_delta(self, shared, deltas: dict[str, Array]):
        """Apply pushed deltas and re-derive aggregates (the C2 rule)."""
        raise NotImplementedError

    def count_stats(self, cfg, tokens: Array, mask: Array, local
                    ) -> dict[str, Array]:
        """Recompute the shard's contribution to each conserved shared
        statistic directly from the assignments (consistency oracle)."""
        raise NotImplementedError

    # ----------------------------------------------------------- projection
    def project(self, shared):
        """Algorithm 1 on the shared statistics (rules + C2 aggregates)."""
        stats = projection.project(self.stats_dict(shared),
                                   self.shared_rules, self.aggregates)
        return self.shared_from_dict(stats)

    def count_violations(self, shared) -> Array:
        return projection.count_violations(self.stats_dict(shared),
                                           self.shared_rules)

    def local_project(self, local):
        """Apply the family's client-local constraint rules (e.g. HDP's
        1 ≤ m_dk ≤ n_dk) to the local state.  Identity when none exist."""
        if not self.local_rules:
            return local
        d = projection.project(self.local_dict(local), self.local_rules)
        return self.local_from_dict(d)

    def count_local_violations(self, local) -> Array:
        return projection.count_violations(self.local_dict(local),
                                           self.local_rules)

    # ------------------------------------------------------------ lifecycle
    def post_round(self, cfg, locals_: list, shared, key: Array):
        """Per-round auxiliary resampling hook (HDP's CRT tables + θ0).
        Default: no-op."""
        return locals_, shared

    def perplexity(self, cfg, shared, tokens: Array, mask: Array, key: Array
                   ) -> Array:
        raise NotImplementedError

    def topics_per_word(self, shared) -> Array:
        raise NotImplementedError

    # ---------------------------------------------- token-sorted fast path
    def sorted_tile_v(self, cfg) -> int:
        """The vocab tile size the sorted sweep will use for ``cfg`` —
        hoisted layouts MUST be built with this exact size.  The VMEM
        budget is taken over the (tile_v, E) joint-outcome tiles —
        (tile_v, tile_k) when ``cfg.tile_k`` turns on the K-tiled
        staging, which is what keeps tile_v usable at K=1024+."""
        return cfg.tile_v or segment.pick_tile_vmem(
            cfg.vocab_size, self.n_outcomes(cfg),
            tile_k=self.sorted_tile_k(cfg))

    def sorted_tile_b(self, cfg) -> int:
        """The batch tile of the sorted layout and its kernels: the
        config's, or sized from E and the VMEM budget
        (``segment.pick_tile_b``) — hoisted layouts MUST use it too."""
        return cfg.tile_b or segment.pick_tile_b(self.n_outcomes(cfg))

    def sorted_tile_k(self, cfg) -> int | None:
        """K-tile size for the fused kernels' staging axis (None = full
        K).  Layout geometry does not depend on it, only kernel VMEM."""
        return getattr(cfg, "tile_k", None)

    def build_sorted_layouts(self, cfg, tokens: Array, mask: Array
                             ) -> tuple[segment.SortedLayout, ...]:
        """Prebuild the per-chunk sorted layouts ``sweep_sorted`` expects —
        the one sanctioned recipe, so tile/chunk geometry cannot drift from
        what the sweep derives internally.  Build once per shard and reuse
        across sweeps (the layout depends only on tokens/mask)."""
        l = tokens.shape[1]
        n_chunks = max(1, min(cfg.sorted_chunks, l))
        return segment.build_chunked_layouts(
            tokens, mask, cfg.vocab_size,
            bounds=segment.chunk_bounds(l, n_chunks),
            tile_v=self.sorted_tile_v(cfg), tile_b=self.sorted_tile_b(cfg))

    # per-family hooks for the generic chunked sweep ----------------------
    def encode(self, cfg, local) -> Array:
        """(D, L) int32 chain state per position (joint outcome for PDP)."""
        raise NotImplementedError

    def topic_of(self, cfg, e: Array) -> Array:
        """Map encoded outcomes to topic ids (identity for lm families)."""
        return e

    def sorted_chunk(self, cfg, shared, tables, stale: Array,
                     lay: segment.SortedLayout, e_sorted: Array,
                     ndk_rows: Array, key: Array, tile_v: int, tile_b: int,
                     uniforms: tuple[Array, ...] | None = None,
                     fold_in: bool = False) -> Array:
        """Run the family's fused kernel over one sorted chunk.

        ``uniforms`` (optional) overrides the chain's internal uniform
        draw with caller-supplied ``(slot, coin, u_mix, u_sparse, u_acc)``
        streams in sorted-stream order — see ``ops.mhw_sweep_sorted``.
        ``fold_in``: the documents are not counted in ``shared``.
        """
        raise NotImplementedError

    def finalize_sorted(self, cfg, local, e_grid: Array, n_dk: Array,
                        tokens: Array, mask: Array
                        ) -> tuple[Any, dict[str, Array]]:
        """Decode the final outcome grid into (local', deltas)."""
        raise NotImplementedError

    def sweep_sorted(self, cfg, local, shared, tables, stale: Array,
                     tokens: Array, mask: Array, key: Array,
                     layouts: tuple[segment.SortedLayout, ...] | None,
                     chunk_uniforms=None, fold_in: bool = False
                     ) -> tuple[Any, dict[str, Array]]:
        """Token-sorted MHW sweep: fused tile-skipping chains per shard.

        The sweep runs as ``cfg.sorted_chunks`` sequential position-chunks.
        Within a chunk every token proposes word-major against the current
        statistics minus its own contribution (the ^{-di} correction) —
        fully parallel, one fused kernel launch; between chunks ``n_dk`` is
        refreshed so each document's counts advance ``sorted_chunks`` times
        per sweep (the scan layout's Gauss-Seidel recurrence, coarsened).
        The shared statistics stay the sweep-start snapshot throughout,
        exactly as in the scan layout.

        ``chunk_uniforms`` (optional) is a callback ``(c, lay, tile_b) ->
        uniforms | None`` giving the per-chunk uniform streams for
        :meth:`sorted_chunk`; the serving engine supplies per-request
        streams here so each document's chain is independent of its
        batch-mates (DESIGN.md §14).

        ``fold_in=True`` is serving: the documents are folded into frozen
        statistics that do not count them, so the ^{-di} removal applies
        to their doc rows only, never to ``shared``.
        """
        d, l = tokens.shape
        tile_v = self.sorted_tile_v(cfg)
        n_chunks = max(1, min(cfg.sorted_chunks, l))
        bounds = segment.chunk_bounds(l, n_chunks)
        if layouts is not None and len(layouts) != n_chunks:
            raise ValueError(
                f"sorted_layouts has {len(layouts)} chunks, cfg wants "
                f"{n_chunks}; rebuild with "
                f"family.get({self.name!r}).build_sorted_layouts(cfg, ...)")

        e_grid = self.encode(cfg, local)
        n_dk = local.n_dk
        for c in range(n_chunks):
            s, e = bounds[c], bounds[c + 1]
            tok_c, mask_c = tokens[:, s:e], mask[:, s:e]
            bc = d * (e - s)
            tile_b = min(self.sorted_tile_b(cfg), bc)
            lay = layouts[c] if layouts is not None else segment.build_layout(
                tok_c, mask_c, cfg.vocab_size, tile_v=tile_v, tile_b=tile_b)

            # Geometry guard for hoisted layouts: vstart/vcount are in
            # vocab-tile units and rows are padded to tile_b — a layout
            # built with different tiles would sample silently wrong.
            if lay.hist.shape[0] * tile_v != cfg.vocab_size:
                raise ValueError(
                    f"sorted_layouts[{c}] was built with tile_v="
                    f"{cfg.vocab_size // lay.hist.shape[0]}, sweep uses "
                    f"{tile_v}; rebuild with "
                    f"family.get({self.name!r}).build_sorted_layouts")
            if (lay.rows.shape[0] % tile_b != 0
                    or lay.vstart.shape[0] != lay.rows.shape[0] // tile_b):
                raise ValueError(
                    f"sorted_layouts[{c}] batch tiling "
                    f"({lay.vstart.shape[0]} tiles over "
                    f"{lay.rows.shape[0]} draws) does not match "
                    f"tile_b={tile_b}")

            e_c = e_grid[:, s:e]
            e_flat = e_c.reshape(-1)
            e_s = segment.sort_values(lay, e_flat, fill=0)
            ndk = n_dk[lay.docs]   # raw rows; the kernel applies the ^{-di}

            uniforms = (chunk_uniforms(c, lay, tile_b)
                        if chunk_uniforms is not None else None)
            e_new_s = self.sorted_chunk(cfg, shared, tables, stale, lay,
                                        e_s, ndk, jax.random.fold_in(key, c),
                                        tile_v, tile_b, uniforms=uniforms,
                                        fold_in=fold_in)

            e_new_flat = segment.unsort_values(lay, e_new_s, e_flat)
            e_new_c = jnp.where(mask_c, e_new_flat.reshape(d, e - s), e_c)

            docs_c = jnp.arange(bc, dtype=jnp.int32) // (e - s)
            m_c = mask_c.reshape(-1).astype(jnp.float32)
            n_dk = (n_dk
                    .at[docs_c, self.topic_of(cfg, e_new_c.reshape(-1))]
                    .add(m_c)
                    .at[docs_c, self.topic_of(cfg, e_flat)].add(-m_c))
            e_grid = e_grid.at[:, s:e].set(e_new_c)

        return self.finalize_sorted(cfg, local, e_grid, n_dk, tokens, mask)


class _LMFamilyBase(ModelFamily):
    """Shared machinery for the families whose fresh factor is the LM row
    (n_wk − own + β)/(n_k − own + β̄): LDA and HDP-LDA.  They differ only
    in the per-topic prior vector and their extra shared statistics."""

    def language_model(self, cfg, shared) -> Array:
        beta_bar = cfg.beta * cfg.vocab_size
        return (shared.n_wk + cfg.beta) / (shared.n_k[None, :] + beta_bar)

    def dense_probs_rows(self, cfg, shared, rows: Array) -> Array:
        # prior · (LM row) with the division grouped first — the exact
        # operation order of dense_probs, so partial and full rebuilds of
        # the same statistics agree bit-for-bit.
        beta_bar = cfg.beta * cfg.vocab_size
        return (self.sparse_prior(cfg, shared)[None, :]
                * ((shared.n_wk[rows] + cfg.beta)
                   / (shared.n_k[None, :] + beta_bar)))

    def build_alias(self, cfg, shared):
        """The full build from raw statistics on row tiles
        (``kernels/alias_build.py`` ``alias_build_fused``): the family
        supplies only its prior vector."""
        return ops.build_tables_fused(
            shared.n_wk, shared.n_k, self.sparse_prior(cfg, shared),
            beta=cfg.beta, beta_bar=cfg.beta * cfg.vocab_size)

    def rebuild_alias_rows(self, cfg, shared, tables, stale, rows, valid):
        """LM-dense fast path: the full build's kernel over the gathered
        rows computes prior_e·(n_wk+β)/(n_k+β̄) in-register and builds the
        sub-table in one fused launch."""
        sub, p_rows = ops.build_tables_gather_fused(
            shared.n_wk, shared.n_k, self.sparse_prior(cfg, shared), rows,
            beta=cfg.beta, beta_bar=cfg.beta * cfg.vocab_size)
        return alias_mod.update_rows(tables, stale, rows, valid, sub, p_rows)

    def encode(self, cfg, local) -> Array:
        return local.z

    def sorted_chunk(self, cfg, shared, tables, stale, lay, e_sorted,
                     ndk_rows, key, tile_v, tile_b, uniforms=None,
                     fold_in=False) -> Array:
        return ops.mhw_sweep_sorted(
            tables, stale, shared.n_wk, shared.n_k,
            self.sparse_prior(cfg, shared), lay.rows, e_sorted, ndk_rows,
            lay.vstart, lay.vcount, key, mh_steps=cfg.mh_steps,
            beta=cfg.beta, beta_bar=cfg.beta * cfg.vocab_size,
            tile_v=tile_v, tile_b=tile_b, tile_k=self.sorted_tile_k(cfg),
            uniforms=uniforms, fold_in=fold_in)

    def _delta_wk(self, cfg, tokens, mask, z_old, z_new) -> Array:
        w_flat = tokens.reshape(-1)
        m_flat = mask.reshape(-1).astype(jnp.float32)
        return (jnp.zeros((cfg.vocab_size, cfg.n_topics), jnp.float32)
                .at[w_flat, z_new.reshape(-1)].add(m_flat)
                .at[w_flat, z_old.reshape(-1)].add(-m_flat))

    def count_stats(self, cfg, tokens, mask, local) -> dict[str, Array]:
        w = tokens.reshape(-1)
        t = local.z.reshape(-1)
        m = mask.reshape(-1).astype(jnp.float32)
        n_wk = (jnp.zeros((cfg.vocab_size, cfg.n_topics), jnp.float32)
                .at[w, t].add(m))
        return {"n_wk": n_wk}

    def topics_per_word(self, shared) -> Array:
        return lda.topics_per_word(
            lda.SharedStats(n_wk=shared.n_wk, n_k=shared.n_k))


class LDAFamily(_LMFamilyBase):
    name = "lda"
    config_cls = lda.LDAConfig
    shared_cls = lda.SharedStats
    local_cls = lda.LocalState
    shared_stats = ("n_wk", "n_k")
    local_stats = ("z", "n_dk")
    conserved_stats = ("n_wk",)
    delta_names = ("n_wk",)
    rules = projection.LDA_RULES
    aggregates = projection.LDA_AGGREGATES

    def init_state(self, cfg, tokens, mask, key):
        return lda.init_state(cfg, tokens, mask, key)

    def dense_probs(self, cfg, shared) -> Array:
        return lda.dense_probs(cfg, shared)

    def sparse_prior(self, cfg, shared) -> Array:
        return jnp.full((cfg.n_topics,), cfg.alpha, jnp.float32)

    def sweep(self, cfg, local, shared, tables, stale, tokens, mask, key, *,
              method="mhw", layout="scan", sorted_layouts=None):
        local2, dwk, _ = lda.sweep(cfg, local, shared, tables, stale, tokens,
                                   mask, key, method=method, layout=layout,
                                   sorted_layouts=sorted_layouts)
        return local2, {"n_wk": dwk}

    def apply_delta(self, shared, deltas):
        n_wk = shared.n_wk + deltas["n_wk"]
        return lda.SharedStats(n_wk=n_wk, n_k=n_wk.sum(0))

    def finalize_sorted(self, cfg, local, e_grid, n_dk, tokens, mask):
        dwk = self._delta_wk(cfg, tokens, mask, local.z, e_grid)
        return lda.LocalState(z=e_grid, n_dk=n_dk), {"n_wk": dwk}

    def perplexity(self, cfg, shared, tokens, mask, key) -> Array:
        return lda.perplexity(cfg, shared, tokens, mask, key)


class HDPFamily(_LMFamilyBase):
    name = "hdp"
    config_cls = hdp.HDPConfig
    shared_cls = hdp.SharedStats
    local_cls = hdp.LocalState
    shared_stats = ("n_wk", "n_k", "m_k", "theta0")
    local_stats = ("z", "n_dk", "m_dk")
    replicated_stats = ("theta0",)
    conserved_stats = ("n_wk",)
    delta_names = ("n_wk",)
    rules = projection.HDP_RULES
    aggregates = projection.HDP_AGGREGATES

    def init_state(self, cfg, tokens, mask, key):
        return hdp.init_state(cfg, tokens, mask, key)

    def dense_probs(self, cfg, shared) -> Array:
        return hdp.dense_probs(cfg, shared)

    def sparse_prior(self, cfg, shared) -> Array:
        return cfg.b1 * shared.theta0

    def sweep(self, cfg, local, shared, tables, stale, tokens, mask, key, *,
              method="mhw", layout="scan", sorted_layouts=None):
        local2, dwk, _ = hdp.sweep(cfg, local, shared, tables, stale, tokens,
                                   mask, key, method=method, layout=layout,
                                   sorted_layouts=sorted_layouts)
        return local2, {"n_wk": dwk}

    def apply_delta(self, shared, deltas):
        n_wk = shared.n_wk + deltas["n_wk"]
        return hdp.SharedStats(n_wk=n_wk, n_k=n_wk.sum(0),
                               m_k=shared.m_k, theta0=shared.theta0)

    def finalize_sorted(self, cfg, local, e_grid, n_dk, tokens, mask):
        dwk = self._delta_wk(cfg, tokens, mask, local.z, e_grid)
        return (hdp.LocalState(z=e_grid, n_dk=n_dk, m_dk=local.m_dk),
                {"n_wk": dwk})

    def post_round(self, cfg, locals_, shared, key):
        """CRT table resampling per client; m_k sums across clients (it is
        a shared aggregation parameter, paper §5.2), then θ0 | m_k."""
        m_k_total = None
        locals_ = list(locals_)
        for c in range(len(locals_)):
            locals_[c], m_k = hdp.resample_tables(
                cfg, locals_[c], shared, jax.random.fold_in(key, c))
            m_k_total = m_k if m_k_total is None else m_k_total + m_k
        theta0 = hdp.resample_theta0(cfg, m_k_total,
                                     jax.random.fold_in(key, 101))
        shared = hdp.SharedStats(n_wk=shared.n_wk, n_k=shared.n_k,
                                 m_k=m_k_total, theta0=theta0)
        return locals_, shared

    def perplexity(self, cfg, shared, tokens, mask, key) -> Array:
        return hdp.perplexity(cfg, shared, tokens, mask, key)


class PDPFamily(ModelFamily):
    name = "pdp"
    config_cls = pdp.PDPConfig
    shared_cls = pdp.SharedStats
    local_cls = pdp.LocalState
    shared_stats = ("m_wk", "s_wk", "m_k", "s_k")
    local_stats = ("z", "r", "n_dk")
    # s_wk is NOT count-conserved: init_state's polytope repair (and the
    # projection) adjusts table counts without rewriting per-token r
    # indicators — s_wk is governed by the constraint rules instead.
    conserved_stats = ("m_wk",)
    delta_names = ("m_wk", "s_wk")
    rules = projection.PDP_RULES
    aggregates = projection.PDP_AGGREGATES

    def init_state(self, cfg, tokens, mask, key):
        return pdp.init_state(cfg, tokens, mask, key)

    def n_outcomes(self, cfg) -> int:
        return 2 * cfg.n_topics

    def language_model(self, cfg, shared) -> Array:
        return pdp.language_model(cfg, shared)

    def dense_probs(self, cfg, shared) -> Array:
        return pdp.dense_probs(cfg, shared)

    def build_alias(self, cfg, shared):
        return pdp.build_alias(cfg, shared)

    def sparse_prior(self, cfg, shared) -> Array:
        return jnp.full((2 * cfg.n_topics,), cfg.alpha, jnp.float32)

    def dense_probs_rows(self, cfg, shared, rows: Array) -> Array:
        # The (m, s)-dependent joint rows: both table and customer counts
        # of the gathered token-types feed the 2K outcome columns — which
        # is why alias_delta_stats tracks m_wk AND s_wk drift for PDP.
        table = stirling.as_jax(cfg.stirling_n_max, cfg.discount)
        log_f0, log_f1 = pdp._log_factors(
            cfg, table, shared.m_wk[rows], shared.s_wk[rows],
            shared.m_k[None, :], shared.s_k[None, :])
        return cfg.alpha * jnp.concatenate(
            [jnp.exp(log_f0), jnp.exp(log_f1)], axis=-1)

    def sweep(self, cfg, local, shared, tables, stale, tokens, mask, key, *,
              method="mhw", layout="scan", sorted_layouts=None):
        local2, dm, ds = pdp.sweep(cfg, local, shared, tables, stale, tokens,
                                   mask, key, method=method, layout=layout,
                                   sorted_layouts=sorted_layouts)
        return local2, {"m_wk": dm, "s_wk": ds}

    def apply_delta(self, shared, deltas):
        m_wk = shared.m_wk + deltas["m_wk"]
        s_wk = shared.s_wk + deltas["s_wk"]
        return pdp.SharedStats(m_wk=m_wk, s_wk=s_wk,
                               m_k=m_wk.sum(0), s_k=s_wk.sum(0))

    def count_stats(self, cfg, tokens, mask, local) -> dict[str, Array]:
        m_wk = pdp._count(cfg, tokens, local.z, mask,
                          jnp.ones_like(local.r))
        s_wk = pdp._count(cfg, tokens, local.z, mask, local.r)
        return {"m_wk": m_wk, "s_wk": s_wk}

    def topics_per_word(self, shared) -> Array:
        return lda.topics_per_word(
            lda.SharedStats(n_wk=shared.m_wk, n_k=shared.m_k))

    def encode(self, cfg, local) -> Array:
        return local.z + cfg.n_topics * local.r

    def topic_of(self, cfg, e: Array) -> Array:
        return e % cfg.n_topics

    def sorted_chunk(self, cfg, shared, tables, stale, lay, e_sorted,
                     ndk_rows, key, tile_v, tile_b, uniforms=None,
                     fold_in=False) -> Array:
        stirl = stirling.as_jax(cfg.stirling_n_max, cfg.discount)
        return ops.pdp_sweep_sorted(
            tables, stale, shared.m_wk, shared.s_wk, shared.m_k, shared.s_k,
            stirl, self.sparse_prior(cfg, shared), lay.rows, e_sorted,
            ndk_rows, lay.vstart,
            lay.vcount, key, mh_steps=cfg.mh_steps,
            concentration=cfg.concentration, discount=cfg.discount,
            gamma=cfg.gamma, gamma_bar=cfg.gamma * cfg.vocab_size,
            tile_v=tile_v, tile_b=tile_b, tile_k=self.sorted_tile_k(cfg),
            uniforms=uniforms, fold_in=fold_in)

    def finalize_sorted(self, cfg, local, e_grid, n_dk, tokens, mask):
        z_new = e_grid % cfg.n_topics
        r_new = e_grid // cfg.n_topics
        dm, ds = pdp.deltas_from(cfg, tokens, mask, local.z, local.r,
                                 z_new, r_new)
        return (pdp.LocalState(z=z_new, r=r_new, n_dk=n_dk),
                {"m_wk": dm, "s_wk": ds})

    def perplexity(self, cfg, shared, tokens, mask, key) -> Array:
        return pdp.perplexity(cfg, shared, tokens, mask, key)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

FAMILIES: dict[str, ModelFamily] = {}


def register(family: ModelFamily) -> ModelFamily:
    """Register a family singleton under its name (last wins).

    Rejects a family whose shared/local rule split does not cover its full
    rule set — a rule mixing shared and local operands would otherwise be
    silently dropped from BOTH projection paths (the exact bug class the
    registry exists to prevent).
    """
    dropped = set(family.rules) - set(family.shared_rules) \
        - set(family.local_rules)
    if dropped:
        raise ValueError(
            f"family {family.name!r}: rules {sorted(r.a for r in dropped)} "
            "span shared and local statistics — neither projection path "
            "would apply them; split the rule or fix the stat declarations")
    FAMILIES[family.name] = family
    return family


register(LDAFamily())
register(PDPFamily())
register(HDPFamily())


def get(name: str) -> ModelFamily:
    try:
        return FAMILIES[name]
    except KeyError:
        raise KeyError(f"unknown model family {name!r}; registered: "
                       f"{sorted(FAMILIES)}") from None


def family_of(cfg: Any) -> ModelFamily:
    """Resolve the registered family for a model config instance."""
    for fam in FAMILIES.values():
        if isinstance(cfg, fam.config_cls):
            return fam
    raise TypeError(f"no registered ModelFamily for config {type(cfg)!r}")


def names() -> Sequence[str]:
    return sorted(FAMILIES)
