"""Pallas TPU kernels: fused MHW sweep steps over the token-sorted layout.

One program = one (batch-tile, resident-vocab-tile) pair of the sorted
stream (``repro.data.segment``).  With the (TILE_V, E) table tile — alias
``prob``/``alias``/``mass`` rows, the stale dense matrix and the *fresh*
shared-statistic rows — resident in VMEM, the whole per-token MH chain of
paper §3 retires in a single residency:

  1. the fresh per-outcome factor f is computed from the resident tile —
     each word-topic row is touched once per (batch-tile, vocab-tile) pair
     instead of once per scan position;
  2. the sparse+dense mixture proposal (paper eq. 4): document-sparse term
     via an inverse-CDF draw over the E lanes, corpus-dense term via the
     alias-table slot/coin draw;
  3. the stale-q point gathers and the MH acceptance coin (paper eq. 7).

Unfused, steps 2–3 are five HBM round trips per MH step (proposal draw,
two q gathers, two p gathers) plus a fresh statistics gather per token;
fused they are VMEM reads.  The grid does not walk the dense (batch tile,
vocab tile) product, almost all of whose pairs hold no draw at a large
vocabulary: it walks a scalar-prefetched work list of the pairs inside
each batch tile's vocab window (:func:`work_list`), with the K-tile
staging axis minor.  Its non-live entries (one per all-padding batch tile,
and the fill past the end) keep the previous step's table block — same
vocab tile, last K tile — so the pipeline issues no copy for them, and
``pl.when`` skips their body.

Every data-dependent read is written in a form Mosaic lowers: the per-token
table rows are one single-pass bfloat16 MXU contraction of a one-hot
(TILE_B, TILE_V) matrix with exact bfloat16 parts of the table blocks
(:func:`_stage_rows`), and every lane read inside the chain is a one-hot
select-and-sum (``mhw._gather_k``).  Both are exact — one non-zero term per
output — so interpreted (CPU) and lowered (TPU) kernels read the same
values the oracles gather.

Two kernels instantiate the ``ModelFamily`` dense-proposal factorization
(p(e) ∝ (doc_e + prior_e)·f_e, see ``repro.core.mhw``):

* :func:`mhw_sweep_fused` — lm families (LDA, HDP-LDA): E = K outcomes,
  f = (n_wk − own + β)/(n_k − own + β̄), per-topic ``prior`` vector
  (α·1 for LDA, b1·θ0 for HDP).  Oracle: ``mhw.sorted_chain``.
* :func:`pdp_sweep_fused` — PDP: E = 2K joint (topic, table-indicator)
  outcomes, f = the generalized-Stirling-ratio factors of paper eqs. (5)-(6).
  The factors of every vocabulary row are evaluated once per call by XLA
  (the Stirling table lookups are 2-D gathers Mosaic cannot lower) and
  stream through VMEM like the alias tiles; the kernel swaps in each
  token's own-topic column, which the ^{-di} removal changes.  Oracle:
  ``pdp.sorted_chain_pdp``.

Both kernels delegate the chain itself to ``mhw.mix_chain`` — the same
function their oracles call — so kernel and oracle are bit-identical given
the same uniforms (tests/test_sorted_sweep.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shared with the oracles: the bit-exactness contract requires kernels and
# oracles to run the identical chain math on identical factor values.
from repro.core.mhw import _EPS, mix_chain
from repro.core.pdp import fresh_log_factors, own_contrib, own_log_factors
from repro.kernels import backend

# Live (tile_b, E) float32 arrays the compiler keeps in VMEM for one chain
# body: four staged tables, the double-buffered ndk block and the chain's
# temporaries.  Sizes ``vmem_limit_bytes``; ``segment.pick_tile_b`` keeps
# tile_b·E small enough that this stays well inside v5e's 128 MiB.
_LIVE_ROWS = 24
_VMEM_FLOOR = 32 * 2 ** 20
_VMEM_CEIL = 100 * 2 ** 20


def _vmem_limit(tile_b: int, e: int, tile_v: int, tile_k: int) -> int:
    # + the staging contraction: its bfloat16 one-hot and parts block and
    # its float32 product.
    parts = stage_parts(e)
    need = (4 * (_LIVE_ROWS * tile_b * e + 16 * tile_v * tile_k)
            + 2 * tile_v * (tile_b + parts * tile_k)
            + 4 * parts * tile_b * tile_k)
    return int(min(max(2 * need, _VMEM_FLOOR), _VMEM_CEIL))


# What a live step stages: three float32 tables (n_wk or PDP's log factors,
# stale, prob), then the int32 alias indices.
_STAGED_DTYPES = (jnp.float32,) * 3 + (jnp.int32,)


def _n_parts(dtype, e: int) -> int:
    """Exact bfloat16 parts a table block of ``dtype`` splits into: three
    for a float, and for integer indices below ``e`` one base-256 digit
    per 8 bits of e − 1 (two up to e = 65,536)."""
    if jnp.issubdtype(dtype, jnp.floating):
        return 3
    assert e <= 2 ** 24, f"E={e}: indices past 2^24 are not exact in f32"
    return max(1, -(-(e - 1).bit_length() // 8))


def _check_staged(*tables):
    assert tuple(t.dtype for t in tables) == _STAGED_DTYPES, \
        [t.dtype for t in tables]


def stage_parts(e: int) -> int:
    """Single-pass (tile_v, tile_k) column blocks one live step contracts
    its one-hot with, over E outcomes: 11 at E = 1024 or 2048."""
    return sum(_n_parts(dt, e) for dt in _STAGED_DTYPES)


def n_pairs(nb: int, nv: int) -> int:
    """Static length of :func:`work_list` for nb batch tiles over nv vocab
    tiles.  Rows are sorted, so consecutive batch tiles' windows share at
    most their boundary vocab tile: Σ max(vcount, 1) ≤ nb + nv − 1."""
    return nb + nv


def work_list(vstart: jax.Array, vcount: jax.Array, nv: int):
    """The fused kernels' grid: one entry per (batch tile, vocab tile) pair
    inside a batch tile's window, batch-tile major, then non-live fill up
    to :func:`n_pairs`.

    Returns four (n_pairs,) int32 arrays: ``pair_b`` (batch tile),
    ``pair_t`` (vocab tile), ``live`` (1 where the pair holds draws) and
    ``first`` (1 on a batch tile's first entry, where its output block is
    initialised).  A batch tile with ``vcount == 0`` (all padding) gets
    one non-live entry, so its output still starts from the chain init.
    A non-live entry carries the vocab tile of the entry before it, so
    its table blocks do not change and the pipeline copies nothing.
    Requires windows of a sorted stream (``segment.build_layout``).
    """
    nb = vstart.shape[0]
    p = jnp.arange(n_pairs(nb, nv), dtype=jnp.int32)
    cnt = jnp.maximum(vcount, 1)
    ends = jnp.cumsum(cnt)
    bi = jnp.minimum(jnp.searchsorted(ends, p, side="right"),
                     nb - 1).astype(jnp.int32)
    j = p - (ends[bi] - cnt[bi])                  # entry within its tile
    in_list = p < ends[-1]
    live = in_list & (j < vcount[bi])
    first = in_list & (j == 0)
    t = jnp.clip(vstart[bi] + jnp.minimum(j, vcount[bi] - 1), 0, nv - 1)
    t = t[jax.lax.cummax(jnp.where(live, p, 0))]  # non-live: carry forward
    return (bi, t.astype(jnp.int32), live.astype(jnp.int32),
            first.astype(jnp.int32))


def _index_maps(nk: int):
    """BlockSpec index maps shared by both sorted-layout kernels over the
    (work-list entry, K tile) grid: per-batch-tile blocks, whole-array
    residents, and the (vocab tile, K tile) table blocks, which a non-live
    entry holds at the previous step's block."""
    def bmap(p, ki, pb, pt, live, first):
        return (0, pb[p])

    def bmap2(p, ki, pb, pt, live, first):
        return (pb[p], 0)

    def fullmap(p, ki, pb, pt, live, first):
        return (0, 0)

    def vmapk(p, ki, pb, pt, live, first):
        # (vocab-tile, k-tile) table block — the (tile_v, tile_k) residency
        # that replaces the (tile_v, K) one.
        return (pt[p], jnp.where(live[p] > 0, ki, nk - 1))

    def vmap_mass(p, ki, pb, pt, live, first):
        # mass viewed as (nv, 1, tile_v): one lane-major row per vocab tile.
        return (pt[p], 0, 0)

    return bmap, bmap2, fullmap, vmapk, vmap_mass


def _vec(ref):
    """A (1, TILE_B) per-token block as a (TILE_B,) vector.  Per-token
    operands travel as (1, B) rows: XLA tiles a rank-1 HBM array by 1024
    elements, which no (TILE_B,) block shorter than that can match."""
    return jax.lax.index_in_dim(ref[...], 0, 0, keepdims=False)


def _tile_onehot(rows, row_lo, tile_v: int):
    """(TILE_B,) sorted rows → (in_tile, one-hot (TILE_B, TILE_V) bool)."""
    local = rows - row_lo
    in_tile = (local >= 0) & (local < tile_v)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows.shape[0], tile_v), 1)
    return in_tile, lane == local[:, None]


def _bf16_parts(block, e: int):
    """A table block as :func:`_n_parts` float32 values, each exact in
    bfloat16.  A float's hi and mid parts are the top 8 significant bits
    (the bits a bfloat16 keeps, cut by a mask, not rounded) of what the
    parts before them leave; lo, the rest, has at most 8.  An integer
    block's parts are its base-256 digits, lowest first."""
    if jnp.issubdtype(block.dtype, jnp.floating):
        x, parts = block, []
        for _ in range(2):
            bits = jax.lax.bitcast_convert_type(x, jnp.int32) & -65536
            parts.append(jax.lax.bitcast_convert_type(bits, jnp.float32))
            x = x - parts[-1]
        return parts + [x]
    return [((block >> (8 * i)) & 255).astype(jnp.float32)
            for i in range(_n_parts(block.dtype, e))]


def _stage_rows(onehot, blocks, e: int):
    """Rows of resident (TILE_V, tile_k) table blocks picked by a one-hot
    (TILE_B, TILE_V) bool matrix, in one single-pass MXU contraction.

    The bfloat16 one-hot meets every block's exact bfloat16 parts
    (:func:`_bf16_parts`, integer blocks holding indices below ``e``) side
    by side, accumulating in float32.  Each output has one non-zero
    product, so every part comes back exactly, and ``(hi + mid) + lo`` or
    the digits' Σ 256^i·d_i rebuilds the block's rows bit for bit: zero and
    every finite float32 of magnitude ≥ 2^-100 (below that lo leaves
    bfloat16's normal range), and every index below ``e``.  Returns the
    rows of each block, (TILE_B, tile_k) in its dtype."""
    parts = [_bf16_parts(blk, e) for blk in blocks]
    rhs = jnp.concatenate([p.astype(jnp.bfloat16) for ps in parts
                           for p in ps], axis=1)
    out = jnp.dot(onehot.astype(jnp.bfloat16), rhs,
                  preferred_element_type=jnp.float32)
    tile_k = blocks[0].shape[1]
    rows, col = [], 0
    for blk, ps in zip(blocks, parts):
        cols = [out[:, (col + i) * tile_k:(col + i + 1) * tile_k]
                for i in range(len(ps))]
        col += len(ps)
        if jnp.issubdtype(blk.dtype, jnp.floating):
            rows.append((cols[0] + cols[1]) + cols[2])
        else:
            acc = cols[-1]
            for c in cols[-2::-1]:
                acc = acc * 256.0 + c
            rows.append(acc.astype(blk.dtype))
    return rows


def _dense_mass(onehot, mass_row):
    """Per-token stale dense mass: one-hot select-and-sum over the tile's
    (1, TILE_V) mass row."""
    return jnp.sum(jnp.where(onehot, mass_row, 0.0), axis=-1)


def _mhw_fused_kernel(pb_ref, pt_ref, live_ref, first_ref, rows_ref, z_ref,
                      ndk_ref, slot_ref, coin_ref, umix_ref, usp_ref,
                      uacc_ref, prob_ref, alias_ref, mass_ref, stale_ref,
                      nwk_ref, nk_ref, prior_ref, out_ref, nwk_s, stale_s,
                      prob_s, alias_s, *, tile_v: int, tile_k: int,
                      n_ktiles: int, beta: float, beta_bar: float,
                      fold_in: bool):
    p = pl.program_id(0)
    ki = pl.program_id(1)
    live = live_ref[p] > 0
    in_tile, onehot = _tile_onehot(_vec(rows_ref), pt_ref[p] * tile_v, tile_v)

    @pl.when((first_ref[p] > 0) & (ki == 0))
    def _init():
        out_ref[...] = z_ref[...]

    @pl.when(live)
    def _stage():
        # Stage this (tile_v, tile_k) table block's per-token rows into the
        # full-K VMEM scratch.  Pure data movement: column tiles of the
        # same gathered rows concatenate to exactly the rows the untiled
        # kernel gathers, so tiling cannot perturb the chain.
        ksl = pl.ds(ki * tile_k, tile_k)
        staged = _stage_rows(onehot, (nwk_ref[...], stale_ref[...],
                                      prob_ref[...], alias_ref[...]),
                             alias_s.shape[-1])
        for s_ref, rows in zip((nwk_s, stale_s, prob_s, alias_s), staged):
            s_ref[:, ksl] = rows

    @pl.when(live & (ki == n_ktiles - 1))
    def _body():
        z0 = _vec(z_ref)                           # (TILE_B,) chain init
        k_topics = ndk_ref.shape[-1]

        # ^{-di} correction in-kernel: remove the token's own contribution
        # from its doc row, its n_wk row and the topic totals (as in the
        # scan path) — callers pass *raw* gathered n_dk rows.  A folded-in
        # document is not counted in the frozen n_wk / n_k, so there only
        # its doc row loses the token.
        karange = jax.lax.broadcasted_iota(jnp.int32, (1, k_topics), 1)
        # (Mosaic cannot broadcast a 1-D bool into a column: widen first.)
        own = ((karange == z0[:, None]).astype(jnp.float32)
               * in_tile.astype(jnp.float32)[:, None])
        ndk = ndk_ref[...] - own                   # (TILE_B, K)
        rows_wk = nwk_s[...]                       # (TILE_B, K) staged
        own_wk = 0.0 if fold_in else own
        lm = (rows_wk - own_wk + beta) / (nk_ref[...] - own_wk + beta_bar)

        z = mix_chain(
            z0, doc=ndk, prior=prior_ref[...], logf=jnp.log(lm + _EPS),
            sparse_w=ndk * lm, stale_rows=stale_s[...],
            prob_rows=prob_s[...], alias_rows=alias_s[...],
            dense_mass=_dense_mass(onehot, mass_ref[...]),
            slot=slot_ref[...], coin=coin_ref[...], u_mix=umix_ref[...],
            u_sparse=usp_ref[...], u_acc=uacc_ref[...])

        out_ref[...] = jnp.where(in_tile, z.astype(jnp.int32),
                                 _vec(out_ref))[None, :]


def _check_tiles(v, e, b, tile_v, tile_b, tile_k):
    tile_v = min(tile_v, v)
    tile_b = min(tile_b, b)
    tile_k = e if tile_k is None else min(tile_k, e)
    assert v % tile_v == 0 and b % tile_b == 0, (v, tile_v, b, tile_b)
    assert e % tile_k == 0, f"E={e} must be a multiple of tile_k={tile_k}"
    return tile_v, tile_b, tile_k


@functools.partial(jax.jit,
                   static_argnames=("tile_v", "tile_b", "tile_k", "n_steps",
                                    "beta", "beta_bar", "fold_in",
                                    "interpret"))
def mhw_sweep_fused(prob: jax.Array, alias: jax.Array, mass: jax.Array,
                    stale: jax.Array, n_wk: jax.Array, n_k: jax.Array,
                    prior: jax.Array, rows: jax.Array, z0: jax.Array,
                    ndk: jax.Array, slot: jax.Array, coin: jax.Array,
                    u_mix: jax.Array, u_sparse: jax.Array, u_acc: jax.Array,
                    vstart: jax.Array, vcount: jax.Array, *,
                    tile_v: int, tile_b: int, tile_k: int | None = None,
                    n_steps: int = 2, beta: float = 0.01,
                    beta_bar: float | None = None, fold_in: bool = False,
                    interpret: bool | None = None) -> jax.Array:
    """Fused sorted-layout MHW chain for one sweep — lm families (LDA/HDP).

    prob/alias/stale/n_wk: (V, K); mass: (V,); n_k: (K,); prior: (K,)
    per-topic prior mass (α·1 for LDA, b1·θ0 for HDP).
    rows/z0: (B,) sorted token-types (≥V ⇒ padding, left at z0) and chain
    init; ndk: (B, K) *raw* gathered doc-topic rows per sorted draw (the
    ^{-di} removal happens in-kernel).  slot/coin/u_mix/u_sparse/u_acc:
    (n_steps, B) per-MH-step uniforms (slot is int32 in [0, K)).
    vstart/vcount: (B/tile_b,) vocab-tile windows from
    ``segment.build_layout``; the grid walks their :func:`work_list`.
    Returns (B,) int32 final states.

    ``tile_k`` (None ⇒ K) adds the K-tile *staging* axis: the (V, K)
    tables stream through VMEM in (tile_v, tile_k) blocks whose per-token
    rows accumulate into full-K scratch; the chain itself — which needs
    the full K row per token (prefix-sum proposal CDF, arbitrary-index
    reads) — runs once per (batch, vocab) tile on the staged scratch,
    bit-identical to the untiled kernel.  Table VMEM residency drops from
    (tile_v, K) to (tile_v, tile_k); the (tile_b, K) per-token state is
    the floor, so tile_b shrinks as K grows (``segment.pick_tile_b``).
    ``fold_in`` marks documents the statistics do not count (serving):
    the ^{-di} removal then touches only their doc rows.
    ``interpret`` None ⇒ the platform decides (``kernels.backend``).
    """
    v, k = prob.shape
    b = rows.shape[0]
    tile_v, tile_b, tile_k = _check_tiles(v, k, b, tile_v, tile_b, tile_k)
    _check_staged(n_wk, stale, prob, alias)
    nb, nv, nk = b // tile_b, v // tile_v, k // tile_k
    assert vstart.shape == (nb,) and vcount.shape == (nb,)
    if beta_bar is None:
        beta_bar = beta * v

    kernel = functools.partial(_mhw_fused_kernel, tile_v=tile_v,
                               tile_k=tile_k, n_ktiles=nk,
                               beta=beta, beta_bar=beta_bar, fold_in=fold_in)
    bmap, bmap2, fullmap, vmapk, vmap_mass = _index_maps(nk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_pairs(nb, nv), nk),
        in_specs=[
            pl.BlockSpec((1, tile_b), bmap),           # rows
            pl.BlockSpec((1, tile_b), bmap),           # z0
            pl.BlockSpec((tile_b, k), bmap2),        # ndk
            pl.BlockSpec((n_steps, tile_b), bmap),   # slot
            pl.BlockSpec((n_steps, tile_b), bmap),   # coin
            pl.BlockSpec((n_steps, tile_b), bmap),   # u_mix
            pl.BlockSpec((n_steps, tile_b), bmap),   # u_sparse
            pl.BlockSpec((n_steps, tile_b), bmap),   # u_acc
            pl.BlockSpec((tile_v, tile_k), vmapk),   # prob
            pl.BlockSpec((tile_v, tile_k), vmapk),   # alias
            pl.BlockSpec((pl.Squeezed(), 1, tile_v), vmap_mass),  # mass
            pl.BlockSpec((tile_v, tile_k), vmapk),   # stale
            pl.BlockSpec((tile_v, tile_k), vmapk),   # n_wk
            pl.BlockSpec((1, k), fullmap),           # n_k
            pl.BlockSpec((1, k), fullmap),           # prior
        ],
        out_specs=pl.BlockSpec((1, tile_b), bmap),
        scratch_shapes=[
            pltpu.VMEM((tile_b, k), jnp.float32),    # staged n_wk rows
            pltpu.VMEM((tile_b, k), jnp.float32),    # staged stale rows
            pltpu.VMEM((tile_b, k), jnp.float32),    # staged prob rows
            pltpu.VMEM((tile_b, k), jnp.int32),      # staged alias rows
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, b), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(tile_b, k, tile_v, tile_k)),
        name="mhw_sweep_fused",
        interpret=backend.interpret("mhw_sweep_fused", requested=interpret),
    )(*work_list(vstart, vcount, nv), rows.reshape(1, b), z0.reshape(1, b),
      ndk, slot, coin,
      u_mix, u_sparse, u_acc, prob, alias, mass.reshape(nv, 1, tile_v),
      stale, n_wk, n_k.reshape(1, -1), prior.reshape(1, -1))[0]


# ---------------------------------------------------------------------------
# PDP: joint (topic, table-indicator) outcomes e = t + K·r  (paper §2.2)
# ---------------------------------------------------------------------------


def _pdp_fused_kernel(pb_ref, pt_ref, live_ref, first_ref, rows_ref, e_ref,
                      ownf0_ref, ownf1_ref, ndk_ref, slot_ref, coin_ref,
                      umix_ref, usp_ref, uacc_ref, prob_ref, alias_ref,
                      mass_ref, stale_ref, logf_ref, prior_ref, out_ref,
                      logf_s, stale_s, prob_s, alias_s, *, tile_v: int,
                      tile_k: int, n_etiles: int, fold_in: bool):
    p = pl.program_id(0)
    ei = pl.program_id(1)          # e-tile over the 2K joint outcomes
    live = live_ref[p] > 0
    in_tile, onehot = _tile_onehot(_vec(rows_ref), pt_ref[p] * tile_v, tile_v)

    @pl.when((first_ref[p] > 0) & (ei == 0))
    def _init():
        out_ref[...] = e_ref[...]

    @pl.when(live)
    def _stage():
        # The (V, 2K) joint-outcome tables stream one e-tile per step.
        esl = pl.ds(ei * tile_k, tile_k)
        staged = _stage_rows(onehot, (logf_ref[...], stale_ref[...],
                                      prob_ref[...], alias_ref[...]),
                             alias_s.shape[-1])
        for s_ref, rows in zip((logf_s, stale_s, prob_s, alias_s), staged):
            s_ref[:, esl] = rows

    @pl.when(live & (ei == n_etiles - 1))
    def _body():
        e0 = _vec(e_ref)                           # (TILE_B,) joint outcome
        k_topics = ndk_ref.shape[-1]

        # ^{-di}: the staged rows hold every topic's factors without the
        # token's own removal; its own topic's pair of columns (r = 0, 1)
        # takes the precomputed corrected factors instead — except for a
        # folded-in document, which the statistics do not count.
        own_t, _ = own_contrib(k_topics, e0, in_tile)
        log_f = logf_s[...]                                   # (TILE_B, 2K)
        if not fold_in:
            own_e = jnp.concatenate([own_t, own_t], axis=-1) > 0
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * k_topics), 1)
            own_f = jnp.where(lane < k_topics, _vec(ownf0_ref)[:, None],
                              _vec(ownf1_ref)[:, None])
            log_f = jnp.where(own_e, own_f, log_f)
        ndk_m = ndk_ref[...] - own_t
        ndk_ext = jnp.concatenate([ndk_m, ndk_m], axis=-1)

        e = mix_chain(
            e0, doc=ndk_ext, prior=prior_ref[...], logf=log_f,
            sparse_w=ndk_ext * jnp.exp(log_f),
            stale_rows=stale_s[...], prob_rows=prob_s[...],
            alias_rows=alias_s[...],
            dense_mass=_dense_mass(onehot, mass_ref[...]),
            slot=slot_ref[...], coin=coin_ref[...], u_mix=umix_ref[...],
            u_sparse=usp_ref[...], u_acc=uacc_ref[...])

        out_ref[...] = jnp.where(in_tile, e.astype(jnp.int32),
                                 _vec(out_ref))[None, :]


@functools.partial(jax.jit,
                   static_argnames=("tile_v", "tile_b", "tile_k", "n_steps",
                                    "b_conc", "a_disc", "gamma", "gamma_bar",
                                    "fold_in", "interpret"))
def pdp_sweep_fused(prob: jax.Array, alias: jax.Array, mass: jax.Array,
                    stale: jax.Array, m_wk: jax.Array, s_wk: jax.Array,
                    m_k: jax.Array, s_k: jax.Array, stirl: jax.Array,
                    prior: jax.Array, rows: jax.Array, e0: jax.Array,
                    ndk: jax.Array, slot: jax.Array, coin: jax.Array,
                    u_mix: jax.Array, u_sparse: jax.Array, u_acc: jax.Array,
                    vstart: jax.Array, vcount: jax.Array, *,
                    tile_v: int, tile_b: int, tile_k: int | None = None,
                    n_steps: int = 2, b_conc: float = 10.0,
                    a_disc: float = 0.1, gamma: float = 0.5,
                    gamma_bar: float | None = None, fold_in: bool = False,
                    interpret: bool | None = None) -> jax.Array:
    """Fused sorted-layout MHW chain for one PDP sweep (2K outcomes).

    prob/alias/stale: (V, 2K) joint-outcome tables; mass: (V,);
    m_wk/s_wk: (V, K) customer/table counts; m_k/s_k: (K,); stirl: the
    log-Stirling table; prior: (2K,) = α·1.  rows/e0: (B,) sorted
    token-types and joint-outcome chain init; ndk: (B, K) raw gathered doc
    rows; uniforms (n_steps, B), slot int32 in [0, 2K).  Returns (B,)
    int32 final joint outcomes.

    The fresh Stirling-ratio factors of every row (no ^{-di} removal) and
    each token's corrected own-topic pair are evaluated here by XLA
    (``pdp.fresh_log_factors`` / ``pdp.own_log_factors`` — the oracle's
    functions on the same values); the kernel stages the (V, 2K) factor
    table with the alias tiles.  ``tile_k`` (None ⇒ 2K) sets the e-tile
    width of that staging axis; results are bit-exact for every tile_k.
    ``fold_in`` as in :func:`mhw_sweep_fused`: no own-topic correction of
    the statistics' factors.
    """
    v, e_out = prob.shape
    k = m_wk.shape[1]
    assert e_out == 2 * k
    bsz = rows.shape[0]
    tile_v, tile_b, tile_k = _check_tiles(v, e_out, bsz, tile_v, tile_b,
                                          tile_k)
    nb, nv, ne = bsz // tile_b, v // tile_v, e_out // tile_k
    assert vstart.shape == (nb,) and vcount.shape == (nb,)
    if gamma_bar is None:
        gamma_bar = gamma * v
    hyper = dict(b=b_conc, a=a_disc, gamma=gamma, gamma_bar=gamma_bar)
    log_f = fresh_log_factors(stirl, m_wk, s_wk, m_k, s_k, **hyper)
    own_f0, own_f1 = own_log_factors(stirl, m_wk, s_wk, m_k, s_k, rows, e0,
                                     **hyper)
    _check_staged(log_f, stale, prob, alias)

    kernel = functools.partial(_pdp_fused_kernel, tile_v=tile_v,
                               tile_k=tile_k, n_etiles=ne, fold_in=fold_in)
    bmap, bmap2, fullmap, vmapk, vmap_mass = _index_maps(ne)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_pairs(nb, nv), ne),
        in_specs=[
            pl.BlockSpec((1, tile_b), bmap),            # rows
            pl.BlockSpec((1, tile_b), bmap),            # e0
            pl.BlockSpec((1, tile_b), bmap),            # own-topic log f (r=0)
            pl.BlockSpec((1, tile_b), bmap),            # own-topic log f (r=1)
            pl.BlockSpec((tile_b, k), bmap2),         # ndk
            pl.BlockSpec((n_steps, tile_b), bmap),    # slot
            pl.BlockSpec((n_steps, tile_b), bmap),    # coin
            pl.BlockSpec((n_steps, tile_b), bmap),    # u_mix
            pl.BlockSpec((n_steps, tile_b), bmap),    # u_sparse
            pl.BlockSpec((n_steps, tile_b), bmap),    # u_acc
            pl.BlockSpec((tile_v, tile_k), vmapk),    # prob (e-tiles)
            pl.BlockSpec((tile_v, tile_k), vmapk),    # alias (e-tiles)
            pl.BlockSpec((pl.Squeezed(), 1, tile_v), vmap_mass),  # mass
            pl.BlockSpec((tile_v, tile_k), vmapk),    # stale (e-tiles)
            pl.BlockSpec((tile_v, tile_k), vmapk),    # fresh log f (e-tiles)
            pl.BlockSpec((1, e_out), fullmap),        # prior
        ],
        out_specs=pl.BlockSpec((1, tile_b), bmap),
        scratch_shapes=[
            pltpu.VMEM((tile_b, e_out), jnp.float32),  # staged log f
            pltpu.VMEM((tile_b, e_out), jnp.float32),  # staged stale
            pltpu.VMEM((tile_b, e_out), jnp.float32),  # staged prob
            pltpu.VMEM((tile_b, e_out), jnp.int32),   # staged alias
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, bsz), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(tile_b, e_out, tile_v, tile_k)),
        name="pdp_sweep_fused",
        interpret=backend.interpret("pdp_sweep_fused", requested=interpret),
    )(*work_list(vstart, vcount, nv),
      *(x.reshape(1, bsz) for x in (rows, e0, own_f0, own_f1)),
      ndk, slot, coin, u_mix, u_sparse, u_acc, prob, alias,
      mass.reshape(nv, 1, tile_v), stale, log_f, prior.reshape(1, -1))[0]
