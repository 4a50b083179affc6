"""Pallas TPU kernel: blocked Walker/Vose alias-table construction.

The alias build is the producer half of the paper's multi-thread sampler
(§5.1): tables over the dense proposal term are (re)built every refresh
cadence for every token-type row.  On TPU the thread pool dissolves into a
*blocked, row-vectorized* kernel:

  grid          = vocabulary tiles (one program per TILE_R rows)
  VMEM working  = a (TILE_R, K) tile of the dense term + the table state
  inner loop    = the classical two-stack pairing loop, run in lockstep
                  across the TILE_R rows of the tile (rows are VPU lanes;
                  every loop step retires one "small" slot per row)

The LM-dense families (LDA, HDP), whose dense term factorizes as
prior_e · (n_wk+β)/(n_t+β̄), build from the raw statistics: the term is
computed *inside* the kernel, saving a V×K HBM round trip, and written
out once as the stale snapshot.  One kernel body serves the full build
(:func:`alias_build_fused`, which reads (TILE_R, K) ``n_wk`` tiles
straight from HBM) and the incremental rebuild of the paper's §5.1
producer/consumer design (:func:`alias_build_gather_fused`, which gathers
the R token-type rows whose pushed delta mass drifted and builds them in
the same tiles, so its cost scales with R, not V).  Other families build
over a materialized dense term: :func:`alias_build`, and
:func:`alias_build_rows` over a compacted (R, E) block.

Validated against ``repro.core.alias.build`` in interpret mode (CPU);
the block shapes keep the working set ≤ a few MB of VMEM for production
sizes (TILE_R=8, K≤4096 → ~1.5 MB including table state).  The pairing
loop is O(K) lane operations per slot, so a row costs O(K²) — the price
of lowering without sort or scatter — but only VPU work: the tile's
state stays on the chip for all K steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

DEFAULT_TILE_R = 8


def _build_tile(scaled: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Two-stack alias pairing for a (R, K) tile, rows in lockstep.

    ``scaled`` is the K-normalized distribution × K (mean 1.0 per row).
    Returns (prob, alias) of shapes (R, K) float32 / int32.

    Bit-identical to the stack machine of ``core.alias._build_one`` without
    materializing its stacks (no sort, no scatter — forms Mosaic cannot
    lower).  In that machine the initial smalls leave in ascending index
    order and the initial larges in descending order, and a large that
    drops below 1 is pushed onto the small stack and is popped next.  So
    the state is two membership masks plus a "pending" demoted large, and
    each pop is a masked min/max over the K lanes; each table write is a
    one-hot select.
    """
    r, k = scaled.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (r, k), 1)
    small = scaled < 1.0
    none = jnp.full((r, 1), -1, jnp.int32)

    def lane_sum(x):
        return jnp.sum(x, axis=-1, keepdims=True)

    def body(_, carry):
        # (Membership masks ride the loop as int32: Mosaic cannot carry
        # bool vectors through a loop.)
        prob, alias, scaled, small_left, large_left, pending, n_small, \
            n_large = carry
        active = (n_small > 0) & (n_large > 0)          # (R, 1)
        has_pending = pending >= 0
        first_small = jnp.min(jnp.where(small_left > 0, idx, k), axis=-1,
                              keepdims=True)
        i = jnp.where(has_pending, pending, first_small)
        j = jnp.max(jnp.where(large_left > 0, idx, -1), axis=-1,
                    keepdims=True)
        at_i = idx == i
        at_j = idx == j
        si = lane_sum(jnp.where(at_i, scaled, 0.0))
        sj = lane_sum(jnp.where(at_j, scaled, 0.0)) - (1.0 - si)
        j_small = sj < 1.0

        write_i = active & at_i
        prob = jnp.where(write_i, si, prob)
        alias = jnp.where(write_i, j, alias)
        scaled = jnp.where(active & at_j, sj, scaled)
        small_left = jnp.where(write_i & ~has_pending, 0, small_left)
        large_left = jnp.where(active & j_small & at_j, 0, large_left)
        pending = jnp.where(active, jnp.where(j_small, j, -1), pending)
        n_small = jnp.where(active & ~j_small, n_small - 1, n_small)
        n_large = jnp.where(active & j_small, n_large - 1, n_large)
        return (prob, alias, scaled, small_left, large_left, pending,
                n_small, n_large)

    small_i = small.astype(jnp.int32)
    n_small = lane_sum(small_i)
    init = (jnp.ones((r, k), jnp.float32), idx, scaled, small_i,
            1 - small_i, none, n_small, k - n_small)
    prob, alias, *_ = jax.lax.fori_loop(0, k, body, init)
    return prob, alias


def _build_rows(p: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(R, K) unnormalized rows → (prob, alias, mass (R, 1)); all-zero rows
    fall back to uniform, as in ``core.alias._build_one``."""
    k = p.shape[-1]
    mass = jnp.sum(p, axis=-1, keepdims=True)
    safe = mass > 0
    pn = jnp.where(safe, p / jnp.where(safe, mass, 1.0),
                   jnp.full_like(p, 1.0 / k))
    prob, alias = _build_tile(pn * k)
    return prob, alias, mass


def _flat_mass(outs):
    """(prob, alias, mass (V, 1)) → (prob, alias, mass (V,)).  Kernels
    write mass as a (TILE_R, 1) column: Mosaic refuses rank-1 blocks
    shorter than a lane tile."""
    prob, alias, mass = outs
    return prob, alias, mass[:, 0]


def _alias_build_kernel(p_ref, prob_ref, alias_ref, mass_ref):
    p = p_ref[...].astype(jnp.float32)                 # (TILE_R, K)
    prob_ref[...], alias_ref[...], mass_ref[...] = _build_rows(p)


def _alias_build_tiled_kernel(p_ref, prob_ref, alias_ref, mass_ref,
                              p_s, prob_s, alias_s, *, tile_k: int):
    """Two-phase K-streamed build (grid (nr, 2, nk), lexicographic order):
    phase 0 stages the row tile's input k-tiles into full-K scratch;
    phase 1 runs the pairing once (at its first k-tile step) on the
    staged rows and flushes the result back out one k-tile per step.
    Walker pairing moves probability mass between arbitrary outcome
    columns, so the *build state* is irreducibly full-K per row — the
    streaming bounds the in/out block residency, not the scratch.

    Output blocks written during phase 0 hold garbage; the grid revisits
    every (row, k-tile) output block in phase 1 after all of that row's
    phase-0 steps (the phase axis is major to the k axis), so the
    phase-1 flush is the one that lands."""
    pi = pl.program_id(1)
    ki = pl.program_id(2)
    ksl = pl.ds(ki * tile_k, tile_k)

    @pl.when(pi == 0)
    def _stage():
        p_s[:, ksl] = p_ref[...].astype(jnp.float32)

    @pl.when((pi == 1) & (ki == 0))
    def _build():
        prob_s[...], alias_s[...], mass_ref[...] = _build_rows(p_s[...])

    @pl.when(pi == 1)
    def _flush():
        prob_ref[...] = prob_s[:, ksl]
        alias_ref[...] = alias_s[:, ksl]


@functools.partial(jax.jit, static_argnames=("tile_r", "tile_k", "interpret"))
def alias_build(p: jax.Array, *, tile_r: int = DEFAULT_TILE_R,
                tile_k: int | None = None, interpret: bool | None = None):
    """Build alias tables for (V, K) rows. Returns (prob, alias, mass).

    ``tile_k`` (None ⇒ K) streams the input and output K dimension in
    (tile_r, tile_k) blocks through the two-phase kernel; the build math
    is identical either way (the pairing always sees the full row), so
    tiled and untiled tables are bit-identical."""
    v, k = p.shape
    assert v % tile_r == 0, f"V={v} must be a multiple of tile_r={tile_r}"
    out_shape = [
        jax.ShapeDtypeStruct((v, k), jnp.float32),
        jax.ShapeDtypeStruct((v, k), jnp.int32),
        jax.ShapeDtypeStruct((v, 1), jnp.float32),
    ]
    interpret = backend.interpret("alias_build", requested=interpret)
    if tile_k is None or tile_k >= k:
        return _flat_mass(pl.pallas_call(
            _alias_build_kernel,
            grid=(v // tile_r,),
            in_specs=[pl.BlockSpec((tile_r, k), lambda i: (i, 0))],
            out_specs=[
                pl.BlockSpec((tile_r, k), lambda i: (i, 0)),
                pl.BlockSpec((tile_r, k), lambda i: (i, 0)),
                pl.BlockSpec((tile_r, 1), lambda i: (i, 0)),
            ],
            out_shape=out_shape,
            name="alias_build",
            interpret=interpret,
        )(p))
    assert k % tile_k == 0, f"K={k} must be a multiple of tile_k={tile_k}"
    nk = k // tile_k
    kernel = functools.partial(_alias_build_tiled_kernel, tile_k=tile_k)
    return _flat_mass(pl.pallas_call(
        kernel,
        grid=(v // tile_r, 2, nk),
        in_specs=[pl.BlockSpec((tile_r, tile_k), lambda i, pi, ki: (i, ki))],
        out_specs=[
            pl.BlockSpec((tile_r, tile_k), lambda i, pi, ki: (i, ki)),
            pl.BlockSpec((tile_r, tile_k), lambda i, pi, ki: (i, ki)),
            pl.BlockSpec((tile_r, 1), lambda i, pi, ki: (i, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((tile_r, k), jnp.float32),   # staged input rows
            pltpu.VMEM((tile_r, k), jnp.float32),   # built prob rows
            pltpu.VMEM((tile_r, k), jnp.int32),     # built alias rows
        ],
        name="alias_build",
        interpret=interpret,
    )(p))


@functools.partial(jax.jit, static_argnames=("tile_r", "tile_k", "interpret"))
def alias_build_rows(p: jax.Array, *, tile_r: int = DEFAULT_TILE_R,
                     tile_k: int | None = None, interpret: bool | None = None):
    """Alias build over a compacted (R, K) row block — the gathered changed
    rows of an incremental rebuild.  R need not be a tile_r multiple (rows
    are padded with zero mass, which the kernel's uniform fallback absorbs,
    and trimmed from the outputs)."""
    r, k = p.shape
    pad = (-r) % tile_r
    p_pad = jnp.pad(p, ((0, pad), (0, 0))) if pad else p
    prob, alias, mass = alias_build(p_pad, tile_r=min(tile_r, r + pad),
                                    tile_k=tile_k, interpret=interpret)
    return prob[:r], alias[:r], mass[:r]


# Elements of one (tile_r, K) array of the LM-dense build's pairing state,
# K padded to the 128-lane width: the row tile holds tile_r·K near this.
# At K=1024 it gives 64 rows, the fastest of 8, 16, 32 and 64 on a v5e:
# the pairing steps of independent rows overlap (PERF.md §6).
LM_TILE_ELEMS = 64 * 1024


def pick_tile_r(k: int, rows: int) -> int:
    """Row tile of the LM-dense build: the largest power-of-two multiple
    of 8 (the sublane count) whose (tile_r, K) tile stays within
    ``LM_TILE_ELEMS``, and no larger than ``rows`` rounded up to 8."""
    lanes = -(-k // 128) * 128
    t = 8
    while 2 * t * lanes <= LM_TILE_ELEMS:
        t *= 2
    return min(t, -(-rows // 8) * 8)


def _alias_build_lm_kernel(n_wk_ref, n_k_ref, prior_ref, prob_ref,
                           alias_ref, mass_ref, stale_ref, *, beta,
                           beta_bar):
    """A (tile_r, K) tile of statistics rows: the dense term
    prior_e·((n_wk+β)/(n_k+β̄)) computed in-register, the table build,
    and the dense rows written too (the stale snapshot)."""
    n_wk = n_wk_ref[...].astype(jnp.float32)
    n_k = n_k_ref[...].astype(jnp.float32)             # (1, K) broadcast row
    # prior · (LM row), division grouped first — the exact operation order
    # of the families' dense_probs, so the tables and stale rows are
    # bit-identical to core.alias.build over dense_probs.
    p = prior_ref[...] * ((n_wk + beta) / (n_k + beta_bar))
    stale_ref[...] = p
    # max(p, 0) is p, the dense term being non-negative; it keeps the
    # compiler from contracting the product into the mass sum's adds (an
    # FMA), which would round the mass unlike a sum of the stored rows.
    prob_ref[...], alias_ref[...], mass_ref[...] = _build_rows(
        jnp.maximum(p, 0.0))


def _lm_build(n_wk_rows, n_k, prior, *, beta, beta_bar, tile_r, name,
              interpret):
    """(R, K) statistics rows → (prob, alias, mass (R,), dense) over
    (tile_r, K) blocks; R is padded to a tile_r multiple and trimmed."""
    r, k = n_wk_rows.shape
    pad = (-r) % tile_r
    if pad:
        n_wk_rows = jnp.pad(n_wk_rows, ((0, pad), (0, 0)))
    kernel = functools.partial(_alias_build_lm_kernel, beta=beta,
                               beta_bar=beta_bar)
    tile = pl.BlockSpec((tile_r, k), lambda i: (i, 0))
    full_row = pl.BlockSpec((1, k), lambda i: (0, 0))
    prob, alias, mass, dense = pl.pallas_call(
        kernel,
        grid=((r + pad) // tile_r,),
        in_specs=[tile, full_row, full_row],
        out_specs=[tile, tile, pl.BlockSpec((tile_r, 1), lambda i: (i, 0)),
                   tile],
        out_shape=[
            jax.ShapeDtypeStruct((r + pad, k), jnp.float32),
            jax.ShapeDtypeStruct((r + pad, k), jnp.int32),
            jax.ShapeDtypeStruct((r + pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((r + pad, k), jnp.float32),
        ],
        name=name,
        interpret=backend.interpret(name, requested=interpret),
    )(n_wk_rows, n_k.reshape(1, -1), prior.reshape(1, -1))
    return prob[:r], alias[:r], mass[:r, 0], dense[:r]


@functools.partial(jax.jit, static_argnames=("beta", "beta_bar", "tile_r",
                                             "interpret"))
def alias_build_fused(n_wk: jax.Array, n_k: jax.Array, prior: jax.Array, *,
                      beta: float, beta_bar: float, tile_r: int | None = None,
                      interpret: bool | None = None):
    """Full build from raw statistics for every family whose dense term
    factorizes as prior_e · LM row: (V, K) ``n_wk`` tiles are read straight
    from HBM, one (tile_r, K) tile a program.  ``prior`` is the (K,)
    per-topic prior-mass vector (α·1 for LDA, b1·θ0 for HDP); ``tile_r``
    None ⇒ :func:`pick_tile_r`.  Returns (prob, alias, mass, dense) of
    shapes (V, K)/(V, K)/(V,)/(V, K)."""
    v, k = n_wk.shape
    return _lm_build(n_wk, n_k, prior, beta=beta, beta_bar=beta_bar,
                     tile_r=tile_r or pick_tile_r(k, v),
                     name="alias_build_fused", interpret=interpret)


@functools.partial(jax.jit, static_argnames=("beta", "beta_bar", "tile_r",
                                             "interpret"))
def alias_build_gather_fused(n_wk: jax.Array, n_k: jax.Array,
                             prior: jax.Array, rows: jax.Array, *,
                             beta: float, beta_bar: float,
                             tile_r: int | None = None,
                             interpret: bool | None = None):
    """Gather → the :func:`alias_build_fused` kernel over changed rows only.

    ``rows`` is the (R,) int32 changed-row selection; XLA gathers those
    statistics rows (R·K elements) and the kernel runs over them in
    (tile_r, K) tiles, so each row is built exactly as the full build
    builds it and partial and full rebuilds of the same statistics agree
    bit-for-bit.  Returns compacted (prob, alias, mass, dense) rows of
    shapes (R, K)/(R, K)/(R,)/(R, K) for the caller to scatter
    (``repro.core.alias.update_rows``).
    """
    k = n_wk.shape[1]
    return _lm_build(n_wk[rows], n_k, prior, beta=beta, beta_bar=beta_bar,
                     tile_r=tile_r or pick_tile_r(k, rows.shape[0]),
                     name="alias_build_gather_fused", interpret=interpret)
