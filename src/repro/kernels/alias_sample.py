"""Pallas TPU kernels: blocked O(1) alias-table draws.

Consumer half of the paper's §5.1 producer/consumer sampler: given prebuilt
(prob, alias) tables, each token draws from the table of its own token-type
row using two uniforms — slot choice and the biased coin.

TPU adaptation: a flat gather ``prob[rows[b], slot[b]]`` would need the
whole (V, K) table resident, which does not fit VMEM at production sizes
(2M types × 2K topics).  Instead the kernels run a 2-D grid over
(vocab tiles × batch tiles): each program holds one (TILE_V, K) table tile
in VMEM and resolves exactly the draws whose row falls inside its tile,
accumulating into the output block with a mask.  The batch-tile output
block is revisited across vocab tiles (same index map), which Pallas
supports as an accumulation pattern.

Two variants:

* :func:`alias_sample` — layout-oblivious scan: every (vocab, batch) tile
  pair is visited, O(B · V/TILE_V) predicate work.  Kept as the oracle and
  for unsorted draw streams.
* :func:`alias_sample_sorted` — consumes the token-sorted layout of
  ``repro.data.segment`` (DESIGN.md §5): a scalar-prefetched per-batch-tile
  vocab-tile window (``vstart``/``vcount``) drives the table-tile index map,
  so programs whose tile holds zero resident draws neither DMA a fresh tile
  (the index map re-points at the previous tile) nor run the body
  (``pl.when``).  Tile-predicate work drops to ~O(B): each batch tile only
  really visits the few vocab tiles its contiguous sorted row-range spans.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

DEFAULT_TILE_V = 64
DEFAULT_TILE_B = 1024


def _alias_sample_kernel(rows_ref, slot_ref, coin_ref, prob_ref, alias_ref,
                         out_ref, *, tile_v: int):
    vi = pl.program_id(0)
    row_lo = vi * tile_v

    rows = rows_ref[...]                          # (TILE_B,)
    slot = slot_ref[...]
    coin = coin_ref[...]
    prob = prob_ref[...]                          # (TILE_V, K)
    alias = alias_ref[...]

    local = rows - row_lo
    in_tile = (local >= 0) & (local < tile_v)
    safe_local = jnp.clip(local, 0, tile_v - 1)

    p = prob[safe_local, slot]
    a = alias[safe_local, slot]
    draw = jnp.where(coin < p, slot, a).astype(jnp.int32)

    @pl.when(vi == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] = jnp.where(in_tile, draw, out_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("tile_v", "tile_b", "interpret"))
def alias_sample(prob: jax.Array, alias: jax.Array, rows: jax.Array,
                 slot: jax.Array, coin: jax.Array, *,
                 tile_v: int = DEFAULT_TILE_V,
                 tile_b: int = DEFAULT_TILE_B,
                 interpret: bool | None = None) -> jax.Array:
    """Blocked alias draws (full tile scan).

    prob/alias: (V, K) tables; rows/slot/coin: (B,) per-draw row id, slot
    uniform (int in [0,K)) and coin uniform (float in [0,1)).  Returns (B,)
    int32 draws.  RNG stays outside the kernel so the kernel is a pure
    function of its inputs (exactly comparable to the oracle).
    """
    v, k = prob.shape
    b = rows.shape[0]
    tile_v = min(tile_v, v)
    tile_b = min(tile_b, b)
    assert v % tile_v == 0 and b % tile_b == 0
    grid = (v // tile_v, b // tile_b)
    kernel = functools.partial(_alias_sample_kernel, tile_v=tile_v)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b,), lambda vi, bi: (bi,)),
            pl.BlockSpec((tile_b,), lambda vi, bi: (bi,)),
            pl.BlockSpec((tile_b,), lambda vi, bi: (bi,)),
            pl.BlockSpec((tile_v, k), lambda vi, bi: (vi, 0)),
            pl.BlockSpec((tile_v, k), lambda vi, bi: (vi, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b,), lambda vi, bi: (bi,)),
        out_shape=jax.ShapeDtypeStruct((b,), jnp.int32),
        name="alias_sample",
        interpret=backend.interpret("alias_sample", requested=interpret,
                                    lowers=False),
    )(rows, slot, coin, prob, alias)


# ---------------------------------------------------------------------------
# Token-sorted, tile-skipping variant (scalar prefetch)
# ---------------------------------------------------------------------------

def _alias_sample_sorted_kernel(vstart_ref, vcount_ref, rows_ref, slot_ref,
                                coin_ref, prob_ref, alias_ref, out_ref, *,
                                tile_v: int, n_vtiles: int):
    bi = pl.program_id(0)
    vi = pl.program_id(1)
    tid = jnp.clip(vstart_ref[bi] + jnp.minimum(vi, vcount_ref[bi] - 1),
                   0, n_vtiles - 1)
    row_lo = tid * tile_v

    @pl.when(vi == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(vi < vcount_ref[bi])
    def _body():
        rows = rows_ref[...]
        local = rows - row_lo
        in_tile = (local >= 0) & (local < tile_v)
        safe_local = jnp.clip(local, 0, tile_v - 1)
        p = prob_ref[...][safe_local, slot_ref[...]]
        a = alias_ref[...][safe_local, slot_ref[...]]
        draw = jnp.where(coin_ref[...] < p, slot_ref[...], a).astype(jnp.int32)
        out_ref[...] = jnp.where(in_tile, draw, out_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("tile_v", "tile_b", "interpret"))
def alias_sample_sorted(prob: jax.Array, alias: jax.Array, rows: jax.Array,
                        slot: jax.Array, coin: jax.Array, vstart: jax.Array,
                        vcount: jax.Array, *,
                        tile_v: int = DEFAULT_TILE_V,
                        tile_b: int = DEFAULT_TILE_B,
                        interpret: bool | None = None) -> jax.Array:
    """Tile-skipping alias draws over a token-sorted stream.

    rows must be sorted ascending (``segment.build_layout``); entries ≥ V
    are padding sentinels and return 0.  ``vstart``/``vcount``
    (B/tile_b,) give the contiguous vocab-tile window of each batch tile;
    programs outside the window are skipped (no DMA, no body) so the work
    is proportional to the number of *occupied* tile pairs, not the grid.
    """
    v, k = prob.shape
    b = rows.shape[0]
    tile_v = min(tile_v, v)
    tile_b = min(tile_b, b)
    assert v % tile_v == 0 and b % tile_b == 0
    nb, nv = b // tile_b, v // tile_v
    assert vstart.shape == (nb,) and vcount.shape == (nb,)

    kernel = functools.partial(_alias_sample_sorted_kernel, tile_v=tile_v,
                               n_vtiles=nv)

    def table_map(bi, vi, vs, vc):
        return (jnp.clip(vs[bi] + jnp.minimum(vi, vc[bi] - 1), 0, nv - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, nv),
        in_specs=[
            pl.BlockSpec((tile_b,), lambda bi, vi, vs, vc: (bi,)),
            pl.BlockSpec((tile_b,), lambda bi, vi, vs, vc: (bi,)),
            pl.BlockSpec((tile_b,), lambda bi, vi, vs, vc: (bi,)),
            pl.BlockSpec((tile_v, k), table_map),
            pl.BlockSpec((tile_v, k), table_map),
        ],
        out_specs=pl.BlockSpec((tile_b,), lambda bi, vi, vs, vc: (bi,)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b,), jnp.int32),
        name="alias_sample_sorted",
        interpret=backend.interpret("alias_sample_sorted", requested=interpret,
                                    lowers=False),
    )(vstart, vcount, rows, slot, coin, prob, alias)
