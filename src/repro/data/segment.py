"""Token-type segmentation: the word-major sorted layout (paper §5.1).

The paper's sampler touches each word-topic row once per sweep by walking
the corpus *word-major*: all draws of token-type ``w`` are resolved while
``n_wk[w]`` (and its alias table row) is hot.  On TPU the same idea becomes
a **sorted layout**: flatten a shard's (D, L) token grid, sort the flat
stream by token-type once per sweep, and hand the kernels a per-batch-tile
*vocab-tile window*, from which they walk only the (batch-tile,
vocab-tile) pairs that hold draws (``kernels.mhw_fused.work_list``,
DESIGN.md §5).

Because the sort key is the token-type, the vocab tiles touched by any one
batch tile of the sorted stream form a contiguous range — ``vstart[bi]`` to
``vstart[bi] + vcount[bi] - 1`` — so the skip metadata is two small int32
vectors, not a (nb, nv) occupancy matrix.  Padding (masked) positions get
the sentinel row ``vocab_size`` which sorts to the end of the stream and
falls outside every vocab tile, so the kernels never touch them.

The layout depends only on (tokens, mask): drivers should build it once per
shard and reuse it across sweeps (tokens never change between sweeps).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array


class SortedLayout(NamedTuple):
    """Sorted token stream + tile-skip metadata for one shard.

    With B = D·L flat positions padded up to Bp (a multiple of ``tile_b``):

    Attributes:
      order:   (B,)  int32 — flat position of the i-th sorted draw
               (``flat[order]`` sorts any per-position array; scattering
               with ``.at[order].set`` unsorts the first B sorted entries).
      rows:    (Bp,) int32 — token-type per sorted draw; ``vocab_size``
               marks padding (masked positions + Bp-B fill).
      docs:    (Bp,) int32 — document id per sorted draw (0 for padding).
      real:    (Bp,) bool  — True for genuine (unmasked) tokens.
      vstart:  (nb,) int32 — first vocab tile resident for batch tile bi.
      vcount:  (nb,) int32 — number of vocab tiles resident for batch tile
               bi (0 for all-padding tiles: the whole tile row is skipped).
      hist:    (nv,) int32 — draws per vocab tile (diagnostics/tests).
      offsets: (nv+1,) int32 — CSR-style exclusive prefix sum of ``hist``:
               draws of vocab tile t occupy sorted positions
               [offsets[t], offsets[t+1]) of the real-token prefix.
    """

    order: Array
    rows: Array
    docs: Array
    real: Array
    vstart: Array
    vcount: Array
    hist: Array
    offsets: Array


def chunk_bounds(l: int, n_chunks: int) -> tuple[int, ...]:
    """Position-chunk boundaries for a chunked sorted sweep (static per
    shape): chunk c covers positions [bounds[c], bounds[c+1])."""
    return tuple(round(i * l / n_chunks) for i in range(n_chunks + 1))


def pick_tile(n: int, target: int) -> int:
    """Largest divisor of ``n`` that is ≤ ``target`` (tile-size helper)."""
    for t in range(min(target, n), 0, -1):
        if n % t == 0:
            return t
    return 1


def pick_tile_vmem(v: int, k: int, budget_elems: int = 65536,
                   tile_k: int | None = None) -> int:
    """Vocab tile size from a VMEM budget: the largest divisor of ``v``
    whose (tile_v, K) tile stays within ``budget_elems`` elements per
    resident array (~256 KB fp32 at the default).

    With ``tile_k`` set (the K-tiled kernels), table residency is
    (tile_v, tile_k), so the budget divides by ``tile_k`` instead of K —
    tile_v no longer collapses as K grows, which is what makes the
    (V, K) scale axis usable.

    Small models fit entirely in one tile (minimal grid, no skipping
    needed); production vocabularies tile down and rely on the
    scalar-prefetch skip to keep work ~O(B).
    """
    cols = k if tile_k is None else min(tile_k, k)
    return pick_tile(v, max(1, budget_elems // max(cols, 1)))


def pick_tile_b(e: int, budget_elems: int = 1 << 18, cap: int = 1024
                ) -> int:
    """Batch tile of the fused sorted kernels from their per-token VMEM
    state: every staged table row and every chain temporary is a
    (tile_b, E) float32 array, so tile_b·E is held near ``budget_elems``
    (1 MiB per array at the default) — 1024 up to E=256, 256 at E=1024.
    Always a multiple of 128, the lane width of the kernels' (1, tile_b)
    per-token blocks."""
    return max(128, min(cap, budget_elems // max(e, 1) // 128 * 128))


@partial(jax.jit, static_argnames=("vocab_size", "tile_v", "tile_b"))
def build_layout(tokens: Array, mask: Array, vocab_size: int, *,
                 tile_v: int, tile_b: int) -> SortedLayout:
    """Sort a shard's token stream by token-type and derive tile-skip data.

    tokens: (D, L) int32 in [0, vocab_size); mask: (D, L) bool.
    Requires ``vocab_size % tile_v == 0``.
    """
    assert vocab_size % tile_v == 0, (vocab_size, tile_v)
    d, l = tokens.shape
    b = d * l
    bp = -(-b // tile_b) * tile_b
    nv = vocab_size // tile_v

    w = tokens.reshape(-1).astype(jnp.int32)
    m = mask.reshape(-1)
    key_rows = jnp.where(m, w, vocab_size)          # sentinel sorts last
    order = jnp.argsort(key_rows, stable=True).astype(jnp.int32)

    rows = key_rows[order]
    docs = (order // l).astype(jnp.int32)
    pad = bp - b
    if pad:
        rows = jnp.concatenate([rows, jnp.full((pad,), vocab_size, jnp.int32)])
        docs = jnp.concatenate([docs, jnp.zeros((pad,), jnp.int32)])
    real = rows < vocab_size

    # Per-batch-tile vocab-tile window.  Sorted ⇒ the touched tiles are the
    # contiguous range [first_row // tile_v, last_real_row // tile_v].
    rs = rows.reshape(bp // tile_b, tile_b)
    has_real = rs[:, 0] < vocab_size                # sorted: first is min
    last_real = jnp.max(jnp.where(rs < vocab_size, rs, -1), axis=1)
    vstart = jnp.where(has_real, rs[:, 0] // tile_v, 0).astype(jnp.int32)
    vend = jnp.where(has_real, last_real // tile_v, -1)
    vcount = (vend - vstart + 1).astype(jnp.int32)

    tile_of = jnp.where(real, rows // tile_v, nv)
    hist = jnp.bincount(tile_of, length=nv + 1)[:nv].astype(jnp.int32)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(hist).astype(jnp.int32)])

    return SortedLayout(order=order, rows=rows, docs=docs, real=real,
                        vstart=vstart, vcount=vcount, hist=hist,
                        offsets=offsets)


def build_chunked_layouts(tokens: Array, mask: Array, vocab_size: int, *,
                          bounds: tuple[int, ...], tile_v: int,
                          tile_b: int) -> tuple[SortedLayout, ...]:
    """Per-position-chunk layouts for ``lda.sweep(layout="sorted")``.

    ``bounds`` are the chunk boundaries over the position axis (see
    :func:`chunk_bounds`); chunk c covers positions [bounds[c], bounds[c+1]).
    Build once per shard and reuse across sweeps.
    """
    d = tokens.shape[0]
    outs = []
    for c in range(len(bounds) - 1):
        s, e = bounds[c], bounds[c + 1]
        outs.append(build_layout(
            tokens[:, s:e], mask[:, s:e], vocab_size, tile_v=tile_v,
            tile_b=min(tile_b, d * (e - s))))
    return tuple(outs)


def sort_values(layout: SortedLayout, flat: Array, fill=0) -> Array:
    """Arrange a flat (B,) per-position array into sorted-stream order (Bp,)."""
    sorted_b = flat[layout.order]
    pad = layout.rows.shape[0] - sorted_b.shape[0]
    if pad:
        fill_arr = jnp.full((pad,), fill, sorted_b.dtype)
        sorted_b = jnp.concatenate([sorted_b, fill_arr])
    return sorted_b


def unsort_values(layout: SortedLayout, sorted_vals: Array, like: Array) -> Array:
    """Invert :func:`sort_values`: scatter sorted-stream values (Bp,) back to
    flat position order, shaped like ``like`` (flat (B,) template)."""
    b = layout.order.shape[0]
    return like.at[layout.order].set(sorted_vals[:b])
