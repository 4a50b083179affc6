"""Parameter-server abstraction mapped to JAX SPMD (paper §4, §5.3).

The paper's parameter server holds (key,value) sufficient statistics sharded
over server nodes (Chord-style consistent hashing); clients pull stale
copies, sample, and push batched deltas with user-defined communication
filters under an eventual-consistency model.

On a TPU mesh the same roles map to sharding (DESIGN.md §2):

  server group  →  the ``model`` mesh axis: canonical statistics arrays are
                   sharded row-wise over it (`P('model', None)` for (V, K)
                   matrices — row-hashing becomes row-sharding).
  client group  →  the ``data`` mesh axis: each data shard holds a document
                   shard plus a *stale replica* of the shared statistics.
  push/pull     →  `psum` of (filtered) deltas / all-gather of fresh rows.
  consistency   →  bounded staleness: clients run ``tau`` Gibbs sweeps
                   against a frozen snapshot between sync rounds.

Communication filters (paper §5.3 "Communication filters") are implemented
as *delta compression*: the magnitude-priority filter keeps the top-k rows
by L1 delta mass, and the uniform-sampling anti-starvation term keeps a
random subset of the remainder.  The compressed representation (indices,
values) is what crosses the interconnect — visible as smaller collectives
in the lowered HLO.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@dataclass(frozen=True)
class FilterSpec:
    """Communication filter configuration.

    kind:
      "dense"     — no filtering; push the full delta matrix.
      "topk"      — keep ``k_rows`` rows with the largest L1 delta magnitude
                    plus ``random_rows`` uniformly sampled rows (paper §5.3:
                    priority ∝ magnitude + uniform sampling to avoid
                    starvation of small-update parameters).
      "threshold" — zero rows whose L1 delta magnitude is below ``threshold``
                    (KKT-style significance filter).
    """

    kind: str = "dense"
    k_rows: int = 0
    random_rows: int = 0
    threshold: float = 0.0


class CompressedDelta(NamedTuple):
    """Sparse (row-indices, row-values) delta representation."""

    indices: Array  # (k,) int32 row ids
    values: Array   # (k, K) rows


def compress_delta(delta: Array, spec: FilterSpec, key: Array) -> CompressedDelta:
    """Apply the communication filter to a (V, K) row-delta matrix."""
    if spec.kind != "topk":
        raise ValueError("compress_delta only applies to the topk filter")
    v = delta.shape[0]
    k_rows = min(spec.k_rows, v)   # small leaves pass through whole
    mag = jnp.abs(delta).sum(-1)  # (V,) L1 per row
    _, top_idx = jax.lax.top_k(mag, k_rows)
    if spec.random_rows > 0 and k_rows < v:
        # Uniform anti-starvation rows: sampled from the whole vocabulary;
        # collisions with top rows are harmless (delta rows add idempotently
        # because we zero them after selection — see below).
        rand_idx = jax.random.randint(key, (spec.random_rows,), 0, v, jnp.int32)
        idx = jnp.concatenate([top_idx.astype(jnp.int32), rand_idx])
    else:
        idx = top_idx.astype(jnp.int32)
    # De-duplicate by construction: gather rows, then mark first occurrence.
    # (A duplicated index would double-apply the delta; we zero repeats.)
    sorted_idx = jnp.sort(idx)
    dup = jnp.concatenate([jnp.array([False]), sorted_idx[1:] == sorted_idx[:-1]])
    order = jnp.argsort(idx)
    dup_unsorted = jnp.zeros_like(dup).at[order].set(dup)
    rows = delta[idx] * (~dup_unsorted)[:, None]
    return CompressedDelta(indices=idx, values=rows)


def decompress_delta(comp: CompressedDelta, vocab_size: int, n_cols: int) -> Array:
    """Scatter a compressed delta back to a dense (V, K) matrix."""
    dense = jnp.zeros((vocab_size, n_cols), comp.values.dtype)
    return dense.at[comp.indices].add(comp.values)


def filter_delta(delta: Array, spec: FilterSpec, key: Array) -> Array:
    """Dense-in/dense-out filtering (used when the transport is a psum).

    For "topk" this returns the dense matrix with only the selected rows
    non-zero — semantically identical to compress+decompress, and the form
    the distributed driver psums.  The *compressed* transport (all-gather of
    (indices, values)) lives in ``repro.core.distributed.sync_compressed``.
    """
    if spec.kind == "dense":
        return delta
    if spec.kind == "threshold":
        mag = jnp.abs(delta).sum(-1)
        return jnp.where((mag >= spec.threshold)[:, None], delta, 0.0)
    if spec.kind == "topk":
        comp = compress_delta(delta, spec, key)
        return decompress_delta(comp, delta.shape[0], delta.shape[1])
    raise ValueError(spec.kind)


def changed_rows(row_mass: Array, k_rows: int, threshold: float
                 ) -> tuple[Array, Array]:
    """Select the rows an incremental alias rebuild should touch.

    The same magnitude-priority machinery as the top-k communication filter
    (:func:`compress_delta`): the ``k_rows`` rows with the largest
    accumulated L1 delta mass, plus a validity mask ``mass > threshold`` so
    below-threshold rows inside the fixed-size selection are left untouched
    (shapes must be static under jit; masked rows cost a no-op scatter).

    ``row_mass`` is the (V,) per-row accumulated L1 push mass.  The
    accounting that feeds it lives behind the parameter server's push path
    (``repro.core.server.ParameterServer``: per-shard accumulators folded
    on every tracked push, consumed + reset by ``consume_changed_rows``) —
    with a top-k communication filter at most ``k_rows + random_rows`` rows
    are non-zero per push, so size the rebuild budget accordingly.
    """
    k_rows = min(k_rows, row_mass.shape[0])
    mass, idx = jax.lax.top_k(row_mass, k_rows)
    return idx.astype(jnp.int32), mass > threshold


class SparseDelta(NamedTuple):
    """Row-sliced COO pytree delta: one shared row-index vector plus the
    packed rows of every delta statistic at those indices.

    The dense↔sparse boundary contract (DESIGN.md §12): ``to_sparse_delta``
    keeps every row that is non-zero in *any* statistic, so
    ``from_sparse_delta`` reconstructs the dense pytree bit-for-bit — the
    selected rows carry their exact float values and the dropped rows were
    exactly 0.0 in every statistic.  No arithmetic is re-ordered, which is
    why a sparse push under BSP is bit-exact with the dense push.
    """

    rows: Array                  # (R,) int32, strictly increasing, unique
    values: dict[str, Array]     # name -> (R, K) packed rows


def to_sparse_delta(deltas: dict[str, Array]) -> SparseDelta:
    """Dense delta pytree → :class:`SparseDelta` of its non-zero rows.

    Host-side (data-dependent shape — the wire path and the Python
    reference loop use it; the compiled round keeps dense deltas).  Rows
    are the ascending union of non-zero rows across statistics.
    """
    mats = {n: np.asarray(v) for n, v in deltas.items()}
    nz: np.ndarray | None = None
    for v in mats.values():
        row_any = np.any(v != 0, axis=tuple(range(1, v.ndim)))
        nz = row_any if nz is None else (nz | row_any)
    rows = np.flatnonzero(nz).astype(np.int32)
    return SparseDelta(rows=rows,
                       values={n: v[rows] for n, v in mats.items()})


def from_sparse_delta(sp: SparseDelta, n_rows: int) -> dict[str, Array]:
    """:class:`SparseDelta` → dense delta pytree (exact inverse of
    :func:`to_sparse_delta` given the dense row count)."""
    out: dict[str, Array] = {}
    rows = jnp.asarray(sp.rows, jnp.int32)
    for n, v in sp.values.items():
        v = jnp.asarray(v)
        dense = jnp.zeros((n_rows,) + v.shape[1:], v.dtype)
        # Unique indices by construction: the scatter-add writes each
        # selected row's exact value (0 + x == x bit-for-bit).
        out[n] = dense.at[rows].add(v)
    return out


def residual_update(residual: Array, delta: Array, sent: Array) -> Array:
    """Error-feedback accumulator: what a filter withholds is carried to the
    next round instead of dropped, so every update is eventually applied —
    this *is* the eventual-consistency guarantee, kept exactly."""
    return residual + delta - sent
