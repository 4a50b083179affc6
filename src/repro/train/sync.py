"""Stale-synchronous, filter-compressed gradient sync — the paper's
parameter-server communication pattern (eventual consistency + magnitude-
priority filters, §5.3) applied to data-parallel SGD — a *beyond-paper*
transfer.

Mechanics (per client = data shard, expressed with shard_map):
  - each client keeps a full parameter replica and an error-feedback
    *residual* pytree (what filters withheld so far);
  - every step it computes local gradients and adds them to the residual;
  - every ``sync_every`` steps it pushes the *filtered* residual (top-k rows
    by L1 magnitude + uniformly sampled anti-starvation rows) through a
    psum and applies the synced update; between syncs it applies its own
    local update (bounded staleness — exactly the topic-model driver's τ);
  - nothing is ever dropped: residual_update carries withheld mass forward,
    the eventual-consistency guarantee in exact form.

This trades gradient freshness for a ~V/k reduction in sync bytes.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import ps

Array = jax.Array


@dataclass(frozen=True)
class SyncConfig:
    sync_every: int = 1                    # τ: steps between syncs
    filter: ps.FilterSpec = field(default_factory=ps.FilterSpec)


def filter_tree(grads: Any, spec: ps.FilterSpec, key: Array) -> Any:
    """Apply the communication filter leaf-wise.  2-D+ leaves filter by
    row-magnitude on their leading dim; 1-D leaves pass through dense (they
    are negligible traffic)."""
    leaves, treedef = jax.tree.flatten(grads)
    out = []
    for i, g in enumerate(leaves):
        if g.ndim >= 2 and spec.kind != "dense":
            rows = g.reshape(g.shape[0], -1)
            k = jax.random.fold_in(key, i)
            filt = ps.filter_delta(rows, spec, k).reshape(g.shape)
            out.append(filt)
        else:
            out.append(g)
    return jax.tree.unflatten(treedef, out)


def make_sync_fns(mesh: Mesh, scfg: SyncConfig, data_axis: str = "data"):
    """Returns (local_update, synced_update) pieces used by the stale-sync
    trainer loop in ``repro.launch.train`` (driver-level, since the sync
    cadence is a Python-loop decision, matching the paper's round structure).
    """

    def push(residual: Any, key: Array) -> tuple[Any, Any]:
        """Filter the residual, psum across clients, return (synced_grads,
        new_residual).  Runs inside shard_map over the data axis."""
        sent = filter_tree(residual, scfg.filter, key)
        synced = jax.tree.map(lambda s: jax.lax.psum(s, data_axis), sent)
        new_residual = jax.tree.map(lambda r, s: r - s, residual, sent)
        return synced, new_residual

    return push


def sync_bytes_estimate(params: Any, spec: ps.FilterSpec) -> tuple[int, int]:
    """(dense_bytes, filtered_bytes) one sync round would move per client —
    the napkin math for the §Perf collective-term hypothesis."""
    dense = 0
    filtered = 0
    for g in jax.tree.leaves(params):
        nbytes = g.size * 4
        dense += nbytes
        if g.ndim >= 2 and spec.kind == "topk":
            rows = g.shape[0]
            row_bytes = (g.size // rows) * 4
            kept = min(rows, spec.k_rows + spec.random_rows)
            filtered += kept * row_bytes + kept * 4
        else:
            filtered += nbytes
    return dense, filtered
