"""Parameter projection for constraint-violation resolution (paper §5.5).

Under the relaxed (eventual) consistency model, concurrently-pushed deltas
can leave the shared sufficient statistics outside their feasible polytope —
e.g. in PDP the table counts must satisfy 0 ≤ s_wk ≤ m_wk and
m_wk > 0 ⇒ s_wk ≥ 1; aggregates must satisfy m_k = Σ_w m_wk.  Sampling from
inconsistent statistics produces NaN/negative probabilities and divergence
(paper Fig. 8).  The fix is a proximal projection: round every parameter to
the nearest point of the constraint set.

The paper gives three deployment schedules for the same projection:

  Algorithm 1 — single-machine batch pass at the end of an iteration.
  Algorithm 2 — distributed batch pass: parameter IDs are partitioned over
                clients, each projects its slice (the variant the paper
                reports results with).
  Algorithm 3 — on-demand, server-side, applied to every read.

All three share the rule language below.  A ``Rule`` constrains an ordered
pair of arrays elementwise; an ``Aggregate`` re-derives a sum statistic from
its counterpart (the paper's C2 tuples).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

Array = jax.Array
Stats = dict[str, Array]


@dataclass(frozen=True)
class Rule:
    """Elementwise constraint c(A, B) between stats ``a`` and ``b``.

    kind:
      "le"        — A ≤ B            (projection: A ← min(A, B))
      "ge"        — A ≥ B            (projection: A ← max(A, B))
      "nonneg"    — A ≥ 0            (b ignored)
      "pos_link"  — B > 0 ⇒ A ≥ 1 and B = 0 ⇒ A = 0
                    (PDP: m_wk > 0 ⇒ s_wk ≥ 1; m_wk = 0 ⇒ s_wk = 0)
    Projections move each violating entry to the nearest feasible value
    (L1-proximal, matching Algorithm 1's argmin |A' - A|).
    """

    kind: str
    a: str
    b: str | None = None


@dataclass(frozen=True)
class Aggregate:
    """C2 tuple: stats[out] must equal stats[src].sum(axis)."""

    src: str
    out: str
    axis: int | tuple[int, ...] = 0


def _apply_rule(stats: Stats, rule: Rule) -> Stats:
    a = stats[rule.a]
    if rule.kind == "nonneg":
        stats = dict(stats)
        stats[rule.a] = jnp.maximum(a, 0.0)
        return stats
    b = stats[rule.b]
    if rule.kind == "le":
        a2 = jnp.minimum(a, b)
    elif rule.kind == "ge":
        a2 = jnp.maximum(a, b)
    elif rule.kind == "pos_link":
        a2 = jnp.where(b > 0, jnp.maximum(a, 1.0), 0.0)
    else:
        raise ValueError(rule.kind)
    out = dict(stats)
    out[rule.a] = a2
    return out


def count_violations(stats: Stats, rules: Sequence[Rule]) -> Array:
    """Total number of elementwise constraint violations (diagnostics)."""
    total = jnp.zeros((), jnp.float32)
    for rule in rules:
        a = stats[rule.a]
        if rule.kind == "nonneg":
            total += jnp.sum((a < 0).astype(jnp.float32))
            continue
        b = stats[rule.b]
        if rule.kind == "le":
            total += jnp.sum((a > b).astype(jnp.float32))
        elif rule.kind == "ge":
            total += jnp.sum((a < b).astype(jnp.float32))
        elif rule.kind == "pos_link":
            total += jnp.sum(((b > 0) & (a < 1)).astype(jnp.float32))
            total += jnp.sum(((b <= 0) & (a != 0)).astype(jnp.float32))
    return total


def project(stats: Stats, rules: Sequence[Rule],
            aggregates: Sequence[Aggregate] = ()) -> Stats:
    """Algorithm 1 — batch projection on the full statistics.

    Rules are applied in order (the paper sorts so the most-frequent
    parameter types come first; callers pass them pre-sorted) followed by
    aggregate re-derivation.
    """
    for rule in rules:
        stats = _apply_rule(stats, rule)
    stats = dict(stats)
    for agg in aggregates:
        stats[agg.out] = stats[agg.src].sum(agg.axis)
    return stats


def project_distributed(
    stats: Stats,
    rules: Sequence[Rule],
    aggregates: Sequence[Aggregate],
    mesh: jax.sharding.Mesh,
    shard_axis: str = "model",
    row_specs: dict[str, P] | None = None,
) -> Stats:
    """Algorithm 2 — distributed projection.

    Parameter IDs (rows of the (V, K) matrices) are partitioned across
    devices of ``shard_axis``; each shard projects its slice independently
    (the elementwise rules are embarrassingly row-parallel — the paper's
    random allocation of correction tasks by parameter ID).  Aggregates are
    re-derived with a ``psum`` over the shards, which is the SendUpdate of
    Algorithm 1 expressed as a collective.
    """
    elementwise = {k: v for k, v in stats.items()
                   if not any(a.out == k for a in aggregates)}
    agg_names = [a.out for a in aggregates]

    in_specs = {k: (row_specs or {}).get(k, P(shard_axis)) for k in elementwise}
    out_specs = dict(in_specs)
    for a in aggregates:
        out_specs[a.out] = P()  # replicated after psum

    def local_project(shard_stats):
        out = dict(shard_stats)
        for rule in rules:
            out = _apply_rule(out, rule)
        for agg in aggregates:
            partial_sum = out[agg.src].sum(agg.axis)
            out[agg.out] = jax.lax.psum(partial_sum, shard_axis)
        return out

    fn = jax.shard_map(local_project, mesh=mesh, in_specs=(in_specs,),
                       out_specs=out_specs, check_vma=False)
    result = fn(elementwise)
    return result


def make_on_demand(rules: Sequence[Rule]) -> Callable[[Stats], Stats]:
    """Algorithm 3 — server-side on-demand correction.

    Returns a pull-path filter: every time a client pulls parameters the
    returned callable rounds them to the feasible set.  Aggregates are NOT
    re-derived here (that requires a global pass); the read is merely made
    safe, exactly as the paper's server-side variant."""

    def on_pull(stats: Stats) -> Stats:
        out = stats
        for rule in rules:
            out = _apply_rule(out, rule)
        return out

    return on_pull


# Canonical rule sets ------------------------------------------------------

PDP_RULES = (
    Rule("nonneg", "m_wk"),
    Rule("nonneg", "s_wk"),
    Rule("pos_link", "s_wk", "m_wk"),   # m>0 => s>=1 ; m=0 => s=0
    Rule("le", "s_wk", "m_wk"),         # s <= m
)
PDP_AGGREGATES = (
    Aggregate("m_wk", "m_k", 0),
    Aggregate("s_wk", "s_k", 0),
)

LDA_RULES = (Rule("nonneg", "n_wk"),)
LDA_AGGREGATES = (Aggregate("n_wk", "n_k", 0),)

HDP_RULES = (
    Rule("nonneg", "n_wk"),
    Rule("nonneg", "m_dk"),
    Rule("pos_link", "m_dk", "n_dk"),   # n_dk>0 => m_dk>=1 ; n_dk=0 => m_dk=0
    Rule("le", "m_dk", "n_dk"),         # tables <= customers
)
HDP_AGGREGATES = (Aggregate("n_wk", "n_k", 0),)
