"""Distributed collapsed Gibbs sampling on a device mesh (paper §5.2-§5.3).

Clients = shards of the ``data`` mesh axis, each holding a document shard
and a stale replica of the shared statistics.  The canonical statistics
live behind the explicit parameter server (``repro.core.server``):
vocabulary-sharded :class:`~repro.core.server.ServerState` under a
pluggable consistency policy (BSP / SSP / async).  A *round* is:

  1. pull   — the policy's snapshot of the shared statistics (BSP: frozen
              fresh copy; SSP: the versioned stale cache; async: live),
  2. sample — ``tau`` local Gibbs sweeps against the snapshot, applying own
              deltas locally (bounded-staleness eventual consistency),
  3. filter — communication filter on the accumulated delta (paper §5.3),
  4. push   — psum of filtered deltas across clients (or the compressed
              all-gather transport), applied to the canonical statistics,
  5. project— distributed constraint projection (paper §5.5, Algorithm 2)
              on the shared polytope, plus each family's client-local rules
              (e.g. HDP's 1 ≤ m_dk ≤ n_dk table-count constraints) applied
              shard-locally inside the round.

Model specifics enter only through the ``repro.core.family`` registry —
there is exactly one round implementation for LDA / PDP / HDP, and a
family's projection rules are sourced verbatim from
``repro.core.projection.*_RULES`` (split by operand locality, never
hand-copied here).  The per-client round body (:func:`tau_sweeps` — the
staleness loop as a ``lax.scan`` — and :func:`filter_push`) is defined
here and consumed verbatim by the single-device ``engine.Trainer``'s
compiled whole-round program (``repro.engine.round``), so the mesh round
and the client-iterated round cannot drift apart.

Failure injection (paper §5.4): a boolean per-client ``alive`` mask zeroes a
failed client's contribution for the round — the recovery path (reload from
snapshot, re-pull, continue) is exercised in tests.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import family as family_mod
from repro.core import projection, ps
from repro.core import server as server_mod

Array = jax.Array


# --------------------------------------------------------------------------
# The per-client round body — shared with engine.round's compiled round
# --------------------------------------------------------------------------

def tau_sweeps(model_cfg, fam: family_mod.ModelFamily, local, snapshot,
               tables, stale_dense, tokens, mask, sweep_keys, *,
               method: str = "mhw", layout: str = "scan",
               sorted_layouts: tuple | None = None):
    """One client's work for a sync round: ``tau`` sweeps against the frozen
    snapshot, applying its own deltas locally between sweeps (the paper's
    clients update their replica immediately and push asynchronously), then
    the family's client-local constraint rules.

    ``sweep_keys`` is the (tau, ...) stacked per-sweep key array — the
    caller owns the keying so the mesh round and the Trainer each preserve
    their historical RNG streams.  The staleness loop is a ``lax.scan`` so
    ``tau`` does not multiply the trace.

    Returns (local', accumulated_deltas).
    """
    zero = {n: jnp.zeros_like(fam.stats_dict(snapshot)[n])
            for n in fam.delta_names}

    def one_sweep(carry, key):
        local, shared_local, acc = carry
        local, deltas = fam.sweep(model_cfg, local, shared_local, tables,
                                  stale_dense, tokens, mask, key,
                                  method=method, layout=layout,
                                  sorted_layouts=sorted_layouts)
        shared_local = fam.apply_delta(shared_local, deltas)
        acc = {n: acc[n] + deltas[n] for n in acc}
        return (local, shared_local, acc), None

    (local, _, acc), _ = jax.lax.scan(one_sweep, (local, snapshot, zero),
                                      sweep_keys)
    # Local projection: the rules whose operands live in client state
    # (HDP's m_dk polytope) — shard-local and embarrassingly parallel.
    local = fam.local_project(local)
    return local, acc


def filter_push(fam: family_mod.ModelFamily, deltas: dict[str, Array],
                spec: ps.FilterSpec, key: Array,
                residual: dict[str, Array] | None = None):
    """Communication filter + error feedback on a client's accumulated
    delta (§5.3).  What the filter withholds is carried in ``residual`` to
    the next round, never dropped — count mass must be conserved or the
    statistics drift negative.

    Returns (sent, residual').  With the dense filter both pass through
    unchanged (and ``residual`` may stay ``None``).
    """
    if spec.kind == "dense":
        return deltas, residual
    if residual is not None:
        deltas = {n: deltas[n] + residual[n] for n in deltas}
    sent = {n: ps.filter_delta(v, spec, jax.random.fold_in(key, i))
            for i, (n, v) in enumerate(deltas.items())}
    return sent, {n: deltas[n] - sent[n] for n in deltas}


def filter_push_sparse(fam: family_mod.ModelFamily,
                       deltas: dict[str, Array], spec: ps.FilterSpec,
                       key: Array,
                       residual: dict[str, Array] | None = None
                       ) -> tuple[ps.SparseDelta, dict[str, Array] | None]:
    """:func:`filter_push` with a COO row-sliced result (DESIGN.md §12).

    The filter runs dense (identical arithmetic — same residual as the
    dense path), then the sent delta crosses the pytree boundary through
    ``ps.to_sparse_delta``: the non-zero-row union across delta stats,
    packed as (rows, values).  ``ps.from_sparse_delta`` reconstructs the
    sent delta bit-for-bit, so a transport shipping the sparse form is
    bit-exact with one shipping the dense form — while moving only the
    rows the filter (or the corpus' power-law row access) actually
    touched.  Host-side: the result shape is data-dependent.
    """
    sent, residual = filter_push(fam, deltas, spec, key, residual)
    return ps.to_sparse_delta(sent), residual


@dataclass(frozen=True)
class DistConfig:
    model: str = "lda"                 # any name in family.FAMILIES
    tau: int = 1                       # sweeps per sync round (staleness)
    alias_refresh_every: int = 1       # rounds between alias-table rebuilds
    filter: ps.FilterSpec = field(default_factory=ps.FilterSpec)
    project_every: int = 1             # rounds between projections (0 = never)
    # Parameter-server policy + vocabulary sharding (core.server): "bsp" |
    # "ssp:<bound>" | "async".  Under SPMD lock-step, async's immediate
    # per-client application degenerates to the same psum barrier as BSP
    # (the transport is a reduce); its distinguishing behavior here is the
    # non-blocking pull (always the live state, never a versioned cache).
    consistency: str = "bsp"
    n_server_shards: int = 1
    # "scan" | "sorted" (mhw only).  Note: under shard_map the sorted
    # layouts are rebuilt inside each sweep (per-shard token streams only
    # exist inside the mesh program, so they cannot be hoisted from here);
    # engine.Trainer's client-iterated driver hoists them once per shard.
    layout: str = "scan"


# --------------------------------------------------------------------------
# The distributed round
# --------------------------------------------------------------------------

def client_round(model_cfg, fam: family_mod.ModelFamily,
                 dist_cfg: DistConfig, local, snapshot, tables, stale_dense,
                 tokens, mask, key, method="mhw"):
    """One client's work for a sync round: ``tau`` sweeps against the frozen
    snapshot, applying its own deltas locally between sweeps (the paper's
    clients update their local replica immediately and push asynchronously),
    then the family's client-local constraint rules.

    Returns (local', accumulated_deltas).

    Thin wrapper over the shared round body (:func:`tau_sweeps`)
    preserving this module's historical per-sweep keying
    ``fold_in(key, s)``."""
    sweep_keys = jax.vmap(lambda s: jax.random.fold_in(key, s))(
        jnp.arange(dist_cfg.tau))
    return tau_sweeps(
        model_cfg, fam, local, snapshot, tables, stale_dense, tokens, mask,
        sweep_keys, method=method, layout=dist_cfg.layout)


def make_server(model_cfg, dist_cfg: DistConfig) -> server_mod.ParameterServer:
    """The round's :class:`~repro.core.server.ParameterServer` — family,
    vocabulary shard spec and consistency policy resolved from configs."""
    return server_mod.make_server(
        family_mod.get(dist_cfg.model), model_cfg.vocab_size,
        n_shards=dist_cfg.n_server_shards,
        consistency=dist_cfg.consistency)


def make_round_fn(model_cfg, dist_cfg: DistConfig, mesh: Mesh,
                  method: str = "mhw", data_axis: str = "data",
                  model_axis: str = "model",
                  server: server_mod.ParameterServer | None = None):
    """Build the jitted distributed round over an explicit parameter
    server.

    The round consumes a :class:`~repro.core.server.ParameterServer`
    (built from ``dist_cfg`` when not given) instead of raw
    ``shared``/``stale_dense`` pytrees: the returned function takes the
    server's :class:`~repro.core.server.ServerState` — canonical
    vocabulary-sharded statistics, versioned SSP cache, per-client
    clocks, changed-row accounting, and the resident alias proposal
    (host-refreshed via ``server.refresh_proposal``).

    Sharding contract (see module docstring):
      tokens/mask/local state — sharded over ``data`` on the document dim.
      shared stats            — canonical copy sharded over ``model`` rows
                                (the server's vocabulary row-ranges laid
                                over the physical row sharding).
    The round returns (local', server_state').

    Consistency: SSP's refresh predicate is evaluated in-trace from the
    server clocks (``max(clocks) − cache_version > bound``; ``max`` so a
    dead client cannot freeze the schedule — its protection is the zeroed
    push, §5.4); the blocking pull degenerates to a forced synchronous
    refresh under SPMD lock-step, as in the Trainer.
    """
    fam = family_mod.get(dist_cfg.model)
    if server is None:
        server = make_server(model_cfg, dist_cfg)
    n_clients = mesh.shape[data_axis]

    row_sharding = NamedSharding(mesh, P(model_axis, None))
    vec_sharding = NamedSharding(mesh, P())
    doc_sharding = NamedSharding(mesh, P(data_axis, None))

    def round_fn(local, state, tokens, mask, key, alive):
        """alive: (n_clients,) bool — failure-injection mask (paper §5.4)."""
        # 1. pull: the policy view made available to every client —
        #    expressed as a replication constraint (all-gather).  BSP and
        #    async pull the live canonical state; SSP the versioned cache.
        #    The replication constraint is applied to the assembled view
        #    *immediately*: letting the partitioner propagate the
        #    model-axis row sharding into the shard-concatenation corrupts
        #    values on multi-axis host meshes (observed on jax 0.4.37 —
        #    the concat operands get strided over the data axis); pinning
        #    the concat replicated sidesteps it, and every derived tensor
        #    (including the row-constrained canonical store below) is then
        #    partitioned correctly.
        canonical = jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, vec_sharding),
            server.assemble(state))
        if server.policy.caches:
            clock_now = state.clocks.max()
            do_refresh = clock_now - state.cache_version > server.policy.bound
            cache = jax.tree.map(
                lambda fresh, old: jnp.where(do_refresh, fresh, old),
                canonical, state.cache)
            version = jnp.where(do_refresh, clock_now, state.cache_version)
            snapshot = cache
            lag = server.reset_lag(state.client_lag, do_refresh)
        else:
            cache, version = state.cache, state.clocks.max()
            snapshot = canonical
            lag = None

        # 2-3. sample + filter, client-parallel over the data axis.
        def one_client(local_shard, tokens_shard, mask_shard, key_shard,
                       alive_shard, snapshot_rep, tables_rep, stale_rep,
                       lag_shard):
            # Read-my-writes SSP: each client samples the stale cache plus
            # its own deltas since the cache version (its lag shard).
            view = snapshot_rep if lag_shard is None else fam.apply_delta(
                snapshot_rep, {n: v[0] for n, v in lag_shard.items()})
            local2, deltas = client_round(
                model_cfg, fam, dist_cfg, local_shard, view,
                tables_rep, stale_rep, tokens_shard, mask_shard,
                key_shard[0], method)
            a = alive_shard[0].astype(jnp.float32)
            sent, _ = filter_push(
                fam, deltas, dist_cfg.filter,
                jax.random.fold_in(key_shard[0], 7))
            # 4. push: eventual-consistency reduce across clients.
            out = {name: jax.lax.psum(sent[name] * a, data_axis)
                   for name in fam.delta_names}
            lag2 = None if lag_shard is None else {
                n: v + deltas[n][None] * a for n, v in lag_shard.items()}
            return local2, out, lag2

        spec_local = jax.tree.map(lambda _: P(data_axis), local)
        lag_spec = None if lag is None else {n: P(data_axis) for n in lag}
        fn = jax.shard_map(
            one_client, mesh=mesh,
            in_specs=(spec_local, P(data_axis, None), P(data_axis, None),
                      P(data_axis), P(data_axis), P(), P(), P(), lag_spec),
            out_specs=(spec_local, P(), lag_spec),
            check_vma=False,
        )
        keys = jax.random.split(key, n_clients)
        local2, summed, lag = fn(local, tokens, mask, keys, alive, snapshot,
                                 state.tables, state.stale, lag)

        # Pushes always land on the canonical statistics (SSP relaxes
        # what clients *see*, never what the server *applies*).
        shared2 = fam.apply_delta(canonical, summed)

        # 5. distributed projection (Algorithm 2) over the model axis rows.
        #    The shard_mapped row-partitioned form is used for the
        #    single-slice server state (the historical layout); for
        #    multi-shard states the same rules+aggregates run replicated —
        #    mathematically identical (Algorithm 2 *distributes* this very
        #    computation), avoiding the partitioner defect noted at the
        #    pull: resharding the concat-derived statistics onto model-axis
        #    rows mid-program strides them over the wrong mesh axis
        #    (jax 0.4.37).
        stats = fam.stats_dict(shared2)
        if dist_cfg.project_every and server.spec.n_shards == 1:
            row_specs = {n: P(model_axis, None)
                         for n in stats if stats[n].ndim == 2}
            for n in stats:
                if stats[n].ndim != 2:
                    row_specs[n] = P()
            projectable = {n: v for n, v in stats.items()}
            # Only the rules whose every operand is a shared statistic run
            # here; local-operand rules were applied inside client_round.
            elem_rules = [r for r in fam.shared_rules
                          if projectable.get(r.a) is not None
                          and (r.b is None
                               or projectable.get(r.b) is not None)]
            stats = _project_alg2(projectable, elem_rules, fam.aggregates,
                                  mesh, model_axis, row_specs)
        elif dist_cfg.project_every:
            stats = projection.project(stats, fam.shared_rules,
                                       fam.aggregates)
        shared3 = fam.shared_from_dict(stats)

        # Canonical storage: keep the server copy sharded over model rows.
        # Only safe as a constraint when the server state is one dense
        # slice per stat (n_shards == 1): re-slicing a row-constrained
        # tensor into the per-shard outputs mis-lowers on multi-axis host
        # meshes (XLA strides the rows over the wrong axis — observed on
        # jax 0.4.37; same partitioner defect worked around at the pull
        # above), so multi-shard slices stay replicated and GSPMD places
        # them.
        if server.spec.n_shards == 1:
            shared3 = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, row_sharding if x.ndim == 2 else vec_sharding),
                shared3)
        state2 = server.load_dense(state, shared3)
        state2 = server.accumulate_mass(state2, summed)
        state2 = state2._replace(
            cache=cache, cache_version=version.astype(jnp.int32),
            client_lag=lag,
            clocks=state.clocks + alive.astype(jnp.int32))
        return local2, state2

    return jax.jit(round_fn)


def _project_alg2(stats, rules, aggregates, mesh, model_axis, row_specs):
    """Algorithm 2: rows partitioned over the model axis, projected locally,
    aggregates re-derived with a psum."""
    agg_outs = {a.out for a in aggregates}
    elem = {n: v for n, v in stats.items() if n not in agg_outs}

    in_specs = ({n: row_specs[n] for n in elem},)
    out_specs = {n: row_specs[n] for n in elem}
    for a in aggregates:
        out_specs[a.out] = P()

    def local_fn(e):
        out = dict(e)
        for rule in rules:
            if rule.a in out and (rule.b is None or rule.b in out):
                out = projection._apply_rule(out, rule)
        for a in aggregates:
            out[a.out] = jax.lax.psum(out[a.src].sum(a.axis), model_axis)
        return out

    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    result = fn(elem)
    # Preserve non-projected passthrough stats (e.g. theta0).
    for n, v in stats.items():
        if n not in result:
            result[n] = v
    return result


# --------------------------------------------------------------------------
# Compressed transport (paper §5.3 filter as an actual smaller collective)
# --------------------------------------------------------------------------

def sync_compressed(delta: Array, spec: ps.FilterSpec, key: Array,
                    data_axis: str = "data") -> Array:
    """Inside shard_map: compress this client's delta to (indices, values),
    all-gather the compressed representation, and scatter-add — the wire
    carries n_clients·k·K floats instead of V·K.  Returns the dense summed
    delta on every client."""
    comp = ps.compress_delta(delta, spec, key)
    all_idx = jax.lax.all_gather(comp.indices, data_axis)   # (C, k)
    all_val = jax.lax.all_gather(comp.values, data_axis)    # (C, k, K)
    dense = jnp.zeros_like(delta)
    return dense.at[all_idx.reshape(-1)].add(
        all_val.reshape(-1, delta.shape[1]))
