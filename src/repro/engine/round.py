"""The sync-round body, shared by every driver, compiled as one program.

The paper's client loop is *one tight loop*, not a sequence of dispatches:
pull → tau sweeps → filter → push → project → auxiliaries all live inside a
single compiled program per round (§5.1-§5.3).  The per-client round body
(``tau_sweeps`` + ``filter_push``) is defined once in
``repro.core.distributed`` (core owns the round semantics; this engine
module only adds the jit/donation/cadence machinery on top) and is consumed
three ways:

* by ``core.distributed.make_round_fn``'s shard_mapped mesh round
  (clients = data-axis shards),
* by :func:`trainer_round` — the whole-round function ``engine.Trainer``
  jits: clients unrolled inside the trace, the tau staleness loop as
  ``lax.scan``, projection under ``lax.cond`` (so the cadence does not
  retrace), and the incremental alias producer fused at the tail,
* by the Python reference loop ``Trainer._step_python`` — kept un-compiled
  as the dispatch-per-op parity oracle,

so the three drivers cannot drift apart.  (A fourth consumer,
``Trainer._step_remote`` — the ``transport="tcp"`` loop against
``repro.net`` shard servers — reuses :func:`filter_push` and the same
per-client key schedule, which is what keeps the wire path bit-exact
with the in-process round; see DESIGN.md §11.)

Since the ParameterServer redesign the round no longer threads raw
``shared``/``stale_dense`` pytrees: it takes a static
:class:`repro.core.server.ParameterServer` (family + shard spec +
consistency policy) and its traced, donated
:class:`repro.core.server.ServerState` — the vocabulary-sharded canonical
statistics, the versioned SSP pull cache, per-client clocks, the
per-shard changed-row accounting, and the resident alias proposal
(tables + stale dense matrix).  The pull/push semantics are the policy's:

* **BSP** — pull returns the canonical state as of the end of the
  previous round; pushes are summed at the round barrier.  Bit-exact
  with the PR-3 compiled round (assembly of the sharded store is pure
  concatenation; all arithmetic keeps its historical operation order).
* **SSP(s)** — pull returns the versioned stale cache; the traced
  ``do_refresh`` flag (the staleness-bound predicate, computed by the
  policy on the lock-step schedule) refreshes it from the canonical
  state, which in the simulation realizes SSP's blocking pull.
* **async** — each client's filtered push applies to the canonical view
  immediately, so later clients in the same round sample against it
  (Gauss-Seidel ordering); pulls never block.

Compiled-round invariants:

* **One trace per (family, layout, policy).**  Everything that varies
  between rounds — the round index, the fault-injection ``alive`` /
  ``push_ok`` masks (resolved host-side from a ``core.fault.FaultPlan``),
  the projection cadence, the SSP refresh flag — enters as *traced*
  scalars; and only the trace-relevant slice of the Trainer's config
  (:class:`RoundConfig`) keys the jit cache, so host-only knobs (fault
  plans, snapshot cadence/dirs) cannot force retraces either.  RNG keys
  are derived inside the trace with ``fold_in`` on the
  traced round index, reproducing the reference loop's keying
  bit-for-bit.  ``trace_count`` exposes a trace-time counter per
  (family, layout, policy) as the regression guard.
* **Donated buffers.**  The Trainer donates local states, the server
  state (canonical shards, cache, alias proposal) and residuals, so XLA
  updates the round state in place instead of allocating a second copy
  of the model every round.  Donation is skipped on backends that ignore
  it (CPU) to avoid spurious warnings.
* **Async pipelining.**  The round function never blocks; the Trainer only
  synchronizes at evaluation points, so consecutive rounds overlap with
  host-side Python (the dispatch of round r+1 rides on round r's compute).

Incremental alias maintenance (§3.3 l/n staleness, §5.1 producer/consumer):
after the push, the proposal rows that actually drifted are identified
from the server's per-shard changed-row accounting
(``ParameterServer.consume_changed_rows`` — the same magnitude-priority
machinery as the top-k communication filter, now accumulated across
pushes *since the last rebuild*), and only those rows are rebuilt via the
family's gather → build → scatter path (``ModelFamily.rebuild_alias_rows``)
into the server-resident tables.  Column aggregates (n_k, m_k, θ0) still
drift for untouched rows; that staleness is exactly what the MH
acceptance step corrects for, and a periodic full rebuild
(``alias_full_rebuild_every``) bounds it.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import ps

# Re-exported here for drivers/benchmarks that address the round body
# through the engine namespace.
from repro.core.distributed import (filter_push,  # noqa: F401
                                    filter_push_sparse, tau_sweeps)


@dataclass(frozen=True)
class RoundConfig:
    """The trace-relevant slice of ``TrainerConfig`` — the jit static the
    compiled round is keyed on.

    The Trainer's config also carries host-only knobs (snapshot cadence
    and directory, the fault plan, pull-retry budget, alias schedules,
    ``project_every``, …) that never enter the trace; keying the jit
    cache on the full ``TrainerConfig`` would retrace the identical round
    program whenever one of them changes (e.g. a baseline run vs. the
    same run with fault injection + snapshots — exactly the pairs
    tests/test_fault.py's recovery test compares).  This reduced static
    makes those pairs share one trace by construction."""

    layout: str
    method: str
    n_clients: int
    tau: int
    filter: ps.FilterSpec
    alias_rebuild_rows: int
    alias_rebuild_threshold: float | None

    @classmethod
    def from_trainer(cls, tcfg) -> "RoundConfig":
        return cls(layout=tcfg.layout, method=tcfg.method,
                   n_clients=tcfg.n_clients, tau=tcfg.tau,
                   filter=tcfg.filter,
                   alias_rebuild_rows=tcfg.alias_rebuild_rows,
                   alias_rebuild_threshold=tcfg.alias_rebuild_threshold)

# Trace-time counters, keyed (family_name, layout, policy): the
# compile-stability regression guard.  Bumped from inside the round body,
# which only executes at trace time — a steady-state Trainer must not grow
# these for its (family, layout, policy) triple.
_TRACE_COUNTS: dict[tuple[str, str, str], int] = {}


def trace_count(family_name: str, layout: str, policy: str = "bsp") -> int:
    """How many times the compiled round has been traced for this
    (family, layout, policy) — across all Trainer instances (the jit cache
    is shared, so a second Trainer with the same signature costs no
    trace)."""
    return _TRACE_COUNTS.get((family_name, layout, policy), 0)


# ---------------------------------------------------------------------------
# The Trainer's whole-round compiled program
# ---------------------------------------------------------------------------

def _round_impl(server, model_cfg, rcfg, incremental, state, locals_,
                residuals, shard_tokens, shard_masks, layouts, key, r,
                alive, push_ok, do_project, do_refresh):
    """One sync round as a single traced program.

    Static: server / model_cfg / rcfg (:class:`RoundConfig`) /
    incremental (hashable configs — the jit cache is shared across
    Trainer instances with equal signatures).  Traced: everything else,
    including the server state, the round index ``r``, the fault masks
    ``alive`` (client samples + keeps its state) and ``push_ok`` (its
    delta lands on the server — ``alive`` minus lost pushes; the two
    coincide except under a ``lost_push`` fault event), the projection
    flag ``do_project`` and the SSP refresh flag ``do_refresh``, so
    per-round cadence and fault injection never retrace.
    """
    fam, pol = server.family, server.policy
    key_ = (fam.name, rcfg.layout, pol.key)
    _TRACE_COUNTS[key_] = _TRACE_COUNTS.get(key_, 0) + 1

    # pull — policy view: BSP the canonical state, SSP the versioned stale
    # cache (refreshed under the traced staleness-bound flag; each client
    # then layers its own read-my-writes lag on top), async the live view
    # that immediate pushes below keep updating.
    snapshot, cache, version = server.pull_round(state, r, do_refresh)
    lag = server.reset_lag(state.client_lag, do_refresh)
    new_lag_rows = []
    zero = {n: jnp.zeros_like(fam.stats_dict(snapshot)[n])
            for n in fam.delta_names}
    total = zero
    new_locals, new_residuals = [], []
    # RNG keying is the historical reference-loop scheme (flat fold_in on
    # r*131 + c*17 + s / 7000+… / 9000+…), preserved so compiled and Python
    # rounds are bit-identical.  Note the flat offsets can collide across
    # phases once r*131 grows past 7000 (r ≳ 53) — a correlation quirk
    # inherited from PR 2, kept until a coordinated re-keying of both paths.
    for c in range(rcfg.n_clients):                         # clients unrolled
        sweep_keys = jax.vmap(
            lambda s, c=c: jax.random.fold_in(key, r * 131 + c * 17 + s)
        )(jnp.arange(rcfg.tau))
        loc, acc = tau_sweeps(
            model_cfg, fam, locals_[c],
            server.client_view(snapshot, lag, c), state.tables, state.stale,
            shard_tokens[c], shard_masks[c], sweep_keys, method=rcfg.method,
            layout=rcfg.layout,
            sorted_layouts=layouts[c] if layouts is not None else None)
        kf = jax.random.fold_in(key, 7000 + r * 131 + c)
        sent, res = filter_push(fam, acc, rcfg.filter, kf, residuals[c])
        # Fault injection (§5.4, core.fault): a dead client (alive=False)
        # is frozen — no state update, no push, identical to skipping it
        # entirely.  A lost push (alive but push_ok=False) keeps the
        # client's local update and residual but drops its delta on the
        # server floor: the mass is lost, not residual-carried — that is
        # the fault being modeled.
        a = alive[c]
        if lag is not None:
            # Read-my-writes: the pre-filter delta the client applied
            # locally rides in its lag row until the next refresh — it
            # reflects what the client *applied locally*, so it follows
            # `alive`, not `push_ok` (a lost push is still in the
            # client's own replica).
            new_lag_rows.append({
                n: jnp.where(a, lag[n][c] + acc[n], lag[n][c])
                for n in lag})
        new_locals.append(jax.tree.map(
            lambda new, old: jnp.where(a, new, old), loc, locals_[c]))
        new_residuals.append(
            res if res is None else jax.tree.map(
                lambda new, old: jnp.where(a, new, old), res, residuals[c]))
        pf = (a & push_ok[c]).astype(jnp.float32)
        total = {n: total[n] + sent[n] * pf for n in total}
        if pol.immediate:
            # async: the push lands now — the next client pulls it.
            snapshot = fam.apply_delta(
                snapshot, {n: sent[n] * pf for n in sent})

    # A client's server clock advances iff its push was applied.
    pushed = alive & push_ok
    if pol.immediate:                                       # push (applied)
        state = server.load_dense(state, snapshot)
        if incremental:
            state = server.accumulate_mass(state, total)
        state = state._replace(clocks=state.clocks + pushed.astype(jnp.int32))
    else:                                                   # push (barrier)
        state = server.push(state, total, pushed, track_mass=incremental)
    state = server.project(state, do_project)               # project
    dense = server.assemble(state)
    new_locals, dense = fam.post_round(                     # auxiliaries
        model_cfg, new_locals, dense, jax.random.fold_in(key, 9000 + r))
    state = server.load_dense(state, dense)
    if lag is not None:
        lag = {n: jnp.stack([row[n] for row in new_lag_rows])
               for n in lag}
    state = state._replace(cache=cache, cache_version=version,
                           client_lag=lag)

    if incremental:
        # Incremental alias producer: rebuild only the token-type rows
        # whose accumulated push mass drifted past the threshold, against
        # the end-of-round statistics (freshest possible proposal).
        rows, valid, state = server.consume_changed_rows(
            state, rcfg.alias_rebuild_rows, rcfg.alias_rebuild_threshold)
        tables, stale = fam.rebuild_alias_rows(
            model_cfg, server.assemble(state), state.tables, state.stale,
            rows, valid)
        state = state._replace(tables=tables, stale=stale)
    return tuple(new_locals), state, tuple(new_residuals)


@functools.lru_cache(maxsize=None)
def _jitted_round(donate: bool):
    """jit wrapper cache: donation covers the round-owned state (server
    state incl. alias proposal, locals, residuals) where the backend
    honors it."""
    donate_argnums = (4, 5, 6) if donate else ()
    return jax.jit(_round_impl, static_argnums=(0, 1, 2, 3),
                   donate_argnums=donate_argnums)


def lower_round(server, model_cfg, rcfg, incremental, *args):
    """The round program :func:`trainer_round` would dispatch for these
    arguments, lowered but not run (``jax.stages.Lowered``): for
    ``compile().memory_analysis()`` and HLO inspection."""
    donate = jax.default_backend() != "cpu"
    return _jitted_round(donate).lower(server, model_cfg, rcfg,
                                       bool(incremental), *args)


def trainer_round(server, model_cfg, rcfg, incremental, *args):
    """Dispatch one compiled sync round (see :func:`_round_impl` for the
    argument contract).  ``server`` is the static
    :class:`~repro.core.server.ParameterServer` and ``rcfg`` the static
    :class:`RoundConfig` (a full ``TrainerConfig`` is also accepted and
    reduced, so external callers keying on the old signature keep
    working); the first traced argument is the server's donated
    :class:`~repro.core.server.ServerState`.  Buffers are donated only
    where the backend honors donation — CPU ignores it and would warn on
    every compile."""
    if not isinstance(rcfg, RoundConfig):
        rcfg = RoundConfig.from_trainer(rcfg)
    donate = jax.default_backend() != "cpu"
    fn = _jitted_round(donate)
    return fn(server, model_cfg, rcfg, bool(incremental), *args)
