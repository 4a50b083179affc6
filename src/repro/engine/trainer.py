"""``Trainer``: the one driver loop for every ModelFamily (paper §5).

Replaces the hand-rolled per-model driver loops that used to live in
``examples/quickstart.py`` and ``examples/distributed_lvm.py``, and the
per-model adapter classes of ``core/distributed.py``: model specifics enter
only through the ``repro.core.family`` registry, so LDA / PDP / HDP — and
any future family — run the identical lifecycle:

    pull    — ask the parameter server for a snapshot under the configured
              consistency policy (BSP: fresh every round; SSP: a versioned
              stale cache, refreshed when the staleness bound is hit;
              async: the live, immediately-updated statistics),
    sample  — ``tau`` local Gibbs sweeps per client against the snapshot
              (scan oracle layout or the token-sorted tile-skipping fast
              path, selected by ``TrainerConfig.layout``), each client
              applying its own deltas locally (bounded staleness, §5.2),
    filter  — communication filter + error-feedback residuals on the
              accumulated delta (§5.3),
    push    — filtered deltas applied to the server's vocabulary-sharded
              canonical statistics (at the round barrier, or immediately
              per client under async),
    project — constraint projection on the shared polytope (§5.5) plus the
              family's client-local rules (e.g. HDP's 1 ≤ m_dk ≤ n_dk),
    (post)  — family auxiliary resampling (HDP CRT tables + θ0).

The shared statistics live behind an explicit
:class:`repro.core.server.ParameterServer` (DESIGN.md §9): the Trainer
holds the server's :class:`~repro.core.server.ServerState` — the
vocabulary-sharded canonical store, the SSP versioned pull cache,
per-client clocks, the per-shard changed-row accounting, and the resident
alias proposal — and ``TrainerConfig.consistency`` /
``TrainerConfig.n_server_shards`` select the policy and the sharding.
``Trainer.shared`` remains the assembled dense view for evaluation and
diagnostics.

Since PR 3 the whole round is **one compiled program**
(``repro.engine.round``, DESIGN.md §8): clients are unrolled inside the
trace, the tau loop is a ``lax.scan``, round state (locals / server state /
residuals) is donated so XLA updates it in place, and ``step()`` never
blocks — rounds pipeline asynchronously and the Trainer synchronizes only
at evaluation points.  ``TrainerConfig.compiled=False`` keeps the PR-2
Python reference loop (one dispatch per op, blocking per round) for parity
tests; it supports every consistency policy through the same server
methods, so it stays the parity oracle for all of them.

The Trainer also owns the alias-table maintenance (the l/n staleness rule
of §3.3 — the producer half of the paper's §5.1 producer/consumer design),
in three schedules:

* cadence (BSP/async default): tables fully rebuilt every
  ``alias_refresh_every`` rounds and reused in between;
* pull-coupled (SSP): the proposal is part of the pulled cache, so tables
  rebuild exactly when the versioned snapshot refreshes — this skipped
  work is what SSP saves over BSP;
* incremental (``alias_rebuild_threshold`` set): every compiled round ends
  by rebuilding *only* the token-type rows whose accumulated push mass
  exceeds the threshold (the server's per-shard changed-row accounting,
  consumed by ``ParameterServer.consume_changed_rows``), with a full
  rebuild every ``alias_full_rebuild_every`` rounds to bound the drift of
  the column aggregates that partial rebuilds leave stale.

Fault tolerance (§5.4, DESIGN.md §10): ``TrainerConfig.fault_plan``
injects scripted or seeded-random fault schedules
(``repro.core.fault.FaultPlan`` — crashes, stragglers, lost pushes,
failed pull refreshes), resolved host-side per round into traced masks so
chaos runs never retrace; ``snapshot_every``/``snapshot_dir`` write
periodic barrier-free snapshots of the full training pytree through
``repro.checkpoint.ckpt``, ``Trainer.restore()`` resumes from the latest
manifest (bit-exact under BSP), and a crashed client rejoins mid-run by
restoring its locals from the last snapshot and taking a forced-fresh
pull with its read-my-writes lag reset — under SSP a rejoining client is
just a maximally stale client taking its blocking refresh.

The loop is semantically the single-device simulation of
``core.distributed.make_round_fn`` (clients iterated instead of
shard_mapped) — both drive the same round body in ``engine.round``.
Projection runs per ``project_every`` for *every* family — the distributed
round's paper-production default; pass ``project_every=0`` to disable.
"""

from __future__ import annotations

import functools
import time
import warnings
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.core import family as family_mod
from repro.core import fault as fault_mod
from repro.core import ps
from repro.core import server as server_mod
from repro.data.synthetic import shard_corpus
from repro.engine import round as round_mod
from repro.kernels import mhw_fused

Array = jax.Array


@dataclass(frozen=True)
class TrainerConfig:
    """Driver-side knobs; model-side knobs live in the family's config."""

    layout: str = "scan"          # "scan" (oracle) | "sorted" (fast path)
    method: str = "mhw"           # "mhw" | "exact" (scan layout only)
    n_clients: int = 1
    tau: int = 1                  # local sweeps per sync round (staleness)
    # --- parameter server (DESIGN.md §9) --------------------------------
    # Consistency policy: "bsp" (bulk-synchronous, bit-exact with the
    # pre-server round) | "ssp:<bound>" (stale-synchronous: clients run up
    # to <bound> rounds ahead of a versioned cache) | "async" (immediate
    # pushes, non-blocking pulls).
    consistency: str = "bsp"
    # Vocabulary shards of the server's canonical statistics (row-range
    # sharding with a row→shard map; 1 = unsharded).
    n_server_shards: int = 1
    # --------------------------------------------------------------------
    # One compiled program per round (donated buffers, async dispatch);
    # False = the PR-2 Python reference loop (blocking, one jit per op).
    compiled: bool = True
    # --- alias maintenance (§3.3 l/n rule, §5.1 producer) ---------------
    # Rounds between full alias-table rebuilds; None → the model config's
    # value.  Cadence mode only (ignored when incremental mode is on, and
    # under SSP, whose proposal rebuilds on the pull-refresh schedule).
    alias_refresh_every: int | None = None
    # Incremental mode (compiled rounds only): when set, each round ends by
    # rebuilding the ≤ alias_rebuild_rows token-type rows whose accumulated
    # push L1 mass exceeds this threshold (0.0 = any changed row), inside
    # the compiled round.  A full rebuild still runs every
    # alias_full_rebuild_every rounds to bound aggregate drift.
    alias_rebuild_threshold: float | None = None
    alias_rebuild_rows: int = 64
    alias_full_rebuild_every: int = 16
    # --------------------------------------------------------------------
    project_every: int = 1        # rounds between projections (0 = never)
    filter: ps.FilterSpec = field(default_factory=ps.FilterSpec)
    # --- fault tolerance (§5.4, core.fault / checkpoint.ckpt) -----------
    # Scripted or seeded-random schedule of fault events (crashes with
    # kill-and-rejoin recovery, stragglers, lost pushes, failed pull
    # refreshes), resolved host-side per round — see core.fault.FaultPlan.
    fault_plan: fault_mod.FaultPlan | None = None
    # DEPRECATED shim: (client_id, from_round, to_round) compiles to the
    # one-event FaultPlan.crash(...) with a DeprecationWarning.  Mutually
    # exclusive with fault_plan.
    drop_client: tuple[int, int, int] | None = None
    # Periodic barrier-free snapshots of the full training pytree (server
    # state, per-client locals, residuals, clocks, RNG key, round index)
    # through checkpoint.ckpt: every `snapshot_every` rounds into
    # `snapshot_dir` (both must be set to enable).  Trainer.restore()
    # resumes from the latest manifest — bit-exact under BSP; crashed
    # clients also restore their locals from here when they rejoin.
    snapshot_every: int = 0
    snapshot_dir: str | None = None
    snapshot_name: str = "trainer"
    # Bounded retry for failed pull refreshes (the `failed_pull` fault):
    # the clients continue on the stale cache while the refresh is
    # retried each round; after this many consecutive failures the
    # refresh forces through (failover to a healthy replica).
    pull_retry_limit: int = 3
    # --- transport (DESIGN.md §11, repro.net) ---------------------------
    # "inproc" (default): the zero-copy in-process ParameterServer path.
    # "tcp": the shared statistics live in out-of-process shard servers
    # (repro.net.server) and every pull/push crosses the framed binary
    # wire protocol through a RemoteParameterServer.  BSP over tcp is
    # bit-exact with inproc BSP; HDP (cross-client post_round) is not
    # servable over the wire and raises.
    transport: str = "inproc"
    # "host:port" shard-server addresses (tcp only); together the servers
    # must tile the vocabulary rows [0, V).
    server_addrs: tuple[str, ...] = ()
    # Which global client ids THIS process runs (tcp only; None = all of
    # them — the single-process loopback case).  Other clients run in
    # other processes against the same servers; RNG streams key on the
    # global client id, so the union of processes reproduces the
    # in-process run exactly under BSP.
    local_clients: tuple[int, ...] | None = None
    # Encode pushes as COO row-sliced PUSH_SPARSE frames (tcp only;
    # DESIGN.md §12).  Bit-exact with dense pushes under BSP — the server
    # densifies and rides the same barrier path — but only the changed
    # rows cross the wire, which is the bytes/round win on zipf corpora.
    sparse_push: bool = False
    # Consecutive re-dial budget per server for dropped connections
    # during PULL (tcp only; the pull_retry_limit idiom on the wire).
    reconnect_limit: int = 3


@dataclass
class RunResult:
    perplexities: list[float] = field(default_factory=list)
    topics_per_word: list[float] = field(default_factory=list)
    iter_times: list[float] = field(default_factory=list)
    violations: list[float] = field(default_factory=list)


class Trainer:
    """Multi-client trainer for one registered model family.

    >>> cfg = lda.LDAConfig(n_topics=8, vocab_size=400)
    >>> t = Trainer(cfg, tokens, mask,
    ...             config=TrainerConfig(n_clients=4, layout="sorted",
    ...                                  consistency="ssp:2"))
    >>> result = t.run(n_rounds=20, eval_every=5)

    The family is resolved from the model config's type via the registry
    (``family.family_of``).  State lives on the instance: per-client local
    states, the parameter server's :class:`~repro.core.server.ServerState`
    (canonical vocabulary-sharded statistics, SSP pull cache, clocks,
    changed-row accounting, alias proposal), prebuilt sorted layouts (the
    token stream never changes between sweeps, so the per-shard sorts are
    hoisted out of the loop), and the error-feedback residuals of the
    communication filter.
    """

    def __init__(self, model_cfg, tokens: Array, mask: Array, *,
                 config: TrainerConfig = TrainerConfig(),
                 key: Array | None = None):
        if config.layout not in ("scan", "sorted"):
            raise ValueError(f"unknown layout {config.layout!r}")
        if config.layout == "sorted" and config.method != "mhw":
            raise ValueError("layout='sorted' requires method='mhw'")
        if config.alias_rebuild_threshold is not None and not config.compiled:
            raise ValueError("incremental alias rebuilds "
                             "(alias_rebuild_threshold) require compiled "
                             "rounds; the reference loop only supports the "
                             "alias_refresh_every cadence")
        if config.transport not in ("inproc", "tcp"):
            raise ValueError(f"unknown transport {config.transport!r}; "
                             "expected 'inproc' or 'tcp'")
        if config.transport == "inproc" and (
                config.server_addrs or config.local_clients is not None
                or config.sparse_push):
            raise ValueError("server_addrs / local_clients / sparse_push "
                             "are tcp-only knobs; set transport='tcp'")
        self.cfg = model_cfg
        self.tcfg = config
        self.fault_plan = self._resolve_fault_plan(config)
        self.family = family_mod.family_of(model_cfg)
        if config.transport == "tcp":
            self._validate_tcp(config)
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.tokens = jnp.asarray(tokens)
        self.mask = jnp.asarray(mask)
        self.n_tokens = int(np.asarray(mask).sum())
        # Which global client ids this process runs: all of them inproc
        # (and for single-process tcp); a subset when this Trainer is one
        # of several worker processes sharing the wire servers.
        self.local_clients = (tuple(range(config.n_clients))
                              if config.local_clients is None
                              else tuple(sorted(config.local_clients)))
        remote_mode = config.transport == "tcp"
        local_set = set(self.local_clients)

        shards = shard_corpus(np.asarray(tokens), np.asarray(mask),
                              config.n_clients)
        self.shards = [(jnp.asarray(t), jnp.asarray(m)) for t, m in shards]

        # init() builds per-shard stats; the canonical shared state is
        # their sum (replicated stats — e.g. θ0 — taken from shard 0).
        # Over the wire, each process computes only its local clients'
        # contributions and INIT-pushes them — the servers perform the
        # same ascending-client-id merge at the INIT barrier.
        self.locals_: list = [None] * config.n_clients
        shared = None
        init_stats: dict[int, Any] = {}
        for c, (t, m) in enumerate(self.shards):
            if remote_mode and c not in local_set:
                continue
            loc, sh = self.family.init_state(model_cfg, t, m,
                                             jax.random.fold_in(self.key, c))
            self.locals_[c] = loc
            if remote_mode:
                init_stats[c] = sh
            else:
                shared = sh if shared is None else self._merge_shared(shared, sh)

        # The parameter server: vocabulary-sharded canonical statistics
        # under the configured consistency policy (DESIGN.md §9) — held
        # in-process, or behind the framed wire protocol (DESIGN.md §11).
        self.remote = None
        if remote_mode:
            from repro.net import client as net_client
            self.server = None
            self.pstate = None
            self.remote = net_client.RemoteParameterServer(
                config.server_addrs, family=self.family,
                n_clients=config.n_clients,
                vocab_size=model_cfg.vocab_size,
                consistency=config.consistency,
                sparse_push=config.sparse_push,
                reconnect_limit=config.reconnect_limit,
                local_clients=self.local_clients)
            for c in sorted(init_stats):
                self.remote.init_push(c, init_stats[c])
            stats_template = self.family.stats_dict(
                init_stats[self.local_clients[0]])
        else:
            self.server = server_mod.make_server(
                self.family, model_cfg.vocab_size,
                n_shards=config.n_server_shards,
                consistency=config.consistency)
            self.pstate = self.server.init_state(shared, config.n_clients)
            stats_template = None
        # Host mirror of the SSP cache version (the lock-step pull
        # schedule is deterministic, so the host never needs to sync to
        # decide a refresh) and a rebuild counter for tests/benchmarks.
        self._host_version: int | None = None
        self.alias_builds = 0
        # Wire-transport client state: the pulled versioned snapshot (the
        # SSP cache at the client edge), the alias proposal built from it,
        # and each local client's own read-my-writes lag row.
        self._tcp_snapshot = None
        self._tcp_version: int | None = None
        self._tcp_tables = None
        self._tcp_stale = None
        self._lag: dict[int, dict[str, Array]] | None = None
        if remote_mode and self.remote.policy.caches:
            self._lag = {
                c: {n: jnp.zeros_like(stats_template[n])
                    for n in self.family.delta_names}
                for c in self.local_clients}

        # Hoisted sorted layouts: one tuple of per-chunk layouts per shard
        # (local clients only — a worker never sweeps remote shards).
        self.layouts = None
        if config.layout == "sorted":
            self.layouts = tuple(
                self.family.build_sorted_layouts(model_cfg, t, m)
                if c in local_set else None
                for c, (t, m) in enumerate(self.shards))
        # Static counters of the fused sweep kernel's grid over one round's
        # chunks: the steps it walks, the (batch tile, vocab tile) pairs
        # among them that hold draws, and the single-pass column blocks a
        # live step's staging contracts its one-hot with.
        self.sweep_grid_steps = self.sweep_live_pairs = 0
        self.sweep_stage_parts = 0
        if self.layouts is not None:
            e = self.family.n_outcomes(model_cfg)
            self.sweep_stage_parts = mhw_fused.stage_parts(e)
            n_ktiles = e // min(self.family.sorted_tile_k(model_cfg) or e, e)
            lays = [lay for ls in self.layouts if ls is not None
                    for lay in ls]
            self.sweep_grid_steps = n_ktiles * sum(
                mhw_fused.n_pairs(lay.vstart.shape[0], lay.hist.shape[0])
                for lay in lays)
            self.sweep_live_pairs = int(sum(jnp.sum(lay.vcount)
                                            for lay in lays))

        self.alias_refresh_every = (
            config.alias_refresh_every
            if config.alias_refresh_every is not None
            else getattr(model_cfg, "alias_refresh_every", 1))
        # Error-feedback residuals (ps.residual_update): what a
        # communication filter withholds is carried to the next round,
        # never dropped — count mass must be conserved or the statistics
        # drift negative (paper §5.3's eventual-consistency contract).
        # Zero-initialized (not None) so the compiled round's pytree
        # structure is stable from the first call.
        if config.filter.kind != "dense":
            stats = (stats_template if remote_mode
                     else self.family.stats_dict(self.shared))
            self.residuals: list = [
                {n: jnp.zeros_like(stats[n]) for n in self.family.delta_names}
                if (not remote_mode or c in local_set) else None
                for c in range(config.n_clients)]
        else:
            self.residuals = [None] * config.n_clients
        self.round_idx = 0
        # Fault-tolerance host state: the reduced jit static (host-only
        # knobs like the fault plan and snapshot cadence must not key the
        # trace cache), the failed-pull retry budget, and observability
        # counters for tests/benchmarks.
        self._rcfg = round_mod.RoundConfig.from_trainer(config)
        self._pull_retries = 0
        self.pull_failures = 0
        self.rejoins = 0

    def _validate_tcp(self, config: TrainerConfig) -> None:
        """Reject TrainerConfig combinations the wire transport cannot
        honor (each names its inproc-only machinery).

        ``fault_plan`` / ``drop_client`` and ``snapshot_every`` used to be
        rejected here too; since the wire grew idempotent replay, ghost
        pushes and worker-side snapshots (DESIGN.md §13) the same fault
        schedules and snapshot cadences run over tcp — simulated faults
        ride the wire as ghost barrier frames, and a killed worker
        process restores from its own snapshot with ``Trainer.restore``.
        """
        if not config.server_addrs:
            raise ValueError("transport='tcp' requires server_addrs "
                             "(host:port shard servers)")
        if config.alias_rebuild_threshold is not None:
            raise ValueError("incremental alias rebuilds are inproc "
                             "compiled-round machinery; tcp rebuilds from "
                             "the pulled snapshot on the refresh schedule")
        if type(self.family).post_round is not family_mod.ModelFamily.post_round:
            raise NotImplementedError(
                f"family {self.family.name!r} overrides post_round "
                "(cross-client auxiliary resampling at the barrier) — not "
                "servable over the wire; use transport='inproc'")
        if config.local_clients is not None:
            lc = tuple(config.local_clients)
            if not lc or len(set(lc)) != len(lc) or \
                    not all(0 <= c < config.n_clients for c in lc):
                raise ValueError(
                    f"local_clients {lc} must be distinct ids in "
                    f"[0, {config.n_clients})")

    @staticmethod
    def _resolve_fault_plan(config: TrainerConfig) -> fault_mod.FaultPlan:
        """The run's fault plan: ``config.fault_plan``, or the deprecated
        ``drop_client`` tuple compiled to a one-event crash plan."""
        if config.drop_client is not None:
            if config.fault_plan is not None:
                raise ValueError(
                    "TrainerConfig.drop_client and TrainerConfig.fault_plan "
                    "are mutually exclusive — drop_client is the deprecated "
                    "shim; express the crash as FaultPlan.crash(...) inside "
                    "the plan instead")
            warnings.warn(
                "TrainerConfig.drop_client is deprecated; use "
                "fault_plan=FaultPlan.crash(client, start, stop) "
                "(repro.core.fault) — drop_client compiles to exactly that "
                "one-event plan", DeprecationWarning, stacklevel=3)
            return fault_mod.FaultPlan.from_drop_client(config.drop_client)
        if config.fault_plan is None:
            return fault_mod.FaultPlan.none()
        if config.fault_plan.max_client >= config.n_clients:
            raise ValueError(
                f"fault plan names client {config.fault_plan.max_client} "
                f"but the run has only {config.n_clients} clients")
        return config.fault_plan

    # ------------------------------------------------------------------
    @property
    def shared(self):
        """The assembled dense shared statistics (the server's canonical
        snapshot — always fresh, regardless of the pull policy).  Over
        tcp this is a SNAPSHOT round-trip that first waits for every
        stepped round to finalize at the servers."""
        if self.remote is not None:
            return self.remote.snapshot(min_round=self.round_idx)
        return self.server.snapshot(self.pstate)

    @shared.setter
    def shared(self, value):
        if self.remote is not None:
            raise ValueError("Trainer.shared is read-only over tcp — the "
                             "shard servers own the canonical state")
        self.pstate = self.server.load_dense(self.pstate, value)

    @property
    def tables(self):
        return self._tcp_tables if self.remote is not None \
            else self.pstate.tables

    @property
    def stale(self):
        return self._tcp_stale if self.remote is not None \
            else self.pstate.stale

    @property
    def clocks(self) -> np.ndarray:
        """Per-client round clocks as tracked by the server."""
        if self.remote is not None:
            return self.remote.clock()[1]
        return np.asarray(self.pstate.clocks)

    @property
    def _incremental(self) -> bool:
        return self.tcfg.alias_rebuild_threshold is not None

    @property
    def round_traces(self) -> int:
        """Trace count of this Trainer's compiled round signature — the
        compile-stability guard (steady-state rounds must not grow it).
        The jit cache is shared, so another Trainer with an equal signature
        reuses the trace."""
        policy = (self.remote.policy if self.remote is not None
                  else self.server.policy)
        return round_mod.trace_count(self.family.name, self.tcfg.layout,
                                     policy.key)

    def _merge_shared(self, acc, sh):
        fam = self.family
        a, b = fam.stats_dict(acc), fam.stats_dict(sh)
        merged = {n: (a[n] if n in fam.replicated_stats or a[n].shape == ()
                      else a[n] + b[n])
                  for n in a}
        return fam.shared_from_dict(merged)

    def _pull_refresh(self, r: int, *, force: bool = False,
                      failed: bool = False) -> bool:
        """The policy's pull schedule for round ``r`` (host mirror of the
        traced predicate; lock-step clients make it deterministic).  Under
        SSP a True here is the blocking pull: the bound r − version would
        be exceeded, so the client waits for a fresh snapshot.

        ``force`` is the rejoin protocol's forced-fresh pull (retried
        until it succeeds, so it overrides a concurrent ``failed``).
        ``failed`` is the ``failed_pull`` fault: a due refresh degrades
        gracefully — the clients continue on the stale cache (past the
        staleness bound; that is the degradation) and the refresh is
        retried next round, bounded by ``TrainerConfig.pull_retry_limit``
        consecutive failures before it forces through anyway."""
        pol = self.server.policy
        if not pol.caches:
            return True
        if not (force or pol.needs_refresh(r, self._host_version)):
            return False
        if failed and not force \
                and self._pull_retries < self.tcfg.pull_retry_limit:
            self._pull_retries += 1
            self.pull_failures += 1
            return False
        self._pull_retries = 0
        self._host_version = r
        return True

    def _refresh_alias(self, do_refresh: bool) -> None:
        srv, r = self.server, self.round_idx
        if self._incremental:
            # Incremental mode: partial rebuilds happen inside the compiled
            # round; the periodic full rebuild re-anchors the rows whose
            # *aggregate* factors (n_k, m_k, θ0) drifted without row pushes.
            if self.pstate.tables is not None and not (
                    self.tcfg.alias_full_rebuild_every
                    and r % self.tcfg.alias_full_rebuild_every == 0):
                return
        elif srv.policy.caches:
            # SSP: the proposal is part of the pulled versioned cache —
            # rebuilt exactly when the pull refreshes.  The skipped
            # rebuilds on stale rounds are the measured throughput win.
            if self.pstate.tables is not None and not do_refresh:
                return
        elif self.pstate.tables is not None and \
                r % self.alias_refresh_every != 0:
            return
        self.pstate = srv.refresh_proposal(self.cfg, self.pstate)
        self.alias_builds += 1

    def _round_faults(self) -> fault_mod.RoundFaults:
        """This round's host-side fault resolution, with the rejoin
        protocol already executed for any client whose crash window ends
        now: restore its locals (and residuals) from the latest snapshot
        when snapshots are enabled — otherwise its frozen in-memory state
        doubles as the implicit snapshot — and clear its read-my-writes
        lag; the caller then forces a fresh pull for the round."""
        rf = self.fault_plan.resolve(self.round_idx, self.tcfg.n_clients)
        if rf.rejoining:
            self._rejoin(rf.rejoining)
        return rf

    def _rejoin(self, clients: tuple[int, ...]) -> None:
        snap = self._load_latest_snapshot()
        for c in clients:
            if snap is not None and (self.remote is None
                                     or c in self.local_clients):
                self.locals_[c] = snap["locals"][c]
                if self.residuals[c] is not None:
                    self.residuals[c] = snap["residuals"][c]
            if self.remote is not None:
                # Over the wire the rejoin protocol is a REJOIN frame
                # (clear pending pushes + open mutation-log entries, lift
                # any eviction) followed by a forced-fresh pull, which
                # the caller triggers via the rejoining mask.
                if c in self.local_clients:
                    self.remote.rejoin(c)
            else:
                self.pstate = self.server.rejoin_client(self.pstate, c)
        self.rejoins += len(clients)

    def _load_latest_snapshot(self) -> dict | None:
        """The newest readable snapshot, or None when snapshotting is off
        or nothing has been written yet (a client crashing before the
        first snapshot recovers from its frozen init-equivalent state)."""
        if not self.tcfg.snapshot_dir:
            return None
        try:
            return ckpt.restore_latest(self.tcfg.snapshot_dir,
                                       self.tcfg.snapshot_name,
                                       self.snapshot_state())
        except FileNotFoundError:
            return None
        except ckpt.CorruptSnapshotError as e:
            # Every written snapshot is unreadable: degrade to the frozen
            # in-memory state rather than aborting the run (§5.4), loudly.
            warnings.warn(f"rejoin falling back to in-memory state: {e}",
                          RuntimeWarning, stacklevel=2)
            return None

    def _sync(self) -> None:
        """Block until every in-flight round has materialized (eval
        points; compiled rounds otherwise pipeline asynchronously).  Over
        tcp: wait for the servers' barrier to finalize every stepped
        round (the CLOCK message with min_round)."""
        if self.remote is not None:
            self.remote.clock(min_round=self.round_idx)
            return
        jax.block_until_ready(jax.tree.leaves(self.pstate.shards[0])[0])

    # ------------------------------------------------------------------
    def step(self) -> None:
        """One sync round: (faults) → pull → sample → filter → push →
        project → (snapshot).

        Compiled mode (default): one jitted program, donated buffers, no
        host sync — the call returns as soon as the round is dispatched.
        Fault events (``TrainerConfig.fault_plan``) resolve host-side
        into traced masks, and the periodic snapshot
        (``snapshot_every``/``snapshot_dir``) is barrier-free: the host
        blocks only to serialize the buffers it writes while further
        rounds keep dispatching.
        """
        with jax.profiler.TraceAnnotation("repro.train.step",
                                          round=self.round_idx):
            if self.remote is not None:
                self._step_remote()
            elif not self.tcfg.compiled:
                self._step_python()
            else:
                self._step_compiled()
            if self.tcfg.snapshot_every and self.tcfg.snapshot_dir \
                    and self.round_idx % self.tcfg.snapshot_every == 0:
                self.save_snapshot()

    def _step_compiled(self) -> None:
        tcfg = self.tcfg
        r = self.round_idx
        rf = self._round_faults()
        do_refresh = self._pull_refresh(r, force=bool(rf.rejoining),
                                        failed=rf.pull_failed)
        self._refresh_alias(do_refresh)

        do_project = bool(tcfg.project_every
                          and r % tcfg.project_every == 0)
        locals2, self.pstate, residuals2 = round_mod.trainer_round(
            *self._round_args(rf.alive_mask, rf.push_mask, do_project,
                              do_refresh))
        self.locals_ = list(locals2)
        self.residuals = list(residuals2)
        self.round_idx += 1

    def _round_args(self, alive, push_ok, do_project, do_refresh) -> tuple:
        return (self.server, self.cfg, self._rcfg, self._incremental,
                self.pstate, tuple(self.locals_), tuple(self.residuals),
                tuple(t for t, _ in self.shards),
                tuple(m for _, m in self.shards),
                self.layouts, self.key, np.int32(self.round_idx), alive,
                push_ok, np.bool_(do_project), np.bool_(do_refresh))

    def lower_round(self):
        """The compiled round program ``step`` dispatches, lowered for the
        current state and not run (``jax.stages.Lowered``) — for
        ``compile().memory_analysis()`` and HLO inspection.  Needs the
        alias proposal, so call it after the first ``step``."""
        if self.remote is not None or not self.tcfg.compiled:
            raise ValueError("only the in-process compiled round lowers")
        everyone = np.ones(self.tcfg.n_clients, bool)
        return round_mod.lower_round(
            *self._round_args(everyone, everyone, True, True))

    def _refresh_alias_tcp(self, refreshed: bool) -> None:
        """Alias maintenance at the client edge of the wire: the proposal
        is built from the pulled versioned snapshot — under SSP exactly
        when the pull refreshed (the proposal rides the cache, as
        inproc); under BSP/async on the ``alias_refresh_every`` cadence.
        Bit-exact with the inproc schedule: the pulled snapshot at
        version r carries the same statistics ``refresh_proposal`` reads
        from the canonical store at round r."""
        r = self.round_idx
        if self._tcp_tables is not None:
            if self.remote.policy.caches:
                if not refreshed:
                    return
            elif r % self.alias_refresh_every != 0:
                return
        self._tcp_tables, self._tcp_stale = self.family.build_alias(
            self.cfg, self._tcp_snapshot)
        self.alias_builds += 1

    def _step_remote(self) -> None:
        """One sync round over the wire (DESIGN.md §11): the
        ``_step_python`` loop with the server side of each phase replaced
        by protocol messages — pull is a versioned cache refresh (the
        server answers NOT_MODIFIED within the staleness bound), push is
        a delta frame finalized at the server's round barrier (summed
        there in ascending client id — the reference loop's op order),
        projection runs server-side on the same cadence, and the
        read-my-writes lag is this process's own rows.  RNG streams key
        on the *global* client id, so M worker processes jointly
        reproduce the single-process run — bit-exactly under BSP.

        Fault injection (DESIGN.md §13): the same host-side
        ``fault_plan`` resolution as the inproc loops, with the masks
        expressed as wire frames — a dead or push-losing client fills
        its barrier slot with a *ghost* push (counted for completeness,
        no delta, no clock tick), bit-exact with the inproc alive/push
        masks; a ``failed_pull`` skips the due cache refresh and keeps
        sampling the stale snapshot, bounded by ``pull_retry_limit``;
        a rejoin restores locals from the latest snapshot, REJOINs at
        the servers and takes a forced-fresh pull."""
        fam, cfg, tcfg = self.family, self.cfg, self.tcfg
        r = self.round_idx
        pol = self.remote.policy
        rf = self._round_faults()
        force = bool(rf.rejoining)
        skip_pull = False
        if rf.pull_failed and not force and pol.caches \
                and self._tcp_snapshot is not None \
                and pol.needs_refresh(r, self._host_version) \
                and self._pull_retries < tcfg.pull_retry_limit:
            # The due refresh RPC "fails": continue on the stale cache
            # past the bound (that is the degradation) and retry next
            # round — the inproc failed_pull idiom on the wire.
            self._pull_retries += 1
            self.pull_failures += 1
            skip_pull = True
        refreshed = False
        if not skip_pull:
            snapshot_new, version, refreshed = self.remote.pull(
                r, None if force else (
                    self._tcp_version if pol.caches else None))
            if refreshed:
                self._tcp_snapshot = snapshot_new
                self._tcp_version = version
                self._host_version = version
                self._pull_retries = 0
                if self._lag is not None:
                    # Fresh cache already contains every applied push:
                    # zero the read-my-writes accumulators
                    # (srv.reset_lag).
                    self._lag = {
                        c: {n: jnp.zeros_like(v) for n, v in row.items()}
                        for c, row in self._lag.items()}
        snapshot = self._tcp_snapshot
        self._refresh_alias_tcp(refreshed)

        for c in self.local_clients:
            if not rf.alive[c]:
                # Dead client (§5.4): frozen locals, no contribution —
                # but the servers' round barrier still needs its slot,
                # so a ghost frame rides the wire in its place.
                self.remote.push_ghost(r, c)
                continue
            t, m = self.shards[c]
            lays = self.layouts[c] if self.layouts is not None else None
            local_shared = (fam.apply_delta(snapshot, self._lag[c])
                            if self._lag is not None else snapshot)
            acc = None
            for s in range(tcfg.tau):                # sample (τ sweeps)
                k = jax.random.fold_in(self.key, r * 131 + c * 17 + s)
                self.locals_[c], d = fam.sweep(
                    cfg, self.locals_[c], local_shared, self._tcp_tables,
                    self._tcp_stale, t, m, k, method=tcfg.method,
                    layout=tcfg.layout, sorted_layouts=lays)
                local_shared = fam.apply_delta(local_shared, d)
                acc = d if acc is None else {n: acc[n] + d[n] for n in d}
            self.locals_[c] = fam.local_project(self.locals_[c])
            if self._lag is not None:
                # Pre-filter delta rides in the client's own lag row until
                # the next refresh (read-my-writes) — including when the
                # push below is lost (the delta is in the replica
                # regardless), exactly the reference loop.
                self._lag[c] = {n: self._lag[c][n] + acc[n] for n in acc}
            kf = jax.random.fold_in(self.key, 7000 + r * 131 + c)
            acc, self.residuals[c] = round_mod.filter_push(   # filter
                fam, acc, tcfg.filter, kf, self.residuals[c])
            if not rf.push_ok[c]:
                # Lost push (§5.4): the filtered delta is dropped on the
                # floor; a ghost fills the barrier slot in its place.
                self.remote.push_ghost(r, c)
                continue
            self.remote.push(r, c, acc)              # push (delta frame)
        self.round_idx += 1

    def close(self) -> None:
        """Release the wire connections (tcp transport); no-op inproc."""
        if getattr(self, "remote", None) is not None:
            self.remote.close()

    def _step_python(self) -> None:
        """The PR-2 reference loop: one jitted dispatch per sweep/op and a
        device sync every round.  Semantically identical to the compiled
        round (same RNG keying and server methods — integer count
        statistics match bit-exactly for every consistency policy); kept
        as the parity oracle."""
        fam, cfg, tcfg = self.family, self.cfg, self.tcfg
        srv, pol = self.server, self.server.policy
        r = self.round_idx
        rf = self._round_faults()
        do_refresh = self._pull_refresh(r, force=bool(rf.rejoining),
                                        failed=rf.pull_failed)
        self._refresh_alias(do_refresh)
        state = self.pstate
        pushed = rf.alive_mask & rf.push_mask

        snapshot, cache, version = srv.pull_round(state, r, do_refresh)
        lag = srv.reset_lag(state.client_lag, do_refresh)
        total_delta = None
        for c in range(tcfg.n_clients):
            if not rf.alive[c]:
                continue   # dead client: frozen, contributes nothing
            t, m = self.shards[c]
            lays = self.layouts[c] if self.layouts is not None else None
            local_shared = srv.client_view(snapshot, lag, c)
            acc = None
            for s in range(tcfg.tau):                # sample (τ sweeps)
                k = jax.random.fold_in(self.key, r * 131 + c * 17 + s)
                self.locals_[c], d = fam.sweep(
                    cfg, self.locals_[c], local_shared, state.tables,
                    state.stale, t, m, k, method=tcfg.method,
                    layout=tcfg.layout, sorted_layouts=lays)
                local_shared = fam.apply_delta(local_shared, d)
                acc = d if acc is None else {n: acc[n] + d[n] for n in d}
            # Client-local constraint rules (e.g. HDP's table-count
            # polytope 1 ≤ m_dk ≤ n_dk) — applied every round, exactly as
            # the distributed round does.
            self.locals_[c] = fam.local_project(self.locals_[c])
            if lag is not None:
                # Read-my-writes: the pre-filter delta the client applied
                # locally rides in its lag row until the next refresh —
                # including when its push below is lost (the delta is in
                # the client's replica regardless).
                lag = {n: lag[n].at[c].add(acc[n]) for n in lag}
            kf = jax.random.fold_in(self.key, 7000 + r * 131 + c)
            acc, self.residuals[c] = round_mod.filter_push(   # filter (§5.3)
                fam, acc, tcfg.filter, kf, self.residuals[c])
            if not rf.push_ok[c]:
                continue   # lost push (§5.4): the filtered delta is
                           # dropped on the floor, not residual-carried
            total_delta = acc if total_delta is None else {
                n: total_delta[n] + acc[n] for n in acc}
            if pol.immediate:                        # async: push lands now
                snapshot = fam.apply_delta(snapshot, acc)

        if pol.immediate:
            state = srv.load_dense(state, snapshot)
            state = state._replace(
                clocks=state.clocks + jnp.asarray(pushed, jnp.int32))
        elif total_delta is not None:                # push (barrier)
            state = srv.push(state, total_delta, jnp.asarray(pushed))
        do_project = bool(tcfg.project_every
                          and r % tcfg.project_every == 0)
        state = srv.project(state, do_project)       # project
        dense = srv.assemble(state)
        locals2, dense = fam.post_round(             # family auxiliaries
            cfg, self.locals_, dense,
            jax.random.fold_in(self.key, 9000 + r))
        self.locals_ = list(locals2)
        state = srv.load_dense(state, dense)
        self.pstate = state._replace(cache=cache, cache_version=version,
                                     client_lag=lag)
        self._sync()
        self.round_idx += 1

    # ---------------------------------------------------- snapshot/restore
    def snapshot_state(self) -> dict:
        """The full training pytree a snapshot carries (§5.4): the
        server's :class:`~repro.core.server.ServerState` (canonical
        shards, SSP cache + per-client clocks, changed-row accounting,
        resident alias proposal), per-client locals and residuals, the
        run RNG key, and the host-side schedule scalars (round index,
        cache-version mirror, retry/build counters) as int32 leaves —
        everything a bit-exact BSP resume needs.

        Over tcp the shard servers own the canonical statistics (they
        snapshot themselves — SNAPSHOT_WRITE), so the worker snapshot
        carries the *client edge* instead: this process's locals and
        residuals, the pulled versioned snapshot, the alias proposal
        built from it, and the read-my-writes lag rows.  A restored
        worker resumes mid-run against the still-live servers
        (``Trainer.restore``), bit-exactly under BSP with
        ``snapshot_every=1``."""
        hv = -1 if self._host_version is None else self._host_version
        state = {
            "locals": tuple(self.locals_),
            "residuals": tuple(self.residuals),
            "key": self.key,
            "round_idx": np.int32(self.round_idx),
            "host_version": np.int32(hv),
            "alias_builds": np.int32(self.alias_builds),
            "pull_retries": np.int32(self._pull_retries),
        }
        if self.remote is not None:
            if self._tcp_snapshot is None or self._tcp_tables is None:
                raise ValueError(
                    "tcp snapshot before the first pull: the client edge "
                    "(pulled snapshot + alias proposal) is empty — step "
                    "at least one round first")
            tv = -1 if self._tcp_version is None else self._tcp_version
            state.update({
                "tcp_snapshot": self._tcp_snapshot,
                "tcp_version": np.int32(tv),
                "tcp_tables": self._tcp_tables,
                "tcp_stale": self._tcp_stale,
                "tcp_lag": self._lag,
            })
        else:
            state["server"] = self.pstate
        return state

    def save_snapshot(self) -> str:
        """Write a snapshot of :meth:`snapshot_state` at the current
        round through ``checkpoint.ckpt`` (write-then-rename manifest).
        Barrier-free in the §5.4 sense: no ``_sync()`` — the host blocks
        only to serialize the buffers it writes, while already-dispatched
        rounds keep running."""
        if not self.tcfg.snapshot_dir:
            raise ValueError("TrainerConfig.snapshot_dir is not set")
        return ckpt.save(self.tcfg.snapshot_dir, self.tcfg.snapshot_name,
                         self.round_idx, self.snapshot_state())

    @classmethod
    def restore(cls, model_cfg, tokens: Array, mask: Array, *,
                config: TrainerConfig = TrainerConfig(),
                snapshot_dir: str | None = None,
                step: int | None = None,
                key: Array | None = None) -> "Trainer":
        """Resume a run from its latest snapshot manifest.

        Builds a Trainer exactly as ``__init__`` would (same config, same
        corpus — sharding and sorted layouts are re-derived
        deterministically), then overwrites its round state from the
        newest *readable* snapshot in ``snapshot_dir`` (defaulting to
        ``config.snapshot_dir``): a truncated newest file falls back to
        the previous manifest entry (``ckpt.restore_latest``).

        The restored run continues **bit-exactly** under BSP — the
        snapshot carries every round input (state, residuals, clocks,
        RNG key, round index, alias proposal), so rounds ``k, k+1, …``
        replay identically to the uninterrupted run (the oracle property;
        asserted in tests).  Under SSP/async the continuation is
        within-tolerance: the schedule state (cache version, retry
        budget) is restored, but a crash by definition lost whatever
        staleness window was in flight."""
        tcfg = config
        sdir = snapshot_dir if snapshot_dir is not None else tcfg.snapshot_dir
        if not sdir:
            raise ValueError("no snapshot_dir: pass snapshot_dir= or set "
                             "TrainerConfig.snapshot_dir")
        trainer = cls(model_cfg, tokens, mask, config=tcfg, key=key)
        # Materialize the alias proposal so the restore template has the
        # snapshot's pytree structure (snapshots are written after at
        # least one round, whose pull built the tables — a fresh
        # Trainer's `tables=None` placeholder would not unflatten).
        # Note the fresh tcp Trainer's __init__ already re-sent its INIT
        # pushes — the servers' mutation log dedups them (same seed ⇒
        # same digest), so the canonical state is untouched.
        if trainer.remote is not None:
            trainer._materialize_tcp_edge()
        else:
            trainer.pstate = trainer.server.refresh_proposal(
                model_cfg, trainer.pstate)
        snap = ckpt.restore_latest(sdir, tcfg.snapshot_name,
                                   trainer.snapshot_state(), step=step)
        trainer._install_snapshot(snap)
        return trainer

    def _materialize_tcp_edge(self) -> None:
        """Template materialization for a tcp restore: structurally the
        client edge a running worker holds — one pull (any round the
        servers have finalized) plus the alias proposal built from it.
        Values are overwritten by the restored snapshot."""
        if self._tcp_snapshot is None:
            snap, version, _ = self.remote.pull(0, None)
            self._tcp_snapshot = snap
            self._tcp_version = version
        if self._tcp_tables is None:
            self._tcp_tables, self._tcp_stale = self.family.build_alias(
                self.cfg, self._tcp_snapshot)

    def _install_snapshot(self, snap: dict) -> None:
        as_device = functools.partial(jax.tree.map, jnp.asarray)
        self.locals_ = list(as_device(snap["locals"]))
        self.residuals = list(as_device(snap["residuals"]))
        self.key = jnp.asarray(snap["key"])
        self.round_idx = int(snap["round_idx"])
        hv = int(snap["host_version"])
        self._host_version = None if hv < 0 else hv
        self.alias_builds = int(snap["alias_builds"])
        self._pull_retries = int(snap["pull_retries"])
        if self.remote is None:
            self.pstate = as_device(snap["server"])
            return
        self._tcp_snapshot = as_device(snap["tcp_snapshot"])
        tv = int(snap["tcp_version"])
        self._tcp_version = None if tv < 0 else tv
        self._tcp_tables = as_device(snap["tcp_tables"])
        self._tcp_stale = as_device(snap["tcp_stale"])
        self._lag = as_device(snap["tcp_lag"])
        # The rejoin protocol (DESIGN.md §13): clear whatever pending
        # pushes and open mutation-log entries the dead incarnation left
        # at the servers, lift any eviction, and take the next pull
        # fresh.  Replayed pushes for rounds the servers already
        # finalized dedup against the mutation log (bit-exact restore ⇒
        # identical digests), so the resumed rounds apply exactly once.
        for c in self.local_clients:
            self.remote.rejoin(c)
        self._tcp_version = None

    def run(self, n_rounds: int, *, eval_every: int = 5,
            eval_docs: int = 32) -> RunResult:
        """Run ``n_rounds`` sync rounds with periodic held-out evaluation.

        Compiled rounds pipeline asynchronously between evaluation points;
        per-round times are therefore measured per eval segment (wall time
        from the previous sync, amortized over the segment's rounds)."""
        fam, cfg = self.family, self.cfg
        eval_t = self.tokens[:eval_docs]
        eval_m = self.mask[:eval_docs]
        res = RunResult()
        first = self.round_idx
        seg_start = time.perf_counter()
        seg_rounds = 0
        for r in range(first, first + n_rounds):
            self.step()
            seg_rounds += 1
            if (r - first) % eval_every == 0 or r == first + n_rounds - 1:
                self._sync()
                dt = (time.perf_counter() - seg_start) / seg_rounds
                res.iter_times.extend([dt] * seg_rounds)
                res.perplexities.append(float(fam.perplexity(
                    cfg, self.shared, eval_t, eval_m,
                    jax.random.PRNGKey(42))))
                res.topics_per_word.append(
                    float(fam.topics_per_word(self.shared)))
                res.violations.append(
                    float(fam.count_violations(self.shared)))
                seg_start = time.perf_counter()
                seg_rounds = 0
        return res

    # ------------------------------------------------------------ queries
    def perplexity(self, tokens: Array | None = None,
                   mask: Array | None = None,
                   key: Array | None = None) -> float:
        return float(self.family.perplexity(
            self.cfg, self.shared,
            self.tokens if tokens is None else tokens,
            self.mask if mask is None else mask,
            jax.random.PRNGKey(42) if key is None else key))

    def consistency_error(self) -> float:
        """Max |counts-from-assignments − maintained| over the family's
        count-conserved shared statistics, summed across client shards.

        With the dense filter this must be exactly 0.0 in either layout
        AND under every consistency policy — staleness delays what a
        client *sees*, never what the server *applies*: every pushed
        delta lands exactly once (error feedback carries filtered mass),
        so the canonical counts always match the assignments.
        """
        fam, cfg = self.family, self.cfg
        if self.remote is not None and \
                len(self.local_clients) != self.tcfg.n_clients:
            raise RuntimeError(
                "consistency_error needs every client's locals; this "
                "worker only runs clients "
                f"{self.local_clients} of {self.tcfg.n_clients}")
        totals: dict[str, Array] = {}
        for (t, m), loc in zip(self.shards, self.locals_):
            for n, v in fam.count_stats(cfg, t, m, loc).items():
                totals[n] = v if n not in totals else totals[n] + v
        stats = fam.stats_dict(self.shared)
        return max(float(jnp.abs(totals[n] - stats[n]).max())
                   for n in fam.conserved_stats)
