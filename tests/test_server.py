"""The explicit ParameterServer API (core/server.py, DESIGN.md §9):
vocabulary sharding, pluggable consistency, clocks, and the server-side
changed-row accounting.

Contracts:

1. sharding is representation-only — any ``n_server_shards`` is bit-exact
   with the unsharded dense pytree (assembly is pure concatenation and
   all arithmetic runs on the assembled view);
2. BSP through the server is bit-exact with the reference loop (the
   migration oracle — also covered family-wide in test_round_compile);
3. SSP and async keep the count-conservation contract exactly (staleness
   delays what clients *see*, never what the server *applies*) and match
   their Python reference loop bit-for-bit;
4. SSP's versioned cache refreshes on the staleness-bound schedule, and
   the alias proposal rebuilds exactly on refresh rounds (the measured
   throughput win);
5. one compiled-round trace per (family, layout, policy) — the refresh
   flag, projection cadence and failure mask all enter traced.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import family as family_mod
from repro.core import server as server_mod
from repro.core.fault import FaultPlan
from repro.core.server import (Async, BSP, ShardSpec, SSP, make_consistency)
from repro.engine import Trainer, TrainerConfig
from repro.engine import round as round_mod
from tests.conftest import make_family_cfg, make_synthetic_corpus

VOCAB = 64


def _cfg(name, k=4):
    return make_family_cfg(name, n_topics=k, vocab_size=VOCAB)


@pytest.fixture(scope="module")
def corpus():
    return make_synthetic_corpus(n_topics=4, vocab=VOCAB, n_docs=16,
                                 doc_len=12, seed=3)


# ---------------------------------------------------------------------------
# ShardSpec / policy parsing
# ---------------------------------------------------------------------------

def test_shard_spec_row_ranges():
    spec = ShardSpec(n_rows=10, n_shards=3)
    assert spec.bounds == (0, 3, 6, 10)
    assert [spec.rows_of(s) for s in range(3)] == [(0, 3), (3, 6), (6, 10)]
    r2s = spec.row_to_shard()
    assert r2s.shape == (10,)
    # the map agrees with the ranges, covers every row, and shard_of
    # matches it pointwise
    for row in range(10):
        lo, hi = spec.rows_of(r2s[row])
        assert lo <= row < hi
        assert spec.shard_of(row) == r2s[row]
    x = jnp.arange(10 * 2, dtype=jnp.float32).reshape(10, 2)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(s) for s in spec.split(x)]), np.asarray(x))


def test_shard_spec_validates():
    with pytest.raises(ValueError):
        ShardSpec(n_rows=4, n_shards=5)
    with pytest.raises(ValueError):
        ShardSpec(n_rows=4, n_shards=0)


def test_make_consistency_parsing():
    assert isinstance(make_consistency("bsp"), BSP)
    assert isinstance(make_consistency("async"), Async)
    assert make_consistency("ssp:3").bound == 3
    assert make_consistency("ssp(2)").bound == 2
    assert make_consistency("ssp").bound == 1
    assert make_consistency("ssp:2").key == "ssp(2)"
    pol = SSP(bound=4)
    assert make_consistency(pol) is pol
    with pytest.raises(ValueError, match="consistency"):
        make_consistency("eventually-maybe")
    with pytest.raises(ValueError, match="bound"):
        SSP(bound=-1)
    # a negative bound must reach the validator, not silently parse as
    # its absolute value
    with pytest.raises(ValueError, match="bound"):
        make_consistency("ssp:-1")


def test_ssp_init_state_leaves_not_aliased(corpus):
    """The SSP cache must be a materialized copy, never an alias of the
    canonical shards/aux: the whole ServerState is donated to the
    compiled round, and donating one buffer twice is a runtime error on
    donating backends (CPU skips donation, so CI would mask an alias)."""
    tokens, mask, _ = corpus
    fam = family_mod.get("lda")
    cfg = _cfg("lda")
    _, shared = fam.init_state(cfg, jnp.asarray(tokens), jnp.asarray(mask),
                               jax.random.PRNGKey(0))
    srv = server_mod.make_server(fam, VOCAB, consistency="ssp:2")
    state = srv.init_state(shared, n_clients=2)

    def buf(x):
        try:
            return x.unsafe_buffer_pointer()   # the actual device buffer
        except Exception:
            return id(x)

    leaf_bufs = [buf(x) for x in jax.tree.leaves(state)]
    assert len(leaf_bufs) == len(set(leaf_bufs)), \
        "ServerState leaves alias each other — double donation"


def test_trainer_rejects_bad_consistency(corpus):
    tokens, mask, _ = corpus
    with pytest.raises(ValueError, match="consistency"):
        Trainer(_cfg("lda"), tokens, mask,
                config=TrainerConfig(consistency="gossip"))
    with pytest.raises(ValueError, match="n_shards"):
        Trainer(_cfg("lda"), tokens, mask,
                config=TrainerConfig(n_server_shards=10**6))


# ---------------------------------------------------------------------------
# Sharded store: pull/push/snapshot round trips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["lda", "pdp", "hdp"])
@pytest.mark.parametrize("n_shards", [1, 3])
def test_server_split_assemble_roundtrip(name, n_shards, corpus):
    tokens, mask, _ = corpus
    fam = family_mod.get(name)
    cfg = _cfg(name)
    _, shared = fam.init_state(cfg, jnp.asarray(tokens), jnp.asarray(mask),
                               jax.random.PRNGKey(0))
    srv = server_mod.make_server(fam, VOCAB, n_shards=n_shards)
    state = srv.init_state(shared, n_clients=2)
    out = fam.stats_dict(srv.snapshot(state))
    for n, v in fam.stats_dict(shared).items():
        np.testing.assert_array_equal(np.asarray(out[n]), np.asarray(v),
                                      err_msg=n)
    # pull(keys): shard-local slices address the canonical rows
    for s in range(n_shards):
        lo, hi = srv.spec.rows_of(s)
        stat = fam.conserved_stats[0]
        (sl,) = srv.pull(state, [(stat, s)])
        np.testing.assert_array_equal(
            np.asarray(sl), np.asarray(fam.stats_dict(shared)[stat][lo:hi]))


@pytest.mark.parametrize("name", ["lda", "pdp", "hdp"])
def test_sharded_server_bit_exact_with_unsharded(name, corpus):
    """n_server_shards is representation only: identical counts (and all
    shared stats, exactly — no arithmetic touches shard boundaries)."""
    tokens, mask, _ = corpus
    stats = {}
    for n_shards in (1, 4):
        t = Trainer(_cfg(name), tokens, mask, config=TrainerConfig(
            n_clients=2, tau=2, n_server_shards=n_shards))
        for _ in range(3):
            t.step()
        t._sync()
        stats[n_shards] = t.family.stats_dict(t.shared)
    for n in stats[1]:
        np.testing.assert_array_equal(np.asarray(stats[1][n]),
                                      np.asarray(stats[4][n]), err_msg=n)


def test_push_tracks_per_shard_mass_and_clocks(corpus):
    tokens, mask, _ = corpus
    fam = family_mod.get("lda")
    cfg = _cfg("lda")
    _, shared = fam.init_state(cfg, jnp.asarray(tokens), jnp.asarray(mask),
                               jax.random.PRNGKey(0))
    srv = server_mod.make_server(fam, VOCAB, n_shards=4)
    state = srv.init_state(shared, n_clients=3)
    delta = {"n_wk": jnp.zeros((VOCAB, cfg.n_topics))
             .at[5].set(1.0).at[40].set(-2.0)}
    alive = jnp.array([True, False, True])
    state = srv.push(state, delta, alive, track_mass=True)
    # counts applied once, clocks advanced only for pushing clients
    np.testing.assert_array_equal(
        np.asarray(srv.snapshot(state).n_wk),
        np.asarray(shared.n_wk + delta["n_wk"]))
    np.testing.assert_array_equal(np.asarray(state.clocks), [1, 0, 1])
    # per-shard accounting: row 5's mass on its owner shard, row 40's on its
    mass = np.concatenate([np.asarray(m) for m in srv.shard_row_mass(state)])
    expect = np.zeros(VOCAB)
    expect[5] = cfg.n_topics * 1.0
    expect[40] = cfg.n_topics * 2.0
    np.testing.assert_allclose(mass, expect)
    owner5 = srv.spec.shard_of(5)
    lo, _ = srv.spec.rows_of(owner5)
    assert float(srv.shard_row_mass(state)[owner5][5 - lo]) > 0
    # consumption selects exactly the drifted rows and resets the ledger
    rows, valid, state = srv.consume_changed_rows(state, k_rows=8,
                                                  threshold=0.0)
    picked = set(np.asarray(rows)[np.asarray(valid)].tolist())
    assert picked == {5, 40}
    assert all(float(m.sum()) == 0.0 for m in srv.shard_row_mass(state))


# ---------------------------------------------------------------------------
# Consistency policies end to end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("consistency", ["ssp:2", "async"])
@pytest.mark.parametrize("name", ["lda", "pdp", "hdp"])
def test_policies_conserve_counts_and_match_reference(name, consistency,
                                                      corpus):
    """SSP/async compiled rounds match their Python reference loop
    bit-exactly on count statistics and keep exact count conservation —
    relaxed consistency delays what clients see, never what the server
    applies."""
    tokens, mask, _ = corpus
    trainers = {
        compiled: Trainer(_cfg(name), tokens, mask, config=TrainerConfig(
            n_clients=2, consistency=consistency, compiled=compiled))
        for compiled in (True, False)}
    for _ in range(4):
        for t in trainers.values():
            t.step()
    trainers[True]._sync()
    fam = trainers[True].family
    stats = {c: fam.stats_dict(t.shared) for c, t in trainers.items()}
    for n in fam.conserved_stats:
        np.testing.assert_array_equal(np.asarray(stats[True][n]),
                                      np.asarray(stats[False][n]),
                                      err_msg=n)
    for t in trainers.values():
        assert t.consistency_error() == 0.0
        assert np.all(t.clocks == 4)


def test_ssp_refresh_schedule_and_alias_coupling(corpus):
    """SSP(bound=2): the versioned cache (and with it the alias proposal)
    refreshes at rounds 0, 3, 6, ... — clients run up to 2 rounds ahead
    of the snapshot, and the skipped rebuilds are the throughput win."""
    tokens, mask, _ = corpus
    t = Trainer(_cfg("lda"), tokens, mask, config=TrainerConfig(
        n_clients=2, consistency="ssp:2"))
    builds = []
    for _ in range(7):
        t.step()
        builds.append(t.alias_builds)
    t._sync()
    # refresh at r=0, r=3, r=6 → 3 builds in 7 rounds (BSP would do 7)
    assert builds == [1, 1, 1, 2, 2, 2, 3]
    assert int(t.pstate.cache_version) == 6
    # the staleness bound held on every pull: r - version <= 2
    for r, b in enumerate(builds):
        version = {1: 0, 2: 3, 3: 6}[b]
        assert r - version <= 2
    # the pulled cache is genuinely stale between refreshes: after the
    # last round (r=6 refreshed at pull time, then pushed), the cache
    # holds the pre-push state, not the canonical one.
    cache_nwk = np.asarray(t.pstate.cache.n_wk)
    canon_nwk = np.asarray(t.shared.n_wk)
    assert not np.array_equal(cache_nwk, canon_nwk)
    assert t.consistency_error() == 0.0


def test_ssp_matches_bsp_when_bound_zero(corpus):
    """SSP(0) refreshes every round — identical counts to BSP (the
    degenerate bound recovers bulk-synchronous behavior)."""
    tokens, mask, _ = corpus
    out = {}
    for consistency in ("bsp", "ssp:0"):
        t = Trainer(_cfg("lda"), tokens, mask, config=TrainerConfig(
            n_clients=2, tau=2, consistency=consistency))
        for _ in range(3):
            t.step()
        t._sync()
        out[consistency] = np.asarray(t.shared.n_wk)
    np.testing.assert_array_equal(out["bsp"], out["ssp:0"])


def test_async_clients_see_in_round_pushes(corpus):
    """Async applies pushes immediately: with two clients the second
    samples against the first's push, so async counts must differ from
    BSP's barrier semantics after one round (while still conserving)."""
    tokens, mask, _ = corpus
    out = {}
    for consistency in ("bsp", "async"):
        t = Trainer(_cfg("lda"), tokens, mask, config=TrainerConfig(
            n_clients=2, consistency=consistency))
        t.step()
        t._sync()
        assert t.consistency_error() == 0.0
        out[consistency] = np.asarray(t.shared.n_wk)
    assert not np.array_equal(out["bsp"], out["async"])


@pytest.mark.parametrize("consistency", ["ssp:2", "async"])
def test_policy_rounds_trace_once(consistency, corpus):
    """One trace per (family, layout, policy): rounds spanning refresh
    and non-refresh pulls, projection cadence and a failure window must
    not retrace the compiled round."""
    tokens, mask, _ = corpus
    t = Trainer(_cfg("hdp"), tokens, mask, config=TrainerConfig(
        layout="sorted", n_clients=2, consistency=consistency,
        project_every=2, fault_plan=FaultPlan.crash(1, 2, 3)))
    t.step()
    traced_once = t.round_traces
    assert traced_once >= 1
    for _ in range(5):
        t.step()
    t._sync()
    assert t.round_traces == traced_once
    assert t.consistency_error() == 0.0


def test_policy_failure_injection_freezes_clock(corpus):
    """A dead client's push is zeroed and its clock frozen — the signal
    SSP's bound watches on a real deployment."""
    tokens, mask, _ = corpus
    t = Trainer(_cfg("lda"), tokens, mask, config=TrainerConfig(
        n_clients=3, consistency="ssp:1", fault_plan=FaultPlan.crash(1, 0, 2)))
    for _ in range(4):
        t.step()
    t._sync()
    np.testing.assert_array_equal(t.clocks, [4, 2, 4])
    assert t.consistency_error() == 0.0


def test_ssp_converges_near_bsp(corpus):
    """Perplexity sanity on the tiny unit corpus: SSP(2) converges (well
    below the random-init plateau) and lands in BSP's neighborhood.  The
    16-doc corpus is deliberately the worst staleness regime — per-round
    relative drift is huge — so the bound here is loose."""
    tokens, mask, _ = corpus
    ppl = {}
    for consistency in ("bsp", "ssp:2"):
        vals = []
        for seed in (0, 1, 2):
            t = Trainer(_cfg("lda"), tokens, mask,
                        config=TrainerConfig(n_clients=2,
                                             consistency=consistency),
                        key=jax.random.PRNGKey(seed))
            for _ in range(12):
                t.step()
            t._sync()
            vals.append(t.perplexity())
        ppl[consistency] = sum(vals) / len(vals)
    rel = abs(ppl["ssp:2"] - ppl["bsp"]) / ppl["bsp"]
    assert rel < 0.2, ppl
