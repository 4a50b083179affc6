"""Elastic fault tolerance (paper §5.4, DESIGN.md §10): FaultPlan
resolution, Trainer snapshot/restore, and kill-and-rejoin recovery.

Contracts:

1. a FaultPlan resolves host-side to per-round masks deterministically
   (seeded-random plans are pure values);
2. the deprecated ``drop_client`` tuple compiles to the equivalent
   one-event plan with a DeprecationWarning;
3. a BSP run interrupted by a crash and resumed via ``Trainer.restore``
   is bit-exact with the uninterrupted run (the snapshot carries every
   round input);
4. an SSP rejoin is just a maximally-stale client taking its blocking
   refresh: the forced pull lands at the rejoin round and the client's
   read-my-writes lag is cleared;
5. a failed pull refresh degrades gracefully (stale cache + bounded
   host-side retry, then force-through), loses no count mass;
6. lost pushes lose exactly their delta (consistency error goes nonzero
   by design, clocks freeze); stragglers lose nothing;
7. all of it holds identically in the compiled round and the Python
   reference loop.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.fault import FaultEvent, FaultPlan, healthy
from repro.core.lda import LDAConfig
from repro.data.synthetic import CorpusConfig, make_topic_corpus
from repro.engine import Trainer, TrainerConfig
from tests.conftest import make_family_cfg, make_synthetic_corpus

VOCAB = 64


def _cfg(name="lda", k=6):
    return make_family_cfg(name, n_topics=k, vocab_size=VOCAB)


@pytest.fixture(scope="module")
def corpus():
    return make_synthetic_corpus(n_topics=4, vocab=VOCAB, n_docs=24,
                                 doc_len=16, seed=3)


# ---------------------------------------------------------------------------
# FaultPlan resolution (pure host-side)
# ---------------------------------------------------------------------------

def test_fault_event_validation():
    with pytest.raises(ValueError, match="kind"):
        FaultEvent("explode", 0, 0, 1)
    with pytest.raises(ValueError, match="reversed"):
        FaultEvent("crash", 0, 3, 1)
    with pytest.raises(ValueError, match="period"):
        FaultEvent("straggle", 0, 0, 4, period=1)
    with pytest.raises(TypeError):
        FaultPlan(events=("crash",))


def test_plan_resolution_scripted():
    plan = FaultPlan.scripted(
        FaultEvent("crash", client=1, start=2, stop=4),
        FaultEvent("lost_push", client=0, start=3, stop=5),
        FaultEvent("straggle", client=2, start=0, stop=6, period=3),
        FaultEvent("failed_pull", start=4, stop=5),
    )
    n = 4
    # round 0: straggler works ((0-0) % 3 == 0), everyone healthy
    rf = plan.resolve(0, n)
    assert rf.alive == (True, True, True, True)
    assert rf.push_ok == (True, True, True, True)
    assert not rf.pull_failed and rf.rejoining == ()
    # round 1: straggler mid-stall
    rf = plan.resolve(1, n)
    assert rf.alive == (True, True, False, True)
    assert rf.push_ok == (True, True, False, True)
    # round 3: crash active, lost_push active, straggler works
    rf = plan.resolve(3, n)
    assert rf.alive == (True, False, True, True)
    assert rf.push_ok == (False, False, True, True)
    # round 4: crash window ends -> rejoin; shared refresh outage;
    # the period-3 straggler is mid-stall ((4-0) % 3 != 0)
    rf = plan.resolve(4, n)
    assert rf.alive == (True, True, False, True)
    assert rf.rejoining == (1,)
    assert rf.pull_failed
    # past the last window: the cached healthy value
    assert plan.resolve(7, n) is healthy(n)
    assert plan.last_round == 6 and plan.max_client == 2


def test_plan_rejoin_suppressed_by_overlapping_crash():
    plan = FaultPlan.scripted(
        FaultEvent("crash", client=0, start=0, stop=2),
        FaultEvent("crash", client=0, start=2, stop=4),
    )
    rf = plan.resolve(2, 2)
    assert not rf.alive[0] and rf.rejoining == ()
    assert plan.resolve(4, 2).rejoining == (0,)


def test_plan_resolution_rejects_out_of_range_client():
    with pytest.raises(ValueError, match="only 2 clients"):
        FaultPlan.crash(5, 0, 2).resolve(1, 2)


def test_random_plan_deterministic_and_bounded():
    mk = lambda s: FaultPlan.random(s, n_clients=4, n_rounds=32,
                                    p_crash=0.1, p_straggle=0.1,
                                    p_lost_push=0.1, p_failed_pull=0.05)
    assert mk(7).events == mk(7).events
    assert mk(7).events != mk(8).events
    plan = mk(7)
    assert plan.events, "expected events at these hazard rates"
    for e in plan.events:
        assert 0 <= e.start <= e.stop <= 32
        if e.kind != "failed_pull":
            assert e.client < 4
    # at most one concurrent per-client event
    for c in range(4):
        wins = sorted((e.start, e.stop) for e in plan.events
                      if e.kind != "failed_pull" and e.client == c)
        for (_, s0), (s1, _) in zip(wins, wins[1:]):
            assert s0 <= s1


# ---------------------------------------------------------------------------
# drop_client deprecation shim
# ---------------------------------------------------------------------------

def test_drop_client_shim_warns_and_matches(corpus):
    tokens, mask, _ = corpus
    with pytest.warns(DeprecationWarning, match="drop_client"):
        t = Trainer(_cfg(), tokens, mask, config=TrainerConfig(
            n_clients=4, drop_client=(1, 1, 3)))
    assert t.fault_plan == FaultPlan.crash(1, 1, 3)


def test_drop_client_and_fault_plan_mutually_exclusive(corpus):
    tokens, mask, _ = corpus
    with pytest.raises(ValueError, match="mutually exclusive"):
        Trainer(_cfg(), tokens, mask, config=TrainerConfig(
            n_clients=4, drop_client=(1, 1, 3),
            fault_plan=FaultPlan.crash(0, 0, 1)))


def test_trainer_rejects_plan_naming_missing_client(corpus):
    tokens, mask, _ = corpus
    with pytest.raises(ValueError, match="client 3"):
        Trainer(_cfg(), tokens, mask, config=TrainerConfig(
            n_clients=2, fault_plan=FaultPlan.crash(3, 0, 1)))


# ---------------------------------------------------------------------------
# Snapshot / restore / rejoin
# ---------------------------------------------------------------------------

def _stats(t):
    return {n: np.asarray(v)
            for n, v in t.family.stats_dict(t.shared).items()}


def test_bsp_crash_restore_bit_exact(corpus, tmp_path):
    """The oracle property: a run killed after round 4 and resumed from
    the round-4 snapshot replays rounds 4..5 bit-exactly — every shared
    statistic and every client's conserved counts match the
    uninterrupted run."""
    tokens, mask, _ = corpus
    tcfg = TrainerConfig(n_clients=2, snapshot_every=2,
                         snapshot_dir=str(tmp_path))
    ref = Trainer(_cfg(), tokens, mask, config=tcfg)
    for _ in range(6):
        ref.step()
    ref._sync()

    res = Trainer.restore(_cfg(), tokens, mask, config=tcfg, step=4)
    assert res.round_idx == 4
    for _ in range(2):
        res.step()
    res._sync()
    assert res.consistency_error() == 0.0
    a, b = _stats(ref), _stats(res)
    for n in a:
        np.testing.assert_array_equal(a[n], b[n], err_msg=n)


def test_restore_latest_default_and_missing_dir(corpus, tmp_path):
    tokens, mask, _ = corpus
    tcfg = TrainerConfig(n_clients=2, snapshot_every=2,
                         snapshot_dir=str(tmp_path))
    t = Trainer(_cfg(), tokens, mask, config=tcfg)
    for _ in range(5):
        t.step()
    # snapshots at rounds 2 and 4; the manifest's latest wins
    res = Trainer.restore(_cfg(), tokens, mask, config=tcfg)
    assert res.round_idx == 4
    with pytest.raises(ValueError, match="snapshot_dir"):
        Trainer.restore(_cfg(), tokens, mask,
                        config=TrainerConfig(n_clients=2))


def test_ssp_rejoin_forces_refresh_and_resets_lag(corpus, tmp_path):
    """Kill-and-rejoin under SSP(3): the rejoin at round 3 forces a
    fresh pull off-schedule (the natural refresh would wait until round
    4), the rejoined client re-enters with a cleared read-my-writes lag
    (the fresh cache carries every applied push; within the rejoin round
    its row then accumulates exactly its own new delta — which is why
    conservation still holds exactly), and no count mass is lost (the
    crash froze the client, nothing moved)."""
    tokens, mask, _ = corpus
    t = Trainer(_cfg(), tokens, mask, config=TrainerConfig(
        n_clients=2, consistency="ssp:3",
        fault_plan=FaultPlan.crash(1, 1, 3),
        snapshot_every=2, snapshot_dir=str(tmp_path)))
    for _ in range(3):        # rounds 0..2: refresh at 0, crash at 1,2
        t.step()
    assert t._host_version == 0
    t.step()                  # round 3: rejoin -> forced refresh
    t._sync()
    assert t.rejoins == 1
    assert t._host_version == 3
    assert int(np.asarray(t.pstate.cache_version)) == 3
    assert t.consistency_error() == 0.0
    np.testing.assert_array_equal(t.clocks, [4, 2])


def test_server_rejoin_client_clears_one_lag_row(corpus):
    tokens, mask, _ = corpus
    t = Trainer(_cfg(), tokens, mask, config=TrainerConfig(
        n_clients=2, consistency="ssp:3"))
    for _ in range(2):        # rounds past the refresh: lag accumulates
        t.step()
    t._sync()
    assert any(np.abs(np.asarray(v[0])).sum() > 0
               for v in t.pstate.client_lag.values())
    state = t.server.rejoin_client(t.pstate, 0)
    for n, v in state.client_lag.items():
        np.testing.assert_array_equal(np.asarray(v[0]),
                                      np.zeros_like(np.asarray(v[0])))
        np.testing.assert_array_equal(np.asarray(v[1]),
                                      np.asarray(t.pstate.client_lag[n][1]))


def test_failed_pull_bounded_retry_then_force_through(corpus):
    """An SSP(2) refresh outage: the due pull at round 3 fails, clients
    continue on the stale cache (degradation, not derailment) while the
    host retries; after pull_retry_limit consecutive failures the
    refresh forces through.  No count mass is ever lost."""
    tokens, mask, _ = corpus
    plan = FaultPlan.scripted(FaultEvent("failed_pull", start=1, stop=12))
    t = Trainer(_cfg(), tokens, mask, config=TrainerConfig(
        n_clients=2, consistency="ssp:2", fault_plan=plan,
        pull_retry_limit=2))
    for _ in range(6):        # due at 3 -> fail(3), fail(4), force(5)
        t.step()
    t._sync()
    assert t.pull_failures == 2
    assert t._host_version == 5
    assert int(np.asarray(t.pstate.cache_version)) == 5
    assert t.consistency_error() == 0.0


def test_failed_pull_noop_under_bsp(corpus):
    tokens, mask, _ = corpus
    plan = FaultPlan.scripted(FaultEvent("failed_pull", start=0, stop=8))
    t = Trainer(_cfg(), tokens, mask, config=TrainerConfig(
        n_clients=2, consistency="bsp", fault_plan=plan))
    for _ in range(3):
        t.step()
    t._sync()
    assert t.pull_failures == 0
    assert t.consistency_error() == 0.0


@pytest.mark.parametrize("compiled", [True, False])
def test_lost_push_loses_mass_and_freezes_clock(corpus, compiled):
    """A lost push is a *lossy* fault: the client's replica moved but the
    server never saw the delta, so the maintained statistics drift from
    the assignments (nonzero consistency error, by design) and the
    client's clock does not advance for the lost rounds."""
    tokens, mask, _ = corpus
    t = Trainer(_cfg(), tokens, mask, config=TrainerConfig(
        n_clients=2, compiled=compiled,
        fault_plan=FaultPlan.scripted(
            FaultEvent("lost_push", client=1, start=1, stop=3))))
    for _ in range(4):
        t.step()
    t._sync()
    np.testing.assert_array_equal(t.clocks, [4, 2])
    assert t.consistency_error() > 0.0


def test_straggler_conserves_counts(corpus):
    """A straggler with period 2 completes every other round: its clock
    runs at half speed but nothing is lost — the dense-filter
    conservation contract holds exactly."""
    tokens, mask, _ = corpus
    t = Trainer(_cfg(), tokens, mask, config=TrainerConfig(
        n_clients=2, fault_plan=FaultPlan.scripted(
            FaultEvent("straggle", client=1, start=0, stop=6, period=2))))
    for _ in range(6):
        t.step()
    t._sync()
    np.testing.assert_array_equal(t.clocks, [6, 3])
    assert t.consistency_error() == 0.0


def test_compiled_python_parity_under_fault_plan(corpus):
    """The compiled round and the reference loop resolve the same plan to
    identical statistics — the fault masks enter both paths identically
    (bit-exact integer counts, including the lossy lost_push rounds)."""
    tokens, mask, _ = corpus
    plan = FaultPlan.scripted(
        FaultEvent("crash", client=0, start=1, stop=3),
        FaultEvent("lost_push", client=1, start=2, stop=4),
        FaultEvent("straggle", client=2, start=0, stop=5, period=2),
    )
    trainers = {
        compiled: Trainer(_cfg(), tokens, mask, config=TrainerConfig(
            n_clients=3, compiled=compiled, fault_plan=plan))
        for compiled in (True, False)}
    for _ in range(5):
        for t in trainers.values():
            t.step()
    trainers[True]._sync()
    a, b = _stats(trainers[True]), _stats(trainers[False])
    for n in a:
        np.testing.assert_array_equal(a[n], b[n], err_msg=n)
    np.testing.assert_array_equal(trainers[True].clocks,
                                  trainers[False].clocks)


def test_fault_plan_rounds_trace_once(corpus):
    """Chaos must not retrace: a multi-kind plan spanning crashes,
    stragglers, lost pushes and rejoins keeps the one-trace-per-signature
    invariant (the masks are traced inputs)."""
    tokens, mask, _ = corpus
    plan = FaultPlan.random(5, n_clients=3, n_rounds=8, p_crash=0.3,
                            p_straggle=0.3, p_lost_push=0.3,
                            p_failed_pull=0.2)
    t = Trainer(_cfg(), tokens, mask, config=TrainerConfig(
        n_clients=3, consistency="ssp:2", fault_plan=plan))
    t.step()
    traced_once = t.round_traces
    for _ in range(7):
        t.step()
    t._sync()
    assert t.round_traces == traced_once
    assert np.isfinite(t.perplexity(tokens[:16], mask[:16]))


# ---------------------------------------------------------------------------
# Recovery quality
# ---------------------------------------------------------------------------

MAX_DEGRADATION = 0.05


@pytest.fixture(scope="module")
def recovery_corpus():
    return make_topic_corpus(CorpusConfig(
        n_topics=8, vocab_size=400, n_docs=128, doc_len=48, seed=7))


@pytest.mark.parametrize("consistency", ["bsp", "ssp:2", "async"])
def test_kill_rejoin_final_perplexity_within_five_percent(
        consistency, recovery_corpus, tmp_path):
    """Client 1 of 4 crashes over rounds 3-6 of 12 and rejoins from its
    last periodic snapshot; final held-out perplexity ends within 5 % of
    the same run with no fault."""
    tokens, mask, _ = recovery_corpus
    cfg = LDAConfig(n_topics=8, vocab_size=400, alpha=0.1, beta=0.01,
                    mh_steps=2)
    n_rounds = 12

    def final_perplexity(**fault):
        t = Trainer(cfg, tokens, mask, config=TrainerConfig(
            n_clients=4, consistency=consistency, **fault))
        for _ in range(n_rounds):
            t.step()
        return t.perplexity(tokens[:32], mask[:32]), t

    base, _ = final_perplexity()
    killed, t = final_perplexity(fault_plan=FaultPlan.crash(1, 3, 6),
                                 snapshot_every=2,
                                 snapshot_dir=str(tmp_path))
    assert t.rejoins == 1
    assert killed / base - 1.0 <= MAX_DEGRADATION, (killed, base)
