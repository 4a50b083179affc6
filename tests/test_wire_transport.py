"""Transport parity: the tcp backend vs the in-process ParameterServer.

BSP over loopback TCP must be *bit-exact* with the in-process reference
(same corpus, same key, same round count) — the acceptance criterion of
DESIGN.md §11.  SSP stays within mass-conservation and perplexity
tolerance.  The stress tests hammer a live server from threads and check
the final store is exactly init + Σ deltas.
"""

from __future__ import annotations

import threading

import jax
import numpy as np
import pytest

from repro.core import family as fam_mod
from repro.core.lda import LDAConfig
from repro.data.synthetic import CorpusConfig, make_topic_corpus
from repro.engine.trainer import Trainer, TrainerConfig
from repro.net.client import RemoteParameterServer, stress_delta
from repro.net.server import ShardServer, serve_shards
from tests.conftest import make_family_cfg, make_synthetic_corpus

TIMEOUT = 30.0


def _corpus():
    return make_synthetic_corpus(n_topics=4, vocab=64, n_docs=16,
                                 doc_len=12, seed=3)


def _stats(family_name, trainer):
    return {n: np.asarray(v) for n, v in
            fam_mod.get(family_name).stats_dict(trainer.shared).items()}


def _run_ref(cfg, tokens, mask, *, n_clients, rounds, consistency="bsp",
             tau=1):
    t = Trainer(cfg, tokens, mask, key=jax.random.PRNGKey(0),
                config=TrainerConfig(n_clients=n_clients, tau=tau,
                                     consistency=consistency))
    for _ in range(rounds):
        t.step()
    return t


def _servers(family_name, *, n_clients, n_shards=1, consistency="bsp",
             vocab_size=64):
    return serve_shards(family_name, vocab_size=vocab_size,
                        n_clients=n_clients, n_shards=n_shards,
                        consistency=consistency, barrier_timeout=TIMEOUT)


def _addrs(servers):
    return tuple("%s:%d" % s.address for s in servers)


# ---------------------------------------------------------------------------
# Trainer-level parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family_name", ["lda", "pdp"])
def test_bsp_tcp_bitexact_single_worker(family_name):
    """One tcp Trainer hosting every client == in-process, bit for bit."""
    tokens, mask, _ = _corpus()
    cfg = make_family_cfg(family_name, n_topics=4, vocab_size=64)
    ref = _run_ref(cfg, tokens, mask, n_clients=2, rounds=3)
    want = _stats(family_name, ref)

    servers = _servers(family_name, n_clients=2, n_shards=2)
    try:
        t = Trainer(cfg, tokens, mask, key=jax.random.PRNGKey(0),
                    config=TrainerConfig(n_clients=2, tau=1,
                                         transport="tcp",
                                         server_addrs=_addrs(servers)))
        for _ in range(3):
            t.step()
        got = _stats(family_name, t)
        t.close()
    finally:
        for s in servers:
            s.close()
    assert set(want) == set(got)
    for n in want:
        np.testing.assert_array_equal(want[n], got[n], err_msg=n)


def test_bsp_tcp_bitexact_two_workers():
    """Two tcp Trainers (one global client each, stepped concurrently)
    jointly reproduce the single-process run exactly."""
    tokens, mask, _ = _corpus()
    cfg = make_family_cfg("lda", n_topics=4, vocab_size=64)
    ref = _run_ref(cfg, tokens, mask, n_clients=2, rounds=3)
    want = _stats("lda", ref)

    servers = _servers("lda", n_clients=2)
    try:
        mk = lambda cs: Trainer(  # noqa: E731
            cfg, tokens, mask, key=jax.random.PRNGKey(0),
            config=TrainerConfig(n_clients=2, tau=1, transport="tcp",
                                 server_addrs=_addrs(servers),
                                 local_clients=cs))
        t0, t1 = mk((0,)), mk((1,))
        for _ in range(3):
            th = threading.Thread(target=t1.step)
            th.start()
            t0.step()
            th.join(timeout=TIMEOUT)
            assert not th.is_alive()
        got0, got1 = _stats("lda", t0), _stats("lda", t1)
        counters = t0.remote.counters()
        t0.close()
        t1.close()
    finally:
        for s in servers:
            s.close()
    for n in want:
        np.testing.assert_array_equal(want[n], got0[n], err_msg=n)
        np.testing.assert_array_equal(want[n], got1[n], err_msg=n)
    assert counters["rpc_count"] > 0
    assert counters["bytes_out"] > 0


def test_ssp_tcp_runs_within_tolerance():
    """SSP(2) over the wire: NOT_MODIFIED fast path engages, token mass
    is conserved exactly, and model quality lands near the BSP result."""
    tokens, mask, _ = _corpus()
    cfg = make_family_cfg("lda", n_topics=4, vocab_size=64)
    ref = _run_ref(cfg, tokens, mask, n_clients=2, rounds=6)
    ref_ppl = ref.perplexity()
    n_tokens = float(np.asarray(mask).sum())

    servers = _servers("lda", n_clients=2, consistency="ssp:2")
    try:
        t = Trainer(cfg, tokens, mask, key=jax.random.PRNGKey(0),
                    config=TrainerConfig(n_clients=2, tau=1,
                                         consistency="ssp:2",
                                         transport="tcp",
                                         server_addrs=_addrs(servers)))
        for _ in range(6):
            t.step()
        t._sync()
        got = _stats("lda", t)
        ppl = t.perplexity()
        counters = t.remote.counters()
        t.close()
    finally:
        for s in servers:
            s.close()
    # Every token is in exactly one (w, k) cell at all times.
    assert got["n_wk"].sum() == pytest.approx(n_tokens)
    assert np.isfinite(ppl)
    assert abs(ppl - ref_ppl) / ref_ppl < 0.25
    # Staleness bound 2 ⇒ strictly fewer refreshing pulls than rounds ⇒
    # strictly fewer bytes than a BSP run would move.
    assert counters["rpc_count"] > 0


def test_tcp_rejects_unsupported_configs():
    tokens, mask, _ = _corpus()
    cfg = make_family_cfg("hdp", n_topics=4, vocab_size=64)
    with pytest.raises(NotImplementedError):
        Trainer(cfg, tokens, mask, key=jax.random.PRNGKey(0),
                config=TrainerConfig(n_clients=2, transport="tcp",
                                     server_addrs=("127.0.0.1:1",)))
    lcfg = make_family_cfg("lda", n_topics=4, vocab_size=64)
    with pytest.raises(ValueError):
        Trainer(lcfg, tokens, mask, key=jax.random.PRNGKey(0),
                config=TrainerConfig(n_clients=2, transport="tcp"))
    with pytest.raises(ValueError):
        Trainer(lcfg, tokens, mask, key=jax.random.PRNGKey(0),
                config=TrainerConfig(n_clients=2, transport="inproc",
                                     server_addrs=("127.0.0.1:1",)))


# ---------------------------------------------------------------------------
# RemoteParameterServer-level semantics
# ---------------------------------------------------------------------------

def _fresh_remote(servers, n_clients=1, consistency="bsp"):
    return RemoteParameterServer(_addrs(servers), family="lda",
                                 n_clients=n_clients, vocab_size=64,
                                 consistency=consistency, timeout=TIMEOUT)


def _zero_shared():
    fam = fam_mod.get("lda")
    n_wk = np.zeros((64, 4), np.float32)
    return fam.shared_from_dict({"n_wk": n_wk, "n_k": n_wk.sum(0)})


def test_not_modified_and_version_flow():
    servers = _servers("lda", n_clients=1, consistency="ssp:2")
    try:
        with _fresh_remote(servers, consistency="ssp:2") as rps:
            rps.init_push(0, _zero_shared())
            shared, v, refreshed = rps.pull(0, None)
            assert refreshed and v == 0 and shared is not None
            rps.push(0, 0, {"n_wk": np.ones((64, 4), np.float32)})
            # Round 1 with cache at version 0: within bound 2 → cached.
            shared, v, refreshed = rps.pull(1, v)
            assert not refreshed and shared is None and v == 0
            rps.push(1, 0, {"n_wk": np.ones((64, 4), np.float32)})
            rps.push(2, 0, {"n_wk": np.ones((64, 4), np.float32)})
            # Round 3 with the version-0 cache exceeds the bound.
            shared, v, refreshed = rps.pull(3, 0)
            assert refreshed and v == 3
            np.testing.assert_array_equal(
                np.asarray(shared.n_wk), np.full((64, 4), 3, np.float32))
    finally:
        for s in servers:
            s.close()


def test_pull_keys_clock_rejoin_snapshot():
    servers = _servers("lda", n_clients=1, n_shards=2)
    try:
        with _fresh_remote(servers) as rps:
            rps.init_push(0, _zero_shared())
            d = stress_delta(0, 0, (64, 4))
            rps.pull(0)
            rps.push(0, 0, {"n_wk": d})
            sr, clocks = rps.clock(min_round=1)
            assert sr == 1
            np.testing.assert_array_equal(clocks, [1])
            # Addressed row-range read spanning the shard boundary.
            mid = rps.pull_keys(["n_wk"], lo=16, hi=48)["n_wk"]
            np.testing.assert_array_equal(mid, d[16:48])
            rps.rejoin(0)
            snap = rps.snapshot(min_round=1)
            np.testing.assert_array_equal(np.asarray(snap.n_wk), d)
            np.testing.assert_array_equal(np.asarray(snap.n_k), d.sum(0))
    finally:
        for s in servers:
            s.close()


def test_projection_applied_at_barrier():
    """A negative delta pushing a count below zero is clipped by the
    family's nonneg rule at the round barrier, exactly like in-process."""
    servers = _servers("lda", n_clients=1)
    try:
        with _fresh_remote(servers) as rps:
            rps.init_push(0, _zero_shared())
            rps.pull(0)
            neg = np.full((64, 4), -1.0, np.float32)
            rps.push(0, 0, {"n_wk": neg})
            out = rps.pull_keys(["n_wk"])["n_wk"]
            np.testing.assert_array_equal(out, np.zeros((64, 4)))
    finally:
        for s in servers:
            s.close()


def test_concurrent_stress_exact_sum():
    """Many client threads, out-of-order arrivals: the barrier still
    applies rounds deterministically — final state == init + Σ."""
    n_clients, rounds = 4, 8
    servers = _servers("lda", n_clients=n_clients, n_shards=2)
    shape = (64, 4)
    try:
        remotes = [_fresh_remote(servers, n_clients=n_clients)
                   for _ in range(n_clients)]
        for c, rps in enumerate(remotes):
            rps.init_push(c, _zero_shared())

        def worker(c):
            rps = remotes[c]
            version = None
            for r in range(rounds):
                _, v, refreshed = rps.pull(r, version)
                if refreshed:
                    version = v
                rps.push(r, c, {"n_wk": stress_delta(r, c, shape)})

        threads = [threading.Thread(target=worker, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=TIMEOUT * 4)
            assert not t.is_alive(), "stress worker hung"
        remotes[0].clock(min_round=rounds)
        final = remotes[0].pull_keys(["n_wk"])["n_wk"]
        want = np.zeros(shape, np.float32)
        for r in range(rounds):
            for c in range(n_clients):
                want = want + stress_delta(r, c, shape)
        np.testing.assert_array_equal(final, want)
        for rps in remotes:
            rps.close()
    finally:
        for s in servers:
            s.close()


def test_duplicate_push_idempotent_conflict_rejected():
    """The seq-dedup rule (DESIGN.md §13): a byte-identical re-push is
    the lost-ack retry — acked, applied exactly once; different content
    claiming the same (client, round) sequence slot is refused, before
    and after the round finalizes."""
    from repro.net.protocol import ProtocolError
    servers = _servers("lda", n_clients=2)
    try:
        r0 = _fresh_remote(servers, n_clients=2)
        r1 = _fresh_remote(servers, n_clients=2)
        r0.init_push(0, _zero_shared())
        r1.init_push(1, _zero_shared())
        d = np.ones((64, 4), np.float32)
        r0.pull(0)
        r0.push(0, 0, {"n_wk": d})
        # Identical duplicate (even from another connection): recorded
        # ack, no second application.
        r1.push(0, 0, {"n_wk": d})
        # Conflicting content for a recorded sequence slot: refused.
        with pytest.raises(ProtocolError):
            r1.push(0, 0, {"n_wk": 2 * d})
        r1.push(0, 1, {"n_wk": d})      # completes round 0
        r1.clock(min_round=1)
        # After finalization the log still answers: identical → ack,
        # conflicting → refused.
        r1.push(0, 1, {"n_wk": d})
        with pytest.raises(ProtocolError):
            r1.push(0, 1, {"n_wk": 3 * d})
        # Exactly one application per (client, round) despite the dups.
        final = r0.pull_keys(["n_wk"])["n_wk"]
        np.testing.assert_array_equal(final, 2 * d)
        r1.close()
        r0.close()
    finally:
        for s in servers:
            s.close()


def test_stale_push_replay_flag_vs_unflagged():
    """A push for a round below the finalized horizon whose log entry
    has been pruned: a replay-flagged frame (reconnect catch-up) acks
    ``ignored``; an unflagged one is a real protocol violation."""
    from repro.net.protocol import MsgType, ProtocolError
    from repro.net.server import MUTLOG_WINDOW
    servers = _servers("lda", n_clients=1)
    rounds = MUTLOG_WINDOW + 2
    try:
        with _fresh_remote(servers) as rps:
            rps.init_push(0, _zero_shared())
            d = np.ones((64, 4), np.float32)
            for r in range(rounds):
                rps.pull(r)
                rps.push(r, 0, {"n_wk": d})
            # (client 0, round 0) is now below the pruned horizon.
            conn = rps._conns[0]
            _, meta, _ = conn.request(
                MsgType.PUSH, {"round": 0, "client": 0, "replay": True},
                {"n_wk": d}, expect=(MsgType.OK,))
            assert meta.get("ignored") is True
            with pytest.raises(ProtocolError):
                conn.request(MsgType.PUSH, {"round": 0, "client": 0},
                             {"n_wk": d}, expect=(MsgType.OK,))
    finally:
        for s in servers:
            s.close()


# ---------------------------------------------------------------------------
# Process-level launcher
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_launch_loopback_stress_processes(tmp_path):
    """Real processes on loopback: 1 server (2 shards) + 2 stress client
    processes; both report identical checksums of the final store."""
    from repro.launch.loopback import launch_loopback
    res = launch_loopback(mode="stress", n_shards=2,
                          client_sets=((0,), (1,)), n_rounds=4,
                          timeout=180.0, workdir=str(tmp_path))
    assert res.ok, [(p.name, p.returncode, p.stderr[-2000:])
                    for p in res.failures()]
    sums = [p.result["checksums"] for p in res.clients]
    assert sums[0] == sums[1]
    want = np.zeros((64, 4), np.float32)
    for r in range(4):
        for c in range(2):
            want = want + stress_delta(r, c, (64, 4))
    assert res.clients[0].result["sums"]["n_wk"] == pytest.approx(
        float(want.sum()))


# ---------------------------------------------------------------------------
# Sparse delta exchange over the wire (DESIGN.md §12)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family_name", ["lda", "pdp"])
def test_sparse_push_tcp_bitexact(family_name):
    """sparse_push is an encoding, not an algorithm change: the tcp
    Trainer with COO push frames reproduces the in-process run bit for
    bit (incl. the multi-stat pdp delta, whose rows are the non-zero
    union across m_wk/s_wk)."""
    tokens, mask, _ = _corpus()
    cfg = make_family_cfg(family_name, n_topics=4, vocab_size=64)
    ref = _run_ref(cfg, tokens, mask, n_clients=2, rounds=3)
    want = _stats(family_name, ref)

    servers = _servers(family_name, n_clients=2, n_shards=2)
    try:
        t = Trainer(cfg, tokens, mask, key=jax.random.PRNGKey(0),
                    config=TrainerConfig(n_clients=2, tau=1,
                                         transport="tcp",
                                         server_addrs=_addrs(servers),
                                         sparse_push=True))
        for _ in range(3):
            t.step()
        got = _stats(family_name, t)
        t.close()
    finally:
        for s in servers:
            s.close()
    for n in want:
        np.testing.assert_array_equal(want[n], got[n], err_msg=n)


def test_sparse_push_rejected_on_inproc_transport():
    tokens, mask, _ = _corpus()
    cfg = make_family_cfg("lda", n_topics=4, vocab_size=64)
    with pytest.raises(ValueError):
        Trainer(cfg, tokens, mask,
                config=TrainerConfig(n_clients=2, sparse_push=True))


# ---------------------------------------------------------------------------
# Bytes on the wire
# ---------------------------------------------------------------------------

# Encoded bytes/round of the BSP run below (frames with headers, both
# directions, summed over shards); a frame-format or push-cadence
# regression shows up as growth past this baseline.
BSP_BYTES_PER_ROUND = 7523
BYTES_SLACK = 1.10
MIN_SPARSE_REDUCTION = 5.0


def _tcp_trainer(cfg, tokens, mask, servers, **kw):
    return Trainer(cfg, tokens, mask, key=jax.random.PRNGKey(0),
                   config=TrainerConfig(n_clients=2, tau=1,
                                        transport="tcp",
                                        server_addrs=_addrs(servers), **kw))


def test_bsp_tcp_bytes_per_round_guard():
    """BSP at V=64, K=4, 2 clients, 2 shards, tau=1 moves at most 10 %
    more encoded bytes per round than the 7523 B baseline, counting the
    warm-up round (compile + INIT push) with the 4 rounds after it and
    the pull that reads the final statistics back."""
    tokens, mask, _ = make_topic_corpus(CorpusConfig(
        n_topics=4, vocab_size=64, n_docs=16, doc_len=12, seed=3))
    cfg = LDAConfig(n_topics=4, vocab_size=64)
    rounds = 5
    servers = _servers("lda", n_clients=2, n_shards=2)
    try:
        t = _tcp_trainer(cfg, tokens, mask, servers)
        # Warm-up round, drained, then the timed rounds, drained: the
        # cadence the 7523 B baseline was recorded under.
        t.step()
        t._sync()
        for _ in range(rounds - 1):
            t.step()
        t._sync()
        got = _stats("lda", t)
        counters = t.remote.counters()
        t.close()
    finally:
        for s in servers:
            s.close()
    assert got["n_wk"].sum() == pytest.approx(float(np.asarray(mask).sum()))
    encoded = (counters["bytes_in"] + counters["bytes_out"]) / rounds
    assert encoded <= BSP_BYTES_PER_ROUND * BYTES_SLACK, encoded


def test_sparse_push_payload_reduction():
    """On a zipf corpus whose vocabulary (V=2048, K=8) dwarfs the rows a
    round touches, COO push frames carry at least 5x fewer client→server
    payload bytes per steady-state round than dense pushes, and land on
    the same statistics."""
    tokens, mask, _ = make_topic_corpus(CorpusConfig(
        n_topics=4, vocab_size=2048, n_docs=12, doc_len=8, seed=7))
    cfg = LDAConfig(n_topics=8, vocab_size=2048)
    rounds = 3
    payload, stats = {}, {}
    for sparse in (False, True):
        servers = _servers("lda", n_clients=2, n_shards=2, vocab_size=2048)
        try:
            t = _tcp_trainer(cfg, tokens, mask, servers, sparse_push=sparse)
            # The warm-up round's INIT push ships the full dense state
            # once; it stays out of the steady-state count.
            t.step()
            t._sync()
            before = t.remote.counters()["payload_out"]
            for _ in range(rounds):
                t.step()
            t._sync()
            payload[sparse] = \
                (t.remote.counters()["payload_out"] - before) / rounds
            stats[sparse] = _stats("lda", t)
            t.close()
        finally:
            for s in servers:
                s.close()
    for n in stats[False]:
        np.testing.assert_array_equal(stats[False][n], stats[True][n],
                                      err_msg=n)
    ratio = payload[False] / payload[True]
    assert ratio >= MIN_SPARSE_REDUCTION, payload


# ---------------------------------------------------------------------------
# Bounded reconnect on pull
# ---------------------------------------------------------------------------

def test_pull_reconnects_after_dropped_connection():
    """A dead socket under a pull: the client re-dials, re-handshakes,
    carries its wire counters over, and the pull succeeds."""
    servers = _servers("lda", n_clients=1, n_shards=2)
    try:
        with _fresh_remote(servers) as rps:
            rps.init_push(0, _zero_shared())
            rps.pull(0)
            before = rps.counters()
            # Kill both connections out from under the client.
            for conn in rps._conns:
                conn.sock.close()
            shared, v, refreshed = rps.pull(0)
            assert refreshed and shared is not None
            after = rps.counters()
            # Counters carried over the reconnect (monotone, not reset).
            assert after["bytes_out"] > before["bytes_out"]
            assert after["rpc_count"] > before["rpc_count"]
    finally:
        for s in servers:
            s.close()


def test_pull_reconnect_budget_exhausts_on_dead_server():
    """Every reconnect attempt fails once the server is gone: the pull
    must surface a RemoteError after reconnect_limit tries, not spin."""
    from repro.net.client import RemoteError
    servers = _servers("lda", n_clients=1)
    rps = RemoteParameterServer(_addrs(servers), family="lda",
                                n_clients=1, vocab_size=64,
                                timeout=TIMEOUT, reconnect_limit=2)
    try:
        rps.init_push(0, _zero_shared())
        rps.pull(0)
        for s in servers:
            s.close()
        for conn in rps._conns:
            conn.sock.close()
        with pytest.raises(RemoteError, match="after 2 reconnect"):
            rps.pull(0)
    finally:
        rps.close()
        for s in servers:
            s.close()


# ---------------------------------------------------------------------------
# Eviction, shard restart, worker restart (DESIGN.md §13)
# ---------------------------------------------------------------------------

def test_dead_client_evicted_from_barrier_then_rejoins():
    """A client whose connections die stops the barrier only until the
    liveness deadline: it is evicted, rounds finalize from the
    survivors, and a later rejoin re-admits it after a forced-fresh
    pull."""
    servers = serve_shards("lda", vocab_size=64, n_clients=2,
                           barrier_timeout=TIMEOUT, liveness_timeout=0.4)
    d = np.ones((64, 4), np.float32)
    try:
        r0 = _fresh_remote(servers, n_clients=2)
        r1 = _fresh_remote(servers, n_clients=2)
        r0.init_push(0, _zero_shared())
        r1.init_push(1, _zero_shared())
        r0.pull(0)
        r0.push(0, 0, {"n_wk": d})
        r1.pull(0)
        r1.push(0, 1, {"n_wk": d})          # round 0 complete
        r1.close()                          # client 1 dies for good
        r0.pull(1)
        r0.push(1, 0, {"n_wk": d})          # round 1 waits on client 1...
        r0.pull(2)                          # ...until the liveness sweep
        st = servers[0].stats()             #    evicts it mid-wait
        assert st["evicted"] == [1] and st["evictions"] == 1
        # Survivor-only round applied exactly its one delta.
        np.testing.assert_array_equal(r0.pull_keys(["n_wk"])["n_wk"], 3 * d)

        # Rejoin: fresh connection, REJOIN, forced-fresh pull, and the
        # barrier requires both clients again.
        r1b = _fresh_remote(servers, n_clients=2)
        r1b.rejoin(1)
        assert servers[0].stats()["evicted"] == []
        r1b.pull(2, None)
        r1b.push(2, 1, {"n_wk": d})
        r0.push(2, 0, {"n_wk": d})          # completes round 2 (both)
        r0.clock(min_round=3)
        np.testing.assert_array_equal(r0.pull_keys(["n_wk"])["n_wk"], 5 * d)
        r1b.close()
        r0.close()
    finally:
        for s in servers:
            s.close()


def test_voluntary_leave_unblocks_barrier_immediately():
    """REJOIN action=leave drops the client from the required set with
    no liveness wait — the elastic scale-down path."""
    servers = serve_shards("lda", vocab_size=64, n_clients=2,
                           barrier_timeout=TIMEOUT, liveness_timeout=60.0)
    d = np.ones((64, 4), np.float32)
    try:
        r0 = _fresh_remote(servers, n_clients=2)
        r0.init_push(0, _zero_shared())
        r0.init_push(1, _zero_shared())
        r0.leave(1)
        r0.pull(0)
        r0.push(0, 0, {"n_wk": d})          # finalizes without client 1
        r0.clock(min_round=1)
        np.testing.assert_array_equal(r0.pull_keys(["n_wk"])["n_wk"], d)
    finally:
        r0.close()
        for s in servers:
            s.close()


def test_shard_restart_from_snapshot_resumes_midrun(tmp_path):
    """Kill the shard servers mid-run and restart them on the same ports
    from their own snapshots: the client reconnects, replays its buffered
    mutations (all dedup against the restored mutation log), and the run
    finishes with the exact no-failure sum."""
    shape = (64, 4)
    kw = dict(vocab_size=64, n_clients=1, n_shards=2,
              barrier_timeout=TIMEOUT, snapshot_dir=str(tmp_path),
              snapshot_every=1)
    servers = serve_shards("lda", **kw)
    ports = [s.address[1] for s in servers]
    rps = RemoteParameterServer(_addrs(servers), family="lda", n_clients=1,
                                vocab_size=64, timeout=TIMEOUT,
                                reconnect_limit=10)
    try:
        rps.init_push(0, _zero_shared())
        for r in range(3):
            rps.pull(r)
            rps.push(r, 0, {"n_wk": stress_delta(r, 0, shape)})
        for s in servers:                   # hard kill, no shutdown
            s.close()
        servers = serve_shards("lda", ports=ports, restore=True, **kw)
        assert all(s.stats()["server_round"] == 3 for s in servers)
        for r in range(3, 6):
            rps.pull(r)
            rps.push(r, 0, {"n_wk": stress_delta(r, 0, shape)})
        rps.clock(min_round=6)
        want = np.zeros(shape, np.float32)
        for r in range(6):
            want = want + stress_delta(r, 0, shape)
        np.testing.assert_array_equal(rps.pull_keys(["n_wk"])["n_wk"], want)
        assert rps.counters()["reconnects"] >= 2  # one per shard
    finally:
        rps.close()
        for s in servers:
            s.close()


def test_snapshot_write_restore_rpcs(tmp_path):
    """The SNAPSHOT_WRITE / SNAPSHOT_RESTORE frames: persist on demand,
    mutate, reload — the store rolls back to the persisted round."""
    servers = _servers("lda", n_clients=1)
    d = np.ones((64, 4), np.float32)
    try:
        with _fresh_remote(servers) as rps:
            rps.init_push(0, _zero_shared())
            rps.pull(0)
            rps.push(0, 0, {"n_wk": d})
            acks = rps.snapshot_write(str(tmp_path))
            assert [a["step"] for a in acks] == [1]
            rps.pull(1)
            rps.push(1, 0, {"n_wk": d})
            np.testing.assert_array_equal(
                rps.pull_keys(["n_wk"])["n_wk"], 2 * d)
            assert rps.snapshot_restore(str(tmp_path)) == [1]
            np.testing.assert_array_equal(
                rps.pull_keys(["n_wk"])["n_wk"], d)
    finally:
        for s in servers:
            s.close()


def test_trainer_tcp_fault_plan_ghost_parity():
    """A scripted crash fault over tcp (ghost pushes riding the wire)
    matches the identical in-process faulted run bit for bit."""
    from repro.core.fault import FaultPlan
    tokens, mask, _ = _corpus()
    cfg = make_family_cfg("lda", n_topics=4, vocab_size=64)
    plan = FaultPlan.crash(1, 1, 3)
    rounds = 5

    def _faulted(transport_kw):
        t = Trainer(cfg, tokens, mask, key=jax.random.PRNGKey(0),
                    config=TrainerConfig(n_clients=2, tau=1,
                                         fault_plan=plan, **transport_kw))
        for _ in range(rounds):
            t.step()
        out = _stats("lda", t)
        rejoins = t.rejoins
        t.close()
        return out, rejoins

    want, ref_rejoins = _faulted({})
    servers = _servers("lda", n_clients=2)
    try:
        got, tcp_rejoins = _faulted(dict(transport="tcp",
                                         server_addrs=_addrs(servers)))
    finally:
        for s in servers:
            s.close()
    assert ref_rejoins == tcp_rejoins == 1
    for n in want:
        np.testing.assert_array_equal(want[n], got[n], err_msg=n)
