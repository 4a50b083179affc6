"""The shard_map distributed Gibbs round (core/distributed.py) on a real
multi-device mesh — run in a subprocess so the forced device count never
leaks into other tests.  Since the ParameterServer redesign the round
consumes a ``core.server.ParameterServer``: the canonical statistics live
in its vocabulary-sharded ``ServerState`` (here also laid over the mesh's
``model`` axis), the alias proposal is server-resident
(``refresh_proposal``), and the consistency policy is pluggable."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import distributed, lda, ps

    from repro.data.synthetic import CorpusConfig, make_topic_corpus

    assert len(jax.devices()) == 8

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    tokens, mask, _ = make_topic_corpus(CorpusConfig(
        n_topics=8, vocab_size=128, n_docs=64, doc_len=32, seed=0))
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)

    cfg = lda.LDAConfig(n_topics=8, vocab_size=128, mh_steps=2)
    # Two vocabulary shards laid over the 2-wide model axis.
    dcfg = distributed.DistConfig(model="lda", tau=1, n_server_shards=2)
    server = distributed.make_server(cfg, dcfg)
    key = jax.random.PRNGKey(0)
    local, shared = lda.init_state(cfg, tokens, mask, key)
    state = server.init_state(shared, n_clients=4)

    with mesh:
        round_fn = distributed.make_round_fn(cfg, dcfg, mesh, server=server)
        p0 = float(lda.perplexity(cfg, shared, tokens[:16], mask[:16],
                                  jax.random.PRNGKey(5)))
        alive = jnp.ones((4,), bool)
        for r in range(8):
            state = server.refresh_proposal(cfg, state)
            local, state = round_fn(local, state, tokens, mask,
                                    jax.random.fold_in(key, r), alive)
        shared = server.snapshot(state)
        p1 = float(lda.perplexity(cfg, shared, tokens[:16], mask[:16],
                                  jax.random.PRNGKey(5)))

    # Convergence across the mesh
    assert p1 < p0 * 0.8, (p0, p1)
    # Per-client clocks advanced with every applied push
    assert np.asarray(state.clocks).tolist() == [8, 8, 8, 8]
    # The server's per-shard changed-row accounting accumulated push mass
    assert all(float(m.sum()) > 0 for m in server.shard_row_mass(state))
    # Shared statistics remain consistent with the summed local assignments
    nwk = lda.count_wk(cfg, tokens, local.z, mask)
    err = float(jnp.abs(nwk - shared.n_wk).max())
    assert err == 0.0, err
    # Failure injection: a dead client contributes nothing (and its clock
    # freezes), system still OK
    with mesh:
        alive = alive.at[1].set(False)
        state2 = server.refresh_proposal(cfg, state)
        local2, state2 = round_fn(local, state2, tokens, mask,
                                  jax.random.fold_in(key, 99), alive)
        shared2 = server.snapshot(state2)
        p2 = float(lda.perplexity(cfg, shared2, tokens[:16], mask[:16],
                                  jax.random.PRNGKey(5)))
    assert np.isfinite(p2) and p2 < p0, (p0, p2)
    assert np.asarray(state2.clocks).tolist() == [9, 8, 9, 9]

    # SSP on the mesh: the versioned cache refreshes from the clocks
    # (bound=1 -> every other round), counts stay exactly consistent.
    scfg = distributed.DistConfig(model="lda", tau=1, consistency="ssp:1")
    sserver = distributed.make_server(cfg, scfg)
    slocal, sshared = lda.init_state(cfg, tokens, mask, key)
    sstate = sserver.init_state(sshared, n_clients=4)
    with mesh:
        sround = distributed.make_round_fn(cfg, scfg, mesh, server=sserver)
        alive = jnp.ones((4,), bool)
        for r in range(4):
            if sserver.policy.needs_refresh(r, int(sstate.cache_version)) \
                    or r == 0:
                sstate = sserver.refresh_proposal(cfg, sstate)
            slocal, sstate = sround(slocal, sstate, tokens, mask,
                                    jax.random.fold_in(key, 500 + r), alive)
    snwk = lda.count_wk(cfg, tokens, slocal.z, mask)
    serr = float(jnp.abs(snwk - sserver.snapshot(sstate).n_wk).max())
    assert serr == 0.0, serr
    assert int(sstate.cache_version) == 2   # refreshed at clock 0 -> 2

    # The token-sorted fast path under shard_map: the same registry round
    # with DistConfig(layout="sorted") must run on the mesh and keep the
    # shared statistics consistent with the summed local assignments.
    with mesh:
        dcfg_sorted = distributed.DistConfig(model="lda", tau=1,
                                             layout="sorted")
        server_s = distributed.make_server(cfg, dcfg_sorted)
        round_fn_sorted = distributed.make_round_fn(cfg, dcfg_sorted, mesh,
                                                    server=server_s)
        alive = jnp.ones((4,), bool)
        state_s = server_s.refresh_proposal(
            cfg, server_s.init_state(shared, n_clients=4))
        local_s, state_s = round_fn_sorted(local, state_s, tokens, mask,
                                           jax.random.fold_in(key, 400),
                                           alive)
        shared_s = server_s.snapshot(state_s)
    ps_ = float(lda.perplexity(cfg, shared_s, tokens[:16], mask[:16],
                               jax.random.PRNGKey(5)))
    assert np.isfinite(ps_), ps_
    nwk_s = lda.count_wk(cfg, tokens, local_s.z, mask)
    assert float(jnp.abs(nwk_s - shared_s.n_wk).max()) == 0.0

    # PDP and HDP through the same registry-driven round: the one round
    # implementation serves every family (no per-model adapters).
    from repro.core import family, hdp, pdp, projection

    pcfg = pdp.PDPConfig(n_topics=8, vocab_size=128, mh_steps=2,
                         stirling_n_max=128, concentration=5.0)
    plocal, pshared = pdp.init_state(pcfg, tokens, mask, key)
    alive = jnp.ones((4,), bool)
    with mesh:
        pdcfg = distributed.DistConfig(model="pdp", tau=1)
        pserver = distributed.make_server(pcfg, pdcfg)
        round_fn = distributed.make_round_fn(pcfg, pdcfg, mesh,
                                             server=pserver)
        pstate = pserver.init_state(pshared, n_clients=4)
        for r in range(2):
            pstate = pserver.refresh_proposal(pcfg, pstate)
            plocal, pstate = round_fn(plocal, pstate, tokens, mask,
                                      jax.random.fold_in(key, 200 + r),
                                      alive)
        pshared = pserver.snapshot(pstate)
    ppdp = float(pdp.perplexity(pcfg, pshared, tokens[:16], mask[:16],
                                jax.random.PRNGKey(5)))
    assert np.isfinite(ppdp)
    # shared projection held the PDP polytope
    fam = family.get("pdp")
    assert float(fam.count_violations(pshared)) == 0.0

    hcfg = hdp.HDPConfig(n_topics=8, vocab_size=128, b1=2.0, mh_steps=2)
    hlocal, hshared = hdp.init_state(hcfg, tokens, mask, key)
    with mesh:
        hdcfg = distributed.DistConfig(model="hdp", tau=1)
        hserver = distributed.make_server(hcfg, hdcfg)
        round_fn = distributed.make_round_fn(hcfg, hdcfg, mesh,
                                             server=hserver)
        hstate = hserver.init_state(hshared, n_clients=4)
        for r in range(2):
            hstate = hserver.refresh_proposal(hcfg, hstate)
            hlocal, hstate = round_fn(hlocal, hstate, tokens, mask,
                                      jax.random.fold_in(key, 300 + r),
                                      alive)
        hshared = hserver.snapshot(hstate)
    phdp = float(hdp.perplexity(hcfg, hshared, tokens[:16], mask[:16],
                                jax.random.PRNGKey(5)))
    assert np.isfinite(phdp)
    # HDP's local table-count polytope (1 <= m_dk <= n_dk) — previously
    # silently dropped by the ad-hoc adapter — is enforced in-round.
    hfam = family.get("hdp")
    lv = float(projection.count_violations(
        {"m_dk": hlocal.m_dk, "n_dk": hlocal.n_dk}, hfam.local_rules))
    assert lv == 0.0, lv
    print("DISTRIBUTED_ROUND_OK", p0, p1, p2, ppdp, phdp)
""")


@pytest.mark.slow
def test_distributed_round_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "DISTRIBUTED_ROUND_OK" in proc.stdout
