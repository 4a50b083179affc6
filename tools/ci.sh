#!/usr/bin/env bash
# CI entry point: tier-1 tests + quick-mode throughput benchmark.
#
# Runs entirely on CPU — the Pallas kernels execute in interpret mode
# there (repro.kernels.backend: the platform decides), so this validates
# kernel semantics and the benchmark pipeline without TPU hardware.  On a
# TPU host, `python chip_smoke.py` is the end-to-end check.
#
# Usage: tools/ci.sh  (from the repo root)
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "=== tier-1 tests ==="
python -m pytest -x -q

echo "=== quick benchmarks: throughput + families + consistency + failover ==="
# One invocation so bench_results.csv keeps every module's rows.  The
# lda/pdp/hdp modules drive all three model families through
# engine.Trainer and both layouts (writing BENCH_{pdp,hdp}.json), so API
# drift between families breaks CI, not just the nightly benchmarks.
# The throughput module's round_engine / alias_partial_rebuild sections
# track the compiled-round dispatch-overhead win and the incremental
# alias rebuild cost as BENCH_throughput.json artifacts (DESIGN.md §8).
# The consistency module is the parameter-server policy bench
# (DESIGN.md §9): BENCH_consistency.json must carry rounds/s +
# perplexity for every policy with SSP(>=2) strictly faster than BSP,
# and it asserts in-process that the compiled round still traces once
# per (family, layout, policy) — it fails if a policy's per-round
# cadence (refresh flag, projection, failure mask) started retracing.
# The failover module is the kill-and-rejoin robustness bench
# (DESIGN.md §10): one client crashes mid-run and rejoins from its
# periodic snapshot under each consistency policy; BENCH_failover.json
# must carry the recovery-rounds and final-perplexity-degradation
# numbers with degradation <= 5%.
# The wire module is the out-of-process transport bench (DESIGN.md §11):
# the same Trainer config over the in-process server and over loopback
# TCP shard servers; BENCH_wire.json must carry rounds/s for both
# transports, bytes/round (encoded vs payload), and RPC latency
# percentiles per policy, and the module itself hard-fails if
# BSP-over-TCP is not bit-exact with in-process or if the sparse delta
# exchange (DESIGN.md §12) reduces push payload by less than 5x.
# The scale module is the (V, K) ladder (DESIGN.md §12): K-tiled sorted
# sweep tokens/s, incremental alias-build ms/row and dense-vs-sparse
# frame bytes up to (V=65536, K=256) in quick mode.
# The serve module is the online fold-in serving bench (DESIGN.md §14):
# a real InferenceServer under concurrent client connections;
# BENCH_serve.json must carry p50/p99 latency, docs/s, the shed count
# and the fold-in-vs-training perplexity quality gate, and the module
# itself hard-fails if the served results are not bit-exact with the
# reference_fold_in training path or the gate is exceeded.
python -m benchmarks.run --only throughput,lda,pdp,hdp,consistency,failover,wire,scale,serve --quick
python - <<'EOF'
import json
art = json.load(open("BENCH_consistency.json"))
pols = art["policies"]
missing = {"bsp", "ssp1", "ssp2", "ssp4", "async"} - set(pols)
assert not missing, f"BENCH_consistency.json missing policies: {missing}"
for name, res in pols.items():
    assert res["rounds_per_s"] > 0, (name, res)
# Every policy must declare its perplexity-gate coverage, and exactly
# SSP(4) — the deep-staleness frontier point — may ride ungated.
for name, res in pols.items():
    assert res.get("unguarded") is (name == "ssp4"), (name, res)
assert pols["ssp4"].get("unguarded") is True, pols["ssp4"]
print("consistency artifact OK:", ", ".join(
    f"{n}={pols[n]['rounds_per_s']:.2f} r/s" for n in sorted(pols)))
EOF
python - <<'EOF'
import json
art = json.load(open("BENCH_failover.json"))
pols = art["policies"]
missing = {"bsp", "ssp2", "async"} - set(pols)
assert not missing, f"BENCH_failover.json missing policies: {missing}"
for name, res in pols.items():
    for variant in ("baseline", "kill_rejoin"):
        assert variant in res, (name, sorted(res))
        assert res[variant]["perplexity_final"] > 0, (name, variant, res)
    kr = res["kill_rejoin"]
    assert "recovery_rounds" in kr and "degradation" in kr, (name, kr)
    assert kr["degradation"] <= 0.05, (name, kr)
# The tcp section (DESIGN.md §13) is the process-level kill-and-rejoin:
# shard restarted from its snapshot + worker relaunched with --restore,
# through chaos proxies.  BSP must come back bit-exact.
tcp = art["tcp"]
assert tcp["bsp_bitexact"] is True, tcp
assert tcp["degradation"] <= 0.05, tcp
assert tcp["restarts"] == {"server": 1, "client": 1}, tcp
assert tcp["conn_drops"] >= 1, tcp
print("failover artifact OK:", ", ".join(
    f"{n}: +{pols[n]['kill_rejoin']['degradation']*100:.1f}% ppl, "
    f"{pols[n]['kill_rejoin']['recovery_rounds']} rounds to recover"
    for n in sorted(pols))
    + f"; tcp: bit-exact, {tcp['recovery_rounds']} rounds re-executed, "
    f"{tcp['conn_drops']} wire drops survived")
EOF
python - <<'EOF'
import json
art = json.load(open("BENCH_wire.json"))
pols = art["policies"]
missing = {"bsp", "ssp2"} - set(pols)
assert not missing, f"BENCH_wire.json missing policies: {missing}"
for name, res in pols.items():
    for transport in ("inproc", "tcp"):
        assert res["rounds_per_s"][transport] > 0, (name, transport, res)
    bpr = res["bytes_per_round"]
    assert bpr["encoded"] >= bpr["payload"] > 0, (name, bpr)
    lat = res["rpc_latency_ms"]
    assert lat["p50"] > 0 and lat["p99"] >= lat["p50"], (name, lat)
# Bytes/round regression guard: the quick-mode BSP geometry is fixed
# (V=64, K=4, 2 clients, 2 shards, tau=1), so encoded bytes/round is
# deterministic modulo JSON meta jitter.  7523 B is the PR-8 baseline;
# a frame-format or push-cadence regression shows up here.
assert pols["bsp"]["bytes_per_round"]["encoded"] <= 7523 * 1.10, \
    ("bytes/round regression vs 7523 B baseline", pols["bsp"])
sparse = art["sparse"]
assert sparse["reduction_ratio"] >= 5.0, sparse
assert art["parity"]["bsp_bitexact"] is True, art["parity"]
assert art["parity"]["sparse_bitexact"] is True, art["parity"]
print("wire artifact OK:", ", ".join(
    f"{n}: {pols[n]['rounds_per_s']['tcp']:.1f} r/s tcp "
    f"({pols[n]['bytes_per_round']['encoded']/1024:.1f} KiB/round, "
    f"p99 {pols[n]['rpc_latency_ms']['p99']:.1f} ms)"
    for n in sorted(pols))
    + f"; sparse push {sparse['reduction_ratio']:.1f}x smaller")
EOF
python - <<'EOF'
import json
art = json.load(open("BENCH_scale.json"))
pts = art["points"]
assert pts, "BENCH_scale.json has no points"
assert art["max_point"]["vocab"] >= 65536, art["max_point"]
assert art["max_point"]["n_topics"] >= 256, art["max_point"]
for p in pts:
    assert p["tokens_per_s"] > 0, p
    assert p["alias_build_ms_per_row"] > 0, p
    assert p["sparse_parity"] is True, p
    assert p["bytes_per_round"]["ratio"] > 1.0, p
print("scale artifact OK:", ", ".join(
    f"V={p['vocab']} K={p['n_topics']}: {p['tokens_per_s']:.0f} tok/s, "
    f"sparse {p['bytes_per_round']['ratio']:.0f}x" for p in pts))
EOF
python - <<'EOF'
import json
art = json.load(open("BENCH_serve.json"))
srv = art["serve"]
assert srv["n_clients"] >= 2, srv
assert srv["docs"] > 0 and srv["docs_per_s"] > 0, srv
lat = srv["latency_ms"]
assert lat["p50"] > 0 and lat["p99"] >= lat["p50"], lat
assert srv["shed"] >= 0, srv
assert art["parity"]["bit_exact"] is True, art["parity"]
q = art["quality"]
for k in ("fold_in_ppl", "train_eval_ppl", "ratio", "tolerance"):
    assert q[k] > 0, (k, q)
assert q["within_tolerance"] is True, q
print(f"serve artifact OK: {srv['docs_per_s']:.2f} docs/s over "
      f"{srv['n_clients']} clients (p50 {lat['p50']:.0f} ms, "
      f"p99 {lat['p99']:.0f} ms, shed {srv['shed']}); "
      f"fold-in ppl {q['fold_in_ppl']:.1f} vs eval "
      f"{q['train_eval_ppl']:.1f} ({q['ratio']:.2f}x <= "
      f"{q['tolerance']}x)")
EOF

echo "=== loopback e2e smoke: 1 shard server + 2 client processes ==="
# Real processes over 127.0.0.1 speaking the framed protocol end to end;
# the smoke asserts both client processes and an in-process reference
# agree on the final shared-statistics checksums (BSP bit-exactness
# across the socket).  timeout(1) guards against a hung server — a
# protocol bug must fail CI, not wedge it.
timeout 540 python -m repro.launch.loopback --smoke

echo "=== tcp kill-and-rejoin smoke: chaos proxy + shard restart + worker rejoin ==="
# The DESIGN.md §13 acceptance run as a process-level smoke: a BSP
# loopback run through chaos proxies (connection drop on the push path)
# in which one shard-server process is killed at its round barrier and
# restarted from its snapshot (--restore --ports, same addresses) and
# one worker process is killed mid-run and relaunched with --restore.
# The smoke asserts exactly one restart of each, that the scheduled
# drop fired, and that the final checksums are bit-exact with the
# undisturbed in-process run.  timeout(1) again guards against hangs.
timeout 540 python -m repro.launch.loopback --failover-smoke

echo "=== serve e2e smoke: 1 inference server + 2 concurrent client processes ==="
# The DESIGN.md §14 acceptance as a process-level smoke: train a small
# model, snapshot it, boot an inference-server process from the
# checkpoint and two concurrent client processes over 127.0.0.1, and
# require every served result checksum to be bit-identical to an
# in-process FoldInEngine replay of the same requests (the determinism
# contract across process + socket boundaries).  timeout(1) again
# guards against a hung batcher.
timeout 540 python -m repro.launch.serve --smoke

echo "=== artifacts ==="
ls -l BENCH_*.json bench_results.csv
