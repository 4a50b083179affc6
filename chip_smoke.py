#!/usr/bin/env python3
"""Chip smoke: the system's main path, once, on a TPU.

    python chip_smoke.py              # one chip: train, then serve
    python chip_smoke.py --chips 4    # the mesh round on four chips

One chip.  LDA with K=1024 topics over V=131072 token types — one chip's
row-range share of a ~2M-type vocabulary split over 16 server shards —
trained through ``Trainer`` (token-sorted fused kernels, BSP, one client,
incremental alias rebuilds) on a seeded Zipf corpus of 4096 documents x
256 positions, then frozen (``serve.snapshot.from_trainer``) and served to
64 held-out documents through ``FoldInEngine``.  Checks: the compiled round
holds lowered kernels (``tpu_custom_call``), ``consistency_error() == 0``,
held-out perplexity falls, served proportions are bit-equal to
``reference_fold_in``, and fold-in perplexity is within the 1.25x quality
gate of the training-time evaluator.

Four chips.  ``core.distributed.make_round_fn`` with the four clients on a
4-device data axis, against a one-device ``Trainer`` with ``n_clients=4``
at the same configuration: counts stay conserved on both, perplexity falls
on both and the two agree, and every device's peak memory is printed.

The script refuses to run (non-zero exit, no result line) where JAX finds
no TPU.  Its last stdout line is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

K, V = 1024, 131072          # topics; one chip's vocabulary row-range
N_DOCS, DOC_LEN = 4096, 256  # training corpus, ~0.8M tokens
HELD_OUT = 64                # held-out documents: eval and serving
TILE_K = 128                 # K-tiled staging of the fused kernels
ROUNDS = 3                   # timed rounds after the warm-up round
MESH_ROUNDS = 2              # rounds of each four-chip run
SERVE_SLOTS, SERVE_SWEEPS, PARITY_DOCS = 16, 10, 3
QUALITY_TOL = 1.25           # tests/test_serve_engine.py's gate
MESH_PPL_TOL = 0.05          # mesh vs one-device Trainer, relative


def log(*parts) -> None:
    print(*parts, flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip smoke FAILED: {msg}")


def check(ok: bool, msg: str) -> None:
    log(("PASS " if ok else "FAIL ") + msg)
    if not ok:
        fail(msg)


def corpus():
    from repro.data.synthetic import CorpusConfig, make_topic_corpus
    t0 = time.perf_counter()
    tokens, mask, _ = make_topic_corpus(CorpusConfig(
        n_topics=K, vocab_size=V, n_docs=N_DOCS + HELD_OUT,
        doc_len=DOC_LEN, seed=0))
    log(f"corpus: {N_DOCS} train + {HELD_OUT} held-out docs x {DOC_LEN}, "
        f"{int(mask[:N_DOCS].sum())} training tokens, "
        f"{time.perf_counter() - t0:.1f}s to generate")
    return (tokens[:N_DOCS], mask[:N_DOCS], tokens[N_DOCS:],
            mask[N_DOCS:])


def lda_config():
    from repro.core.lda import LDAConfig
    return LDAConfig(n_topics=K, vocab_size=V, tile_k=TILE_K)


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def one_chip(dev) -> None:
    import jax
    import numpy as np

    from repro.core import family as fam_mod
    from repro.engine import Trainer, TrainerConfig
    from repro.serve import (FoldInEngine, InferRequest, ServeConfig,
                             fold_in_perplexity, from_trainer)
    from repro.serve.engine import (InferResult, reference_fold_in,
                                    result_checksum)

    tokens, mask, ho_tokens, ho_mask = corpus()
    cfg = lda_config()
    fam = fam_mod.get("lda")
    log(f"config: LDA K={K} V={V} tile_k={TILE_K} "
        f"tile_v={fam.sorted_tile_v(cfg)} tile_b={fam.sorted_tile_b(cfg)}; "
        "Trainer(layout=sorted, consistency=bsp, n_clients=1, "
        "alias_rebuild_threshold=0.0)")

    t0 = time.perf_counter()
    trainer = Trainer(cfg, tokens, mask, config=TrainerConfig(
        layout="sorted", consistency="bsp", n_clients=1,
        alias_rebuild_threshold=0.0), key=jax.random.PRNGKey(0))
    ppl0 = trainer.perplexity(ho_tokens, ho_mask)
    trainer.step()                              # warm-up: compiles
    jax.block_until_ready(trainer.pstate)
    setup_s = time.perf_counter() - t0
    log(f"set-up (init + held-out eval + first round, compiles "
        f"included): {setup_s:.1f}s")

    compiled = trainer.lower_round().compile()
    log(f"round program memory_analysis: {compiled.memory_analysis()}")
    check("tpu_custom_call" in compiled.as_text(),
          "round program holds lowered Pallas kernels (tpu_custom_call)")

    t0 = time.perf_counter()
    for _ in range(ROUNDS):
        trainer.step()
    jax.block_until_ready(trainer.pstate)
    log(f"{ROUNDS} rounds after warm-up: {time.perf_counter() - t0:.2f}s "
        "wall (host clock, not a benchmark)")
    log(f"device peak_bytes_in_use after training: {peak_bytes(dev)}")

    err = trainer.consistency_error()
    check(err == 0.0, f"consistency_error {err}")
    ppl1 = trainer.perplexity(ho_tokens, ho_mask)
    check(ppl1 < ppl0, f"held-out perplexity falls: {ppl0:.1f} -> "
          f"{ppl1:.1f} after {ROUNDS + 1} rounds")

    # --- serving: freeze and fold the held-out documents in -------------
    t0 = time.perf_counter()
    snap = from_trainer(trainer)
    scfg = ServeConfig(max_slots=SERVE_SLOTS, max_len=DOC_LEN,
                       n_sweeps=SERVE_SWEEPS)
    lens = ho_mask.sum(axis=1).astype(int)
    reqs = [InferRequest(uid=i, tokens=ho_tokens[i, :lens[i]],
                         seed=5000 + i) for i in range(HELD_OUT)]
    results = FoldInEngine(snap, scfg).run(reqs)
    log(f"served {len(results)} held-out docs ({SERVE_SLOTS} slots, "
        f"{SERVE_SWEEPS} sweeps), freeze included: "
        f"{time.perf_counter() - t0:.1f}s wall")
    check(len(results) == HELD_OUT, f"{len(results)} of {HELD_OUT} served")

    for req in reqs[:PARITY_DOCS]:
        _, theta, z = reference_fold_in(snap, req.tokens, req.seed,
                                        n_sweeps=SERVE_SWEEPS,
                                        max_len=DOC_LEN)
        ref = InferResult(uid=req.uid, theta=theta, assignments=z,
                          n_sweeps=SERVE_SWEEPS)
        check(result_checksum(ref) == result_checksum(results[req.uid])
              and np.array_equal(theta, results[req.uid].theta),
              f"doc {req.uid}: served theta bit-equal to reference_fold_in")

    thetas = np.stack([results[i].theta for i in range(HELD_OUT)])
    fold_ppl = fold_in_perplexity(snap, thetas, ho_tokens, ho_mask)
    eval_ppl = float(fam.perplexity(cfg, snap.shared, ho_tokens, ho_mask,
                                    jax.random.PRNGKey(123)))
    ratio = fold_ppl / eval_ppl
    check(ratio <= QUALITY_TOL, f"fold-in perplexity {fold_ppl:.1f} vs "
          f"training-time eval {eval_ppl:.1f}: ratio {ratio:.3f} <= "
          f"{QUALITY_TOL}")
    log(f"device peak_bytes_in_use after serving: {peak_bytes(dev)}")


def four_chips(devs) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType

    from repro.core import distributed, lda
    from repro.engine import Trainer, TrainerConfig

    n = len(devs)
    tokens, mask, ho_tokens, ho_mask = corpus()
    cfg = lda_config()
    key = jax.random.PRNGKey(0)

    # --- the mesh round: n clients on the data axis ---------------------
    mesh = jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    dcfg = distributed.DistConfig(model="lda", tau=1, layout="sorted")
    server = distributed.make_server(cfg, dcfg)
    tok, msk = jnp.asarray(tokens), jnp.asarray(mask)
    local, shared = lda.init_state(cfg, tok, msk, key)
    ppl0 = float(lda.perplexity(cfg, shared, ho_tokens, ho_mask,
                                jax.random.PRNGKey(42)))
    state = server.init_state(shared, n_clients=n)
    alive = jnp.ones((n,), bool)
    t0 = time.perf_counter()
    with mesh:
        round_fn = distributed.make_round_fn(cfg, dcfg, mesh, server=server)
        for r in range(MESH_ROUNDS):
            state = server.refresh_proposal(cfg, state)
            local, state = round_fn(local, state, tok, msk,
                                    jax.random.fold_in(key, r), alive)
        jax.block_until_ready(state)
    log(f"mesh round: {n} clients on a ({n}, 1) data x model mesh, "
        f"{MESH_ROUNDS} rounds, compiles included: "
        f"{time.perf_counter() - t0:.1f}s wall")
    for d in devs:
        log(f"device {d.id} peak_bytes_in_use after the mesh rounds: "
            f"{peak_bytes(d)}")
    mesh_shared = server.snapshot(state)
    check(np.asarray(state.clocks).tolist() == [MESH_ROUNDS] * n,
          f"mesh clocks {np.asarray(state.clocks).tolist()}")
    err = float(jnp.abs(lda.count_wk(cfg, tok, local.z, msk)
                        - mesh_shared.n_wk).max())
    check(err == 0.0, f"mesh counts conserved: consistency error {err}")
    ppl_mesh = float(lda.perplexity(cfg, mesh_shared, ho_tokens, ho_mask,
                                    jax.random.PRNGKey(42)))
    check(ppl_mesh < ppl0, f"mesh held-out perplexity falls: {ppl0:.1f} "
          f"-> {ppl_mesh:.1f}")
    del local, state, mesh_shared

    # --- the reference: one device, the same n clients iterated ---------
    trainer = Trainer(cfg, tokens, mask, config=TrainerConfig(
        layout="sorted", consistency="bsp", n_clients=n), key=key)
    for _ in range(MESH_ROUNDS):
        trainer.step()
    err = trainer.consistency_error()
    check(err == 0.0, f"one-device Trainer counts conserved: {err}")
    ppl_ref = trainer.perplexity(ho_tokens, ho_mask)
    rel = abs(ppl_mesh - ppl_ref) / ppl_ref
    check(rel <= MESH_PPL_TOL, f"mesh vs one-device Trainer held-out "
          f"perplexity {ppl_mesh:.1f} vs {ppl_ref:.1f}: relative gap "
          f"{rel:.4f} <= {MESH_PPL_TOL}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip smoke: no TPU (JAX platform {dev.platform!r}); "
              "refusing to run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} devices", file=sys.stderr)
        return 2

    from repro.launch.cache import enable_compile_cache
    log(f"device: {dev.device_kind} x{len(devs)} ({dev.platform}); "
        f"compile cache {enable_compile_cache()}")
    if args.chips == 4:
        four_chips(devs[:4])
    else:
        one_chip(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
