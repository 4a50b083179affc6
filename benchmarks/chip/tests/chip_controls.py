#!/usr/bin/env python3
"""Readings the training limits are set from, on the chip at a cell's size.

    python3 benchmarks/chip/tests/chip_controls.py \\
        --workload lda_k1024_train_bsp --seeds 11,12,13 --out <file.jsonl> \\
        [--cases half_batch,token_altered,control_bf16]

Training cells: for each seed, in one process, the program's first
``checked_rounds`` rounds after round 0 (sound readings), the same rounds
with a fault planted in what the program produced (state left unchanged;
half of the documents left out; one token's topic altered after the counts
were pushed), and the reference put in the program's place (the control,
in bfloat16, and a float32 twin that must pass).

Serving cells (``--seconds`` sets the window): for each seed, the served
sample's readings; the same documents with one served proportion altered,
with the chain left at its initial draws, and with the reference's
fold-in in the program's place: in bfloat16 throughout (the control), with
the chain in bfloat16 and θ normalised in float32, with the document term
left out of the conditional (α → 10^6), and in float32 (a twin that must
pass).  One JSON line per seed and case.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=0)
    ap.add_argument("--cases", default="",
                    help="training cases to read besides the program's "
                         "(comma-separated; default all)")
    args = ap.parse_args()
    import run as run_mod
    sys.path.insert(0, str(run_mod.ROOT / "src"))
    run_mod.setup_jax(1)
    import jax
    import jax.numpy as jnp
    import numpy as np
    bench = json.loads((run_mod.ROOT / "BENCHMARK.json").read_text())
    out = open(args.out, "a", buffering=1)   # a line per row, kept if cut
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run_mod.Run(bench, argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds,
            trace=0))
        if r.traffic["kind"] == "serve_open":
            cases = serve_cases(r, jnp)
            for name, checks in cases.items():
                row = {"workload": args.workload, "seed": seed,
                       "case": name, "checks": checks}
                print(json.dumps(row), flush=True)
                out.write(json.dumps(row) + "\n")
            continue
        train = r.module("drivers", r.traffic["kind"])
        ref = r.module("refs", r.config["reference"])
        tokens, mask = train.corpus(r, r.traffic["corpus"]["n_docs"])
        fam, cfg, trainer = train.build_trainer(r, tokens, mask)
        model = r.config["model"]

        def grab():
            local = fam.local_dict(trainer.locals_[0])
            state = {n: np.asarray(local[n]) for n in ref.LOCAL_STATE}
            shared = fam.stats_dict(trainer.shared)
            state.update({n: np.asarray(shared[n]) for n in ref.SHARED_STATE})
            return state

        trainer.step()
        states = [grab()]
        for _ in range(r.traffic["checked_rounds"]):
            trainer.step()
            states.append(grab())
        kept = {n: np.asarray(v)
                for n, v in fam.stats_dict(trainer.shared).items()}
        kept.update({n: np.asarray(v) for n, v in
                     fam.local_dict(trainer.locals_[0]).items()})
        del trainer
        cases = {"program": (states, kept)}
        cases["unchanged"] = ([states[0]] * len(states), ref.consistent_kept(
            model, tokens, mask, states[0]))
        half = np.arange(tokens.shape[0])[:, None] < tokens.shape[0] // 2
        hs = [{n: (np.where(half, s[n], states[0][n])
                   if n in ref.LOCAL_STATE else s[n]) for n in s}
              for s in states]
        cases["half_batch"] = (hs, ref.consistent_kept(model, tokens, mask,
                                                       hs[-1]))
        altered = {n: v.copy() for n, v in kept.items()}
        d0 = int(np.argmax(mask[:, 0]))
        altered["z"][d0, 0] = (altered["z"][d0, 0] + 1) % model["n_topics"]
        cases["token_altered"] = (states[:-1] + [
            dict(states[-1], z=altered["z"])], altered)
        for name, dtype in (("control_bf16", jnp.bfloat16),
                            ("reference_f32", jnp.float32)):
            if args.cases and name not in args.cases.split(","):
                continue
            cs, ck = [states[0]], None
            for i in range(r.traffic["checked_rounds"]):
                new, ck = ref.control_round(model, tokens, mask, cs[-1],
                                            seed * 7 + i, dtype)
                cs.append(new)
            cases[name] = (cs, ck)
        if args.cases:
            keep = {"program", *args.cases.split(",")}
            cases = {n: c for n, c in cases.items() if n in keep}
        for name, (st, kp) in cases.items():
            checks = train.reference_checks(ref, r, tokens, mask, st, kp)
            row = {"workload": args.workload, "seed": seed, "case": name,
                   "checks": {k: v for k, (v, _) in checks.items()}}
            print(json.dumps(row), flush=True)
            out.write(json.dumps(row) + "\n")
    out.close()
    return 0


def serve_cases(r, jnp) -> dict:
    """The served sample's readings, and those of the same documents with
    the reference or a fault in the program's place."""
    import numpy as np

    class Chip:
        def memory_stats(self):
            return {"peak_bytes_in_use": 0}

    serve = r.module("drivers", r.traffic["kind"])
    ref = r.module("refs", r.config["reference"])
    captured = {}

    def capture(r_, stats, docs, outs):
        captured.update(stats=stats, docs=docs, outs=outs)
        return real(r_, stats, docs, outs)

    real = serve.reference_checks
    serve.reference_checks = capture
    res = serve.run(r, [Chip()])
    serve.reference_checks = real
    cases = {"program": {k: v for k, (v, _) in res["checks"].items()},
             "latency": {"p95_s": res["e2e"]["serve_latency_p95_s"],
                         "failed": res["failed"]}}
    stats, docs, outs = captured["stats"], captured["docs"], captured["outs"]
    pick = serve.sample(r, docs, outs)
    model, n_sweeps = r.config["model"], r.traffic["serve"]["n_sweeps"]
    sub = [docs[i] for i in pick]

    def readings(zs, thetas):
        return ref.serve_readings(
            model, stats, [(d, z, np.asarray(th, np.float32))
                           for d, z, th in zip(sub, zs, thetas)],
            seed=r.seed, n_sweeps=n_sweeps)

    served = [(outs[i].result.assignments, outs[i].result.theta.copy())
              for i in pick]
    served[0][1][0] += 1.0 / len(sub[0])
    cases["answer_altered"] = readings(*zip(*served))
    rng = np.random.default_rng([r.seed, 5])
    init = [rng.integers(0, model["n_topics"], len(d)) for d in sub]
    cases["chain_unchanged"] = readings(
        init, [ref.theta_of(model, z, len(z)) for z in init])
    for name, dtype, mod in (
            ("control_bf16", jnp.bfloat16, {}),
            ("control_bf16_chain", jnp.bfloat16, {}),
            ("conditional_no_doc", jnp.float32, {"alpha": 1e6}),
            ("reference_f32", jnp.float32, {})):
        fold = ref.fold_in(dict(model, **mod), stats, sub, r.seed + 1,
                           n_sweeps, dtype)
        zs = [z for z, _ in fold]
        thetas = ([th for _, th in fold] if name == "control_bf16" else
                  [ref.theta_of(model, z, len(z)) for z in zs])
        cases[name] = readings(zs, thetas)
    return cases


if __name__ == "__main__":
    sys.exit(main())
