#!/usr/bin/env python3
"""Find the highest rate the serving cell sustains, on the chip.

    python3 benchmarks/chip/tests/serve_sweep.py \\
        --workload lda_k1024_serve_open --seed 7 --seconds 40 \\
        --rates 0.5,1,1.5,2,3

Sets the server up once, then offers each rate for ``--seconds`` (the
cell's own schedule) and prints one JSON line per rate: latency
quantiles, failures, and the mean latency of the last quarter of the
requests against the first (a growing queue shows as a ratio well above
1).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    import numpy as np

    import run as run_mod
    sys.path.insert(0, str(run_mod.ROOT / "src"))
    run_mod.setup_jax(1)
    bench = json.loads((run_mod.ROOT / "BENCHMARK.json").read_text())
    r = run_mod.Run(bench, argparse.Namespace(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=0))
    serve = r.module("drivers", r.traffic["kind"])
    srv, stats, pool_tok, pool_mask = serve.start_server(r)
    addr = f"{srv.address[0]}:{srv.address[1]}"
    slots = r.traffic["serve"]["max_slots"]
    lens = pool_mask.sum(1)
    warm = [pool_tok[i, :lens[i]] for i in range(slots)]
    outs, ths = [serve.Outcome(0.0) for _ in warm], []
    serve.offer(addr, warm, list(range(slots)), outs, time.perf_counter(),
                ths)
    for th in ths:
        th.join()
    for rate in (float(x) for x in args.rates.split(",")):
        due, lengths = serve.schedule(r, rate, args.seconds)
        tok, _, _ = serve.corpus(r, len(due), lengths=lengths)
        docs = [tok[i, :lengths[i]] for i in range(len(due))]
        sweeps0 = srv.engine.sweeps_run
        t0 = time.perf_counter()
        outs, ths = [serve.Outcome(d) for d in due], []
        serve.offer(addr, docs, list(range(len(due))), outs, t0, ths)
        for th in ths:
            th.join(timeout=max(1.0, t0 + args.seconds + serve.DRAIN_S
                                - time.perf_counter()))
        lat = np.array([o.done - o.due if o.result is not None
                        else np.inf for o in outs])
        q = max(1, len(lat) // 4)
        row = {"rate": rate, "requests": len(lat),
               "failed": int(np.isinf(lat).sum()),
               "p50_s": float(np.median(lat)),
               "p95_s": float(np.quantile(lat, 0.95,
                                          method="inverted_cdf")),
               "max_s": float(lat.max()),
               "last_vs_first_quarter": float(lat[-q:].mean()
                                              / lat[:q].mean()),
               "sweeps": srv.engine.sweeps_run - sweeps0,
               "elapsed_s": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
    srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
