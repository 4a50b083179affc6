"""End-to-end driver: the paper's full distributed system — multi-client
parameter-server inference for LDA / PDP / HDP with eventual consistency,
communication filters, constraint projection, snapshots and failover —
through the unified ``engine.Trainer`` / ModelFamily API.

    PYTHONPATH=src python examples/distributed_lvm.py --model pdp --clients 4
    PYTHONPATH=src python examples/distributed_lvm.py --model lda \
        --filter topk --fail-client 1
    PYTHONPATH=src python examples/distributed_lvm.py --model hdp \
        --layout sorted

On a real TPU mesh the same rounds run under shard_map via
``repro.core.distributed.make_round_fn`` (clients = data-axis shards,
server = model-axis row sharding) against the same family registry; this
example drives the identical logic client-by-client so it runs anywhere,
and exercises:

  - τ local sweeps against a frozen snapshot (bounded staleness, §5.2-5.3),
  - the explicit parameter server with a pluggable consistency policy
    (``--consistency bsp|ssp:2|async``) over vocabulary-sharded state
    (``--server-shards``; DESIGN.md §9),
  - scan-oracle or token-sorted tile-skipping layout (``--layout``),
  - magnitude-priority + uniform-sampling delta filters (§5.3),
  - constraint projection on shared AND client-local polytopes (§5.5),
  - fault injection with kill-and-rejoin recovery from periodic
    snapshots (``--fail-client`` builds a ``core.fault.FaultPlan`` crash
    window and enables ``snapshot_every``, so the crashed client rejoins
    mid-run by restoring its locals and taking a forced-fresh pull —
    §5.4; add ``--chaos-seed`` for a seeded-random multi-fault plan).
"""

from __future__ import annotations

import argparse
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.core import hdp, lda, pdp, ps
from repro.core.fault import FaultPlan
from repro.data.synthetic import CorpusConfig, make_topic_corpus
from repro.engine import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["lda", "pdp", "hdp"], default="pdp")
    ap.add_argument("--layout", choices=["scan", "sorted"], default="scan")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--tau", type=int, default=2,
                    help="local sweeps per sync round (staleness)")
    ap.add_argument("--consistency", default="bsp",
                    help="server policy: bsp | ssp:<bound> | async")
    ap.add_argument("--server-shards", type=int, default=1,
                    help="vocabulary shards of the server's canonical "
                         "statistics")
    ap.add_argument("--filter", choices=["dense", "topk"], default="dense")
    ap.add_argument("--fail-client", type=int, default=-1,
                    help="client id to crash mid-run and rejoin from its "
                         "snapshot (§5.4 kill-and-rejoin demo)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="seeded-random multi-fault plan (crashes, "
                         "stragglers, lost pushes, failed pulls)")
    ap.add_argument("--snapshot-dir", default=None)
    args = ap.parse_args()

    tokens, mask, _ = make_topic_corpus(CorpusConfig(
        n_topics=8, vocab_size=400, n_docs=256, doc_len=64, seed=0))
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)

    if args.model == "lda":
        cfg = lda.LDAConfig(n_topics=8, vocab_size=400, mh_steps=2)
    elif args.model == "pdp":
        cfg = pdp.PDPConfig(n_topics=8, vocab_size=400, alpha=0.1,
                            discount=0.1, concentration=5.0, mh_steps=4,
                            stirling_n_max=256)
    else:
        cfg = hdp.HDPConfig(n_topics=16, vocab_size=400, b0=1.0, b1=2.0,
                            mh_steps=4)

    fspec = (ps.FilterSpec(kind="topk", k_rows=50, random_rows=12)
             if args.filter == "topk" else ps.FilterSpec())
    plan = None
    if args.chaos_seed is not None:
        plan = FaultPlan.random(args.chaos_seed, args.clients, args.rounds,
                                p_crash=0.05, p_straggle=0.05,
                                p_lost_push=0.05, p_failed_pull=0.03)
    elif args.fail_client >= 0:
        plan = FaultPlan.crash(args.fail_client, args.rounds // 3,
                               2 * args.rounds // 3)
    # Periodic snapshots back the rejoin protocol (and Trainer.restore).
    snap_dir = args.snapshot_dir or tempfile.mkdtemp(prefix="lvm_snap_")

    print(f"model={args.model} layout={args.layout} clients={args.clients} "
          f"tau={args.tau} consistency={args.consistency} "
          f"server_shards={args.server_shards} filter={args.filter} "
          f"faults={len(plan.events) if plan else 0} snapshots={snap_dir}")
    t0 = time.time()
    trainer = Trainer(cfg, tokens, mask, config=TrainerConfig(
        layout=args.layout, n_clients=args.clients, tau=args.tau,
        consistency=args.consistency, n_server_shards=args.server_shards,
        filter=fspec, fault_plan=plan,
        snapshot_every=max(2, args.rounds // 4), snapshot_dir=snap_dir))
    res = trainer.run(args.rounds, eval_every=max(1, args.rounds // 6))
    for i, ppl in enumerate(res.perplexities):
        print(f"eval {i}: perplexity={ppl:9.2f}"
              f"  violations={res.violations[i]:.0f}")
    if plan:
        print(f"rejoins={trainer.rejoins} pull_failures="
              f"{trainer.pull_failures}")
    print(f"total {time.time() - t0:.1f}s, "
          f"{np.median(res.iter_times) * 1e3:.1f} ms/round (median)")

    # Record the run's summary curves next to the Trainer's snapshots.
    path = ckpt.save(snap_dir, f"{args.model}_run", args.rounds, {
        "perplexities": np.asarray(res.perplexities),
        "iter_times": np.asarray(res.iter_times),
    })
    print(f"snapshot written: {path}")


if __name__ == "__main__":
    main()
