"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 100 --batch 8 --seq 128 [--mesh data=1,model=1]

Builds the mesh, applies the sharding rules from ``repro.train.sharding``
to parameters / optimizer state / batches, jits the training step with
those shardings, and runs the loop with periodic checkpointing.  On the CPU
container the mesh is 1x1 and the same code path exercises the full
sharded program; on a real pod the ``--mesh`` flag selects the production
layout that the dry-run validated.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt
from repro.configs.base import reduced
from repro.configs.registry import ARCHITECTURES
from repro.data.synthetic import lm_batches
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as model_lib
from repro.optim import adamw
from repro.train import sharding as sh
from repro.train.train_step import TrainConfig, make_train_step


def parse_mesh(spec: str) -> dict[str, int]:
    out = {}
    for part in spec.split(","):
        k, v = part.split("=")
        out[k.strip()] = int(v)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m",
                    choices=sorted(ARCHITECTURES))
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="data=1,model=1")
    ap.add_argument("--sharding", default="megatron",
                    choices=["megatron", "zero_seq", "zero_batch"],
                    help="layout (train/sharding.py); zero_* are the §Perf-"
                         "optimized modes")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = ARCHITECTURES[args.arch]
    if args.reduced:
        cfg = reduced(cfg).replace(vocab_size=min(512, cfg.vocab_size))
    m = parse_mesh(args.mesh)
    mesh = make_host_mesh(data=m.get("data", 1), model=m.get("model", 1))
    tcfg = TrainConfig(peak_lr=args.lr, warmup=min(10, args.steps // 5),
                       total_steps=args.steps,
                       microbatches=args.microbatches,
                       loss_chunk=min(512, args.seq))

    with mesh:
        mode = sh.resolve_mode(mesh, args.sharding,
                               args.batch, args.seq)
        param_mode = "zero_seq" if mode == "zero_batch" else mode
        model_lib.set_activation_spec(
            sh.activation_spec(mesh, mode),
            mesh=mesh if mode != "megatron" else None)
        params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw.init(params)
        pspecs = sh.param_specs(params, mesh=mesh, fsdp=True,
                                mode=param_mode)
        pshard = sh.named(pspecs, mesh)
        oshard = type(opt)(step=sh.named(jax.sharding.PartitionSpec(), mesh),
                           m=pshard, v=pshard)
        params = jax.tree.map(jax.device_put, params, pshard)
        opt = adamw.AdamWState(
            step=opt.step,
            m=jax.tree.map(jax.device_put, opt.m, pshard),
            v=jax.tree.map(jax.device_put, opt.v, pshard))

        start = 0
        if args.resume and args.ckpt_dir:
            step0 = ckpt.latest_step(args.ckpt_dir, cfg.name)
            if step0 is not None:
                state = ckpt.restore(args.ckpt_dir, cfg.name,
                                     {"params": params,
                                      "opt": opt._asdict()})
                params = state["params"]
                opt = adamw.AdamWState(**state["opt"])
                start = step0
                print(f"resumed from step {start}")

        step_fn = jax.jit(make_train_step(cfg, tcfg),
                          in_shardings=(pshard, oshard, None),
                          out_shardings=(pshard, oshard, None),
                          donate_argnums=(0, 1))
        data = lm_batches(cfg.vocab_size, args.batch, args.seq,
                          args.steps - start, seed=1, kind="affine")
        t0 = time.time()
        for i, batch in enumerate(data):
            step = start + i
            batch = {"tokens": jnp.asarray(batch["tokens"])}
            params, opt, metrics = step_fn(params, opt, batch)
            if step % 10 == 0 or step == args.steps - 1:
                tok_s = ((i + 1) * args.batch * args.seq
                         / max(time.time() - t0, 1e-9))
                print(f"step {step:5d}  loss={float(metrics['loss']):8.4f}  "
                      f"gnorm={float(metrics['grad_norm']):7.3f}  "
                      f"{tok_s:9.0f} tok/s", flush=True)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                path = ckpt.save(args.ckpt_dir, cfg.name, step + 1,
                                 {"params": params, "opt": opt._asdict()})
                print(f"checkpoint: {path}", flush=True)
    print("training complete")


if __name__ == "__main__":
    main()
