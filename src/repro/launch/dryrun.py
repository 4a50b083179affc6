import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines, before any jax-importing module: jax locks
# the device count on first init, and the production meshes below need 512
# placeholder host devices (16x16 single pod, 2x16x16 multi-pod).

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
workload on the production meshes, without allocating a single real array.

For each combination this prints/records:
  - compiled.memory_analysis()  — per-device HBM footprint (proves it fits)
  - compiled.cost_analysis()    — HLO FLOPs / bytes (feeds §Roofline)
  - collective byte totals parsed from the optimized HLO

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # everything
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b
  PYTHONPATH=src python -m repro.launch.dryrun --shape train_4k --multi-pod
  PYTHONPATH=src python -m repro.launch.dryrun --json out.json

A failure to lower/compile any (arch × shape × mesh) is a bug in the
sharding rules, not an acceptable skip — the only skips are the documented
long_500k full-attention exclusions (DESIGN.md §4).
"""

import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import ARCHITECTURES
from repro.launch import roofline as rl
from repro.launch import specs as specs_lib
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            verbose: bool = True, sharding: str = "megatron") -> dict:
    """Lower + compile one workload on one production mesh; returns the
    record for EXPERIMENTS.md §Dry-run / §Roofline."""
    cfg = ARCHITECTURES[arch]
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"

    reason = specs_lib.skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skip", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    try:
        with mesh:
            spec = specs_lib.make_lowering_spec(cfg, shape, mesh,
                                                mode=sharding)
            lowered = specs_lib.lower(spec)
            lowered_text = lowered.as_text()
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        n_mb = (specs_lib.default_microbatches(cfg)
                if shape.kind == "train" else 1)
        roof = rl.analyze(compiled, compiled.as_text(), cfg=cfg, shape=shape,
                          mesh_name=mesh_name, chips=chips,
                          n_microbatches=n_mb)
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "ok", "kind": spec.kind, "sharding": sharding,
               "compile_s": round(time.time() - t0, 1),
               "memory_analysis": {
                   "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                   "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                   "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                   "generated_code_bytes": getattr(
                       mem, "generated_code_size_in_bytes", 0),
               },
               **roof.row()}
        if verbose:
            hbm = (rec["memory_analysis"]["argument_bytes"]
                   + rec["memory_analysis"]["output_bytes"]
                   + rec["memory_analysis"]["temp_bytes"]) / 2**30
            print(f"[ok]   {arch:22s} {shape_name:12s} {mesh_name:10s} "
                  f"kind={spec.kind:7s} compile={rec['compile_s']:6.1f}s "
                  f"hbm/dev={hbm:7.2f}GiB "
                  f"t_comp={roof.t_compute:.3e}s t_mem={roof.t_memory:.3e}s "
                  f"t_coll={roof.t_collective:.3e}s "
                  f"bottleneck={roof.bottleneck}", flush=True)
        return rec
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        if verbose:
            print(f"[FAIL] {arch:22s} {shape_name:12s} {mesh_name}\n"
                  f"{traceback.format_exc()}", flush=True)
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "fail", "error": f"{type(e).__name__}: {e}"}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one architecture id")
    ap.add_argument("--shape", default=None, help="one input-shape name")
    ap.add_argument("--multi-pod", action="store_true",
                    help="only the 2x16x16 multi-pod mesh")
    ap.add_argument("--single-pod", action="store_true",
                    help="only the 16x16 single-pod mesh")
    ap.add_argument("--json", default=None, help="write records to this file")
    ap.add_argument("--sharding", default="megatron",
                    choices=["megatron", "zero_seq", "zero_batch"],
                    help="megatron = paper-faithful baseline; zero_seq = "
                         "ZeRO-3 + sequence-parallel (§Perf optimization)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    archs = [args.arch] if args.arch else sorted(ARCHITECTURES)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    if args.multi_pod:
        meshes = [True]
    elif args.single_pod:
        meshes = [False]
    else:
        meshes = [False, True]

    assert len(jax.devices()) == 512, (
        "dryrun needs the 512 forced host devices; do not import jax before "
        "this module sets XLA_FLAGS")

    records = []
    for multi_pod in meshes:
        for arch in archs:
            for shape in shapes:
                records.append(run_one(arch, shape, multi_pod=multi_pod,
                                       sharding=args.sharding))

    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skip" for r in records)
    n_fail = sum(r["status"] == "fail" for r in records)
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} documented skips, "
          f"{n_fail} failures")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {args.json}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
