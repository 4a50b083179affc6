#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A run loads, warms up every shape the cell uses (set-up), measures for
about ``--seconds`` seconds, checks what the measured path produced
against a plain reference, and prints one JSON object as its last stdout
line: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` when traced), then ``checks`` — every number compared,
beside its limit, which also make the last lines on stderr.

Everything is found by name.  A cell names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<traffic>.json``);
the traffic's ``kind`` names its driver (``drivers/<kind>.py``), the
configuration's ``reference`` its plain reference (``refs/<name>.py``),
each per-layer metric has its reader (``metrics/<metric>.py``), each
kernel's work its counter (``work/<kernel>.py``), and the cell's limits
sit in ``limits/<cell>.json``.  ``peaks.json`` holds each chip's peaks by
``device_kind``.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler and the metrics are
the cell's per-layer metrics.  Without a TPU, or with fewer chips than the
cell asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class Refused(Exception):
    """The run cannot be made here; no result line is printed."""


def load_module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no {kind} module {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise Refused(f"no {kind} file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


class Run:
    """Everything one run knows; passed to the driver and the readers."""

    def __init__(self, bench: dict, args) -> None:
        cells = {w["name"]: w for w in bench["workloads"]}
        if args.workload not in cells:
            raise Refused(f"unknown workload {args.workload!r}")
        self.bench = bench
        self.cell = cells[args.workload]
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.config = load_json("configs", self.cell["config"])
        self.traffic = load_json("traffic", self.cell["traffic"])
        self.limits = load_json("limits", self.cell["name"])
        self.out_dir = HERE / "out" / self.cell["name"]
        self.out_dir.mkdir(parents=True, exist_ok=True)
        self.trace_dir = self.out_dir / f"trace_{self.seed}"
        self.counters: dict = {}      # what the driver counted
        self.reduced = None           # the trace reduction, when traced
        self.peaks: dict | None = None
        self._compiles: list[float] = []

    def watch_compiles(self) -> None:
        """Record the time of every JAX trace, compile or compile-cache
        read from now on."""
        from jax import monitoring

        def on_event(event: str, secs: float, **kw) -> None:
            if "compil" in event:
                self._compiles.append(time.perf_counter())
        monitoring.register_event_duration_secs_listener(on_event)

    def compiles_between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self._compiles)

    def log(self, msg: str) -> None:
        print(msg, flush=True)

    def span(self, name: str):
        """A host span in the profiler's trace (no-op when not traced)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    @contextlib.contextmanager
    def window(self):
        """The measured window; traced when ``--trace 1``."""
        if not self.trace:
            yield
            return
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        try:
            with self.span("window"):
                yield
        finally:
            jax.profiler.stop_trace()

    @staticmethod
    def module(kind: str, name: str):
        """The module ``<kind>/<name>.py`` of the benchmark."""
        return load_module(kind, name)

    def work(self, kernel: str, **shapes) -> tuple[float, float]:
        """(operations, bytes) the algorithm needs for one call."""
        return load_module("work", kernel).work(**shapes)

    def least_s(self, ops: float, nbytes: float) -> tuple[float, str]:
        """Least time the chip needs for the work, and which bound binds."""
        t_ops = ops / self.peaks["flops_per_s"]
        t_mem = nbytes / self.peaks["hbm_bytes_per_s"]
        return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def setup_jax(chips: int):
    """Compile cache inside the checkout, then find the chips or refuse."""
    cache = ROOT / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX finds no devices: {e}") from e
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX sees "
                      f"{len(devs)}")
    return devs[:chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    try:
        return run(args)
    except Refused as e:
        print(f"chip benchmark refused: {e}", file=sys.stderr)
        return 2


def run(args) -> int:
    bench_path = ROOT / "BENCHMARK.json"
    if not bench_path.is_file():
        raise Refused(f"no {bench_path.name}")
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused("the program under test (src/repro) is not here")
    sys.path.insert(0, str(ROOT / "src"))
    r = Run(json.loads(bench_path.read_text()), args)
    driver = load_module("drivers", r.traffic["kind"])
    devs = setup_jax(r.cell["chips"])
    kind = devs[0].device_kind
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind not in peaks:
        raise Refused(f"no peaks for device_kind {kind!r} in peaks.json")
    r.peaks = peaks[kind]
    r.watch_compiles()
    r.log(f"device: {kind} x{len(devs)}; cell {r.cell['name']}; seed "
          f"{r.seed}; {r.seconds}s; trace {int(r.trace)}")

    res = driver.run(r, devs)   # set-up, window, then the reference
    setup_s = res["window_start"] - T_START

    metrics: dict = {}
    breakdown = None
    if r.trace:
        from benchlib import trace as trace_mod
        r.reduced = trace_mod.reduce_file(trace_mod.find_xplane(
            str(r.trace_dir)))
        breakdown = r.reduced.breakdown()
        for m in cell_metrics(r.bench["per_layer"], r.cell["name"]):
            value = load_module("metrics", m["name"]).read(r)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = dict(res["e2e"], setup_s=setup_s)
        for m in cell_metrics(r.bench["end_to_end"], r.cell["name"]):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": res["memory_peak"]}
    if r.reduced is not None:
        device.update(busy_s=r.reduced.busy_s, window_s=r.reduced.window_s)
    checks = {name: {"value": v, "limit": lim}
              for name, (v, lim) in res["checks"].items()}
    correct = bool(checks) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    detail = {"setup_s": setup_s, "counters": r.counters, "device": device,
              "metrics": metrics, "checks": checks}
    (r.out_dir / f"run_{r.seed}_t{int(r.trace)}.json").write_text(
        json.dumps(detail, indent=1, default=float))
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    print(json.dumps(out), flush=True)
    return 0


def cell_metrics(entries: list[dict], cell: str) -> list[dict]:
    return [m for m in entries if cell in m.get("workloads", [cell])]


if __name__ == "__main__":
    sys.path.insert(0, str(HERE))
    sys.exit(main())
