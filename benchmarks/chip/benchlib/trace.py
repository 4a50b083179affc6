"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

The traced window is the host span ``WINDOW`` that the harness opens
around its measured calls.  Within it:

* busy time is the union of the intervals of the device's operations
  (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), averaged over the
  chips that ran any;
* an operation is named by its HLO instruction (``mhw_sweep_fused.2`` of
  the event ``%mhw_sweep_fused.2 = s32[...] custom-call(...)``), and its
  time is the sum of its events' durations;
* an idle gap is an interval of the window in which no operation ran; it
  is put down to the innermost harness span (``bench.*``) that holds the
  gap's midpoint, or to ``"(no span)"``.

The profiler puts device events on the host's clock to within about a
millisecond, so an operation that starts as the window opens may show a
little before it; the reduction clips each operation to the window.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Reduced:
    window_s: float
    devices: int                        # chips whose operations were traced
    busy_s: float                       # averaged over them
    op_s: dict[str, float]              # device seconds by operation name
    op_count: dict[str, int]
    gaps: list[tuple[str, float]]       # (host span, seconds), longest first

    def kernel_s(self, prefixes: list[str]) -> tuple[float, int]:
        """Seconds and events of the operations whose name starts with any
        of ``prefixes``."""
        names = [n for n in self.op_s if n.startswith(tuple(prefixes))]
        return (sum(self.op_s[n] for n in names),
                sum(self.op_count[n] for n in names))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        by_span: dict[str, float] = collections.defaultdict(float)
        for name, s in self.gaps:
            by_span[name] += s
        gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(event_name: str) -> str:
    """The HLO instruction name of an ``XLA Ops`` event."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    return reduce_planes(data.planes)


def reduce_planes(planes) -> Reduced:
    spans: list[tuple[float, float, str]] = []
    devices: list[list[tuple[float, float, str]]] = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                    op_name(ev.name))
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if evs:
                devices.append(evs)
        elif plane.name.startswith("/host:"):
            spans.extend((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for line in plane.lines for ev in line.events
                         if ev.name.startswith(SPAN_PREFIX))
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    inner = sorted(((s, e, n) for s, e, n in spans if n != WINDOW),
                   key=lambda x: x[1] - x[0])      # innermost first

    def span_at(t: float) -> str:
        return next((n for s, e, n in inner if s <= t <= e), "(no span)")

    op_s: dict[str, float] = collections.defaultdict(float)
    op_count: dict[str, int] = collections.defaultdict(int)
    busy, gaps = 0.0, []
    for evs in devices:
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in evs
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            op_s[n] += (e - s) * 1e-9
            op_count[n] += 1
        merged = _union([(s, e) for s, e, _ in clipped])
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((span_at((a + b) / 2), (b - a) * 1e-9))
    n_dev = max(1, len(devices))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(w1 - w0) * 1e-9, devices=len(devices),
                   busy_s=busy / n_dev,
                   op_s=dict(op_s), op_count=dict(op_count), gaps=gaps)
