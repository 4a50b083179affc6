"""The program's own host spans (``repro.*``) in a run's profiler trace.

The program opens ``jax.profiler.TraceAnnotation`` spans named
``repro.<layer>.<part>`` on its host paths; their keyword metadata comes
back as each event's ``stats``.  This module reduces a traced run's
``.xplane.pb`` to those spans once per run (``read``), for the per-layer
readers under ``metrics/``:

* a span counts when it starts inside the harness's ``bench.window``;
* a span's self time is its duration less that of its child ``repro.*``
  spans on the same thread (host line); spans of other threads are not
  its children;
* ``program_idle_gaps`` is the device's idle time inside the window put
  down to the innermost ``repro.*`` span, of any thread, that holds each
  gap's midpoint, or to ``NO_SPAN`` — the rule ``trace.py`` applies to the
  harness's ``bench.*`` spans.  It goes into the run's counters.

Nothing is read, and every reader gets None, where the trace holds no
``repro.*`` span (a program without them) or no ``/device:TPU:`` plane
(a trace taken on the CPU): a number from the host alone is not written
under a device metric's name.
"""

from __future__ import annotations

import collections
import dataclasses

import numpy as np

from benchlib import trace

PREFIX = "repro."
NO_SPAN = "(no program span)"
_MISSING = object()


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    thread: tuple[int, int]       # (plane, line) of the host trace
    stats: dict
    self_ns: float = 0.0

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class ProgramSpans:
    spans: list[Span]                       # those starting in the window
    idle_gaps: list[tuple[str, float]]      # (span, seconds), longest first

    def of(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]

    def mean_ms(self, name: str) -> float | None:
        """Mean duration of the spans ``name``."""
        got = self.of(name)
        return 1e-6 * sum(s.dur_ns for s in got) / len(got) if got else None

    def mean_self_ms(self, name: str) -> float | None:
        """Mean self time of the spans ``name``."""
        got = self.of(name)
        return 1e-6 * sum(s.self_ns for s in got) / len(got) if got else None

    def per_ms(self, name: str, per: str) -> float | None:
        """Summed duration of the spans ``name`` over the number of spans
        ``per``."""
        n = len(self.of(per))
        return 1e-6 * sum(s.dur_ns for s in self.of(name)) / n if n else None

    def mean_stat(self, name: str, key: str) -> float | None:
        """Mean of the metadata ``key`` over the spans ``name`` that carry
        it."""
        vals = [float(s.stats[key]) for s in self.of(name) if key in s.stats]
        return sum(vals) / len(vals) if vals else None


def read(r) -> ProgramSpans | None:
    """The run's program spans, reduced once per run and kept on ``r``;
    None where the run was not traced or the trace has none to read."""
    got = getattr(r, "program_spans", _MISSING)
    if got is _MISSING:
        planes = _planes(r)
        got = None if planes is None else reduce_planes(planes)
        if got is not None:
            r.counters["program_idle_gaps"] = [[n, s]
                                               for n, s in got.idle_gaps]
        r.program_spans = got
    return got


def _planes(r):
    """The planes of the run's trace, or None without one."""
    if not r.trace:
        return None
    try:
        path = trace.find_xplane(str(r.trace_dir))
    except FileNotFoundError:
        return None
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        return ProfileData.from_serialized_xspace(f.read()).planes


def reduce_planes(planes) -> ProgramSpans | None:
    """Program spans and idle attribution of a trace's planes; None with
    no device plane, no ``repro.*`` span or no window."""
    spans: list[Span] = []
    windows: list[tuple[float, float]] = []
    devices: list[list[tuple[float, float]]] = []
    for p, plane in enumerate(planes):
        if plane.name.startswith(trace.DEVICE_PREFIX):
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                   for line in plane.lines if line.name == trace.OPS_LINE
                   for ev in line.events]
            if evs:
                devices.append(evs)
        elif plane.name.startswith("/host:"):
            for li, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name == trace.WINDOW:
                        windows.append((ev.start_ns,
                                        ev.start_ns + ev.duration_ns))
                    elif ev.name.startswith(PREFIX):
                        end = ev.start_ns + ev.duration_ns
                        stats = dict(getattr(ev, "stats", None) or ())
                        spans.append(Span(ev.name, ev.start_ns, end,
                                          (p, li), stats))
    if not devices or not spans or not windows:
        return None
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    _self_times(spans)
    return ProgramSpans(
        spans=[s for s in spans if w0 <= s.start_ns < w1],
        idle_gaps=_idle_gaps(spans, devices, w0, w1))


def _self_times(spans: list[Span]) -> None:
    """Set each span's self time: its duration less its direct children's,
    a child being the innermost enclosing span on the same thread."""
    by_thread: dict = collections.defaultdict(list)
    for s in spans:
        by_thread[s.thread].append(s)
    for group in by_thread.values():
        group.sort(key=lambda s: (s.start_ns, -s.end_ns))
        stack: list[Span] = []
        for s in group:
            s.self_ns = s.dur_ns
            while stack and stack[-1].end_ns < s.end_ns:
                stack.pop()
            if stack:
                stack[-1].self_ns -= s.dur_ns
            stack.append(s)


def _idle_gaps(spans, devices, w0, w1) -> list[tuple[str, float]]:
    """Each device's idle intervals in the window, put down to the
    innermost span holding the midpoint; summed by span, longest first."""
    inner = sorted(spans, key=lambda s: s.dur_ns)        # innermost first
    starts = np.array([s.start_ns for s in inner], float)
    ends = np.array([s.end_ns for s in inner], float)
    by_span: dict[str, float] = collections.defaultdict(float)
    for evs in devices:
        merged = trace._union([(max(s, w0), min(e, w1)) for s, e in evs
                               if e > w0 and s < w1])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for i in range(0, len(gaps), 4096):
            part = np.array(gaps[i:i + 4096], float)
            mid = part.mean(axis=1)[:, None]
            hit = (starts <= mid) & (mid <= ends)
            first = hit.argmax(axis=1)
            for (a, b), h, j in zip(part, hit.any(axis=1), first):
                name = inner[j].name if h else NO_SPAN
                by_span[name] += (b - a) * 1e-9
    return sorted(by_span.items(), key=lambda kv: -kv[1])
