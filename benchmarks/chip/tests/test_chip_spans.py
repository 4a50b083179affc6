"""The reduction of the program's own spans (``benchlib/spans.py``) and its
six readers, on planes built by hand with hand-computed numbers."""

from __future__ import annotations

import importlib.util
from types import SimpleNamespace as NS

import pytest

from benchlib import spans
from chiptest import CHIP

READERS = ("serve_queue_wait_ms", "serve_admit_ms", "serve_uniforms_ms",
           "serve_step_self_ms", "serve_fetch_ms", "train_host_ms")


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=start, duration_ns=end - start,
              stats=list(stats.items()))


def planes(device=True, program=True):
    """Window [1000, 11000] on the main thread; the batcher's spans on a
    second thread, another program span on a third; six idle gaps."""
    main = NS(name="python", events=[
        ev("repro.train.step", 100, 900, round=0),      # before the window
        ev("bench.window", 1000, 11000),
        ev("repro.train.step", 1500, 1700, round=1),
        ev("repro.train.step", 9000, 9400, round=2)])
    batcher = NS(name="python", events=[
        ev("repro.serve.wait", 0, 1200),                # before the window
        ev("repro.serve.admit", 1200, 1500, uid=7, queue_wait_us=300.0),
        ev("repro.serve.admit", 1500, 1600, uid=8, queue_wait_us=500.0),
        ev("bench.serve.step", 1990, 6010),              # harness span
        ev("repro.serve.step", 2000, 6000, live=2),
        ev("repro.serve.uniforms", 2100, 2600, chunk=0),
        ev("PjitFunction(draw)", 2200, 2300),            # not a program span
        ev("repro.serve.uniforms", 3000, 3800, chunk=1),
        ev("repro.serve.harvest", 6000, 7000, done=0),
        ev("repro.serve.fetch", 6050, 6400),
        ev("repro.serve.step", 7000, 10000, live=2),
        ev("repro.serve.uniforms", 7100, 7500, chunk=0),
        ev("repro.serve.step", 10500, 12000, live=1),    # ends after it
        ev("repro.serve.admit", 12100, 12200, uid=9,     # starts after it
           queue_wait_us=1e4)])
    other = NS(name="python", events=[ev("repro.test.other", 3000, 3600)])
    host = NS(name="/host:CPU", lines=[main, batcher, other])
    if not program:
        for line in host.lines:
            line.events = [e for e in line.events
                           if not e.name.startswith("repro.")]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        ev(f"op{i}", s, e) for i, (s, e) in enumerate([
            (900, 1300), (2150, 2200), (2700, 3100), (3800, 5800),
            (6400, 7100), (7500, 10500)])])])
    return [host, dev] if device else [host]


def reader(name):
    path = CHIP / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_on(monkeypatch, planes_):
    monkeypatch.setattr(spans, "_planes", lambda r: planes_)
    return NS(trace=True, counters={})


def test_window_start_rule():
    red = spans.reduce_planes(planes())
    assert [s.stats["round"] for s in red.of("repro.train.step")] == [1, 2]
    # The step that starts in the window counts, though it ends after it.
    assert [s.start_ns for s in red.of("repro.serve.step")] == [
        2000, 7000, 10500]
    assert [s.stats["uid"] for s in red.of("repro.serve.admit")] == [7, 8]
    assert not red.of("repro.serve.wait")


def test_self_time_leaves_other_threads_children_in():
    red = spans.reduce_planes(planes())
    # Step 1 less its two draws (500 + 800), not less the other thread's
    # span inside it (600) nor the harness's or JAX's events.
    assert [s.self_ns for s in red.of("repro.serve.step")] == [
        4000 - 1300, 3000 - 400, 1500]
    assert [s.self_ns for s in red.of("repro.serve.harvest")] == [650]
    assert [s.self_ns for s in red.of("repro.test.other")] == [600]


def test_metadata_comes_from_stats():
    red = spans.reduce_planes(planes())
    assert [s.stats for s in red.of("repro.serve.admit")] == [
        {"uid": 7, "queue_wait_us": 300.0}, {"uid": 8, "queue_wait_us": 500.0}]
    assert red.mean_stat("repro.serve.admit", "queue_wait_us") == 400.0
    assert red.of("repro.serve.fetch")[0].stats == {}


def test_idle_goes_to_the_innermost_program_span():
    """Gaps [1300, 2150] (no span), [2200, 2700] (a draw inside a step),
    [3100, 3800] (the other thread's shorter span), [5800, 6400] (fetch
    inside harvest), [7100, 7500] (a draw) and [10500, 11000] (a step)."""
    red = spans.reduce_planes(planes())
    assert dict(red.idle_gaps) == pytest.approx({
        "repro.serve.uniforms": 900e-9, spans.NO_SPAN: 850e-9,
        "repro.test.other": 700e-9, "repro.serve.fetch": 600e-9,
        "repro.serve.step": 500e-9})
    assert [n for n, _ in red.idle_gaps][:2] == ["repro.serve.uniforms",
                                                 spans.NO_SPAN]


@pytest.mark.parametrize("name, want", [
    ("serve_queue_wait_ms", 0.4),
    ("serve_admit_ms", 200e-6),
    ("serve_uniforms_ms", 1700e-6 / 3),
    ("serve_step_self_ms", (2700 + 2600 + 1500) * 1e-6 / 3),
    ("serve_fetch_ms", 350e-6 / 3),
    ("train_host_ms", 300e-6),
])
def test_reader_reads_the_hand_computed_number(monkeypatch, name, want):
    r = run_on(monkeypatch, planes())
    assert reader(name)(r) == pytest.approx(want)
    assert dict(r.counters["program_idle_gaps"])[spans.NO_SPAN] == (
        pytest.approx(850e-9))


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("case", ["no device plane", "no program span",
                                  "not traced"])
def test_reader_reads_nothing_without_device_or_program_spans(
        monkeypatch, name, case):
    if case == "not traced":        # the real trace lookup is never made
        r = NS(trace=False, counters={})
    else:
        r = run_on(monkeypatch, planes(device=case != "no device plane",
                                       program=case != "no program span"))
    assert reader(name)(r) is None
    assert "program_idle_gaps" not in r.counters


def test_trace_is_reduced_once_per_run(monkeypatch):
    calls = []
    monkeypatch.setattr(spans, "_planes",
                        lambda r: calls.append(1) or planes())
    r = NS(trace=True, counters={})
    values = [reader(name)(r) for name in READERS]
    assert calls == [1] and None not in values
