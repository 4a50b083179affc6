"""The trace reduction, on planes with hand-computed numbers and on a small
trace recorded on the chip (``record_trace.py``)."""

from __future__ import annotations

import pathlib
from types import SimpleNamespace as NS

import pytest

from benchlib import readings, trace

RECORDED = pathlib.Path(__file__).parent / "data" / "probe.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench.window", 0, 1000), ev("bench.a", 100, 300),
        ev("bench.b", 500, 400), ev("other", 0, 50)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            ev("k1", 0, 100), ev("k2", 50, 100), ev("k1", 450, 50),
            ev("probe", 950, 150), ev("k1", 2000, 100)]),
        NS(name="XLA Modules", events=[ev("module", 0, 1000)])])
    return [host, dev]


def test_reduction_of_known_planes():
    red = trace.reduce_planes(planes())
    assert red.window_s == pytest.approx(1000e-9)
    # Busy: [0, 150] + [450, 500] + [950, 1000 (clipped)] = 250 ns.
    assert red.busy_s == pytest.approx(250e-9)
    assert red.op_s == pytest.approx({"k1": 150e-9, "k2": 100e-9,
                                      "probe": 50e-9})
    assert red.op_count == {"k1": 2, "k2": 1, "probe": 1}
    assert red.kernel_s(["k"]) == (pytest.approx(250e-9), 3)
    # Gaps [150, 450] (mid 300, in bench.a) and [500, 950] (in bench.b).
    assert red.gaps == [("bench.b", pytest.approx(450e-9)),
                        ("bench.a", pytest.approx(300e-9))]
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["k1", pytest.approx(150e-9)]
    assert [g[0] for g in bd["idle_gaps"]] == ["bench.b", "bench.a"]


def test_ops_are_named_by_instruction():
    full = ("%mhw_sweep_fused.2 = s32[1,1024]{1,0} custom-call(s32[4]{0} "
            "%vstart.1, f32[131072,1024]{1,0} %state_tables_alias.1)")
    assert trace.op_name(full) == "mhw_sweep_fused.2"
    assert trace.op_name("jit_round(123)") == "jit_round(123)"
    host, dev = planes()
    dev.lines[0].events.append(ev(full, 600, 100))
    red = trace.reduce_planes([host, dev])
    # The kernel's operand names do not make it an alias build.
    assert red.kernel_s(["alias_build"]) == (0.0, 0)
    assert red.kernel_s(["mhw_sweep_fused"]) == (pytest.approx(100e-9), 1)


def test_no_window_span_is_an_error():
    host, dev = planes()
    host.lines[0].events = host.lines[0].events[1:]
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_planes([host, dev])


def test_recorded_chip_trace():
    """Five probe_kernel calls, each after a 50 ms host sleep, and a sleep
    before the window closes: five kernel events, and the device idle
    through the six sleeps."""
    red = trace.reduce_file(str(RECORDED))
    seconds, events = red.kernel_s(["probe_kernel"])
    assert events == 5
    assert 0 < seconds <= red.busy_s < red.window_s
    idle_in_sleep = sum(s for name, s in red.gaps if name == "bench.sleep")
    assert idle_in_sleep >= 6 * 0.05
    assert red.window_s - red.busy_s >= idle_in_sleep
    assert red.window_s - red.busy_s == pytest.approx(
        sum(s for _, s in red.gaps))
    # The idle-share reader reads the same numbers.
    assert readings.idle_share(NS(reduced=red)) == pytest.approx(
        100 * (1 - red.busy_s / red.window_s))
