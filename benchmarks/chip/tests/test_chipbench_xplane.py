"""The reduction from a profiler trace to busy time, op and program times
and idle gaps named by the programs around them: on planes built by hand, and on a 0.05 s trace of
the offload80 cell recorded on a TPU v5 lite (`data/served_tiny.xplane.pb`)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import xplane

DATA = Path(__file__).resolve().parent / "data"


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        _ev("bench.window", 1000, 10_000),
        _ev("bench.infer", 1000, 6000), _ev("bench.edge", 1500, 1000),
        _ev("bench.cloud", 5000, 1500), _ev("other", 0, 20_000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("conv", 1600, 500), _ev("fusion", 1900, 300),
                                   _ev("conv", 5200, 800), _ev("early", 0, 1200)]),
        NS(name="XLA Modules", events=[_ev("jit_edge(1)", 1600, 600),
                                       _ev("jit_cloud(2)", 5200, 800)]),
        NS(name="Steps", events=[_ev("ignored", 1000, 10_000)])])
    return [host, dev, NS(name="/device:TPU:0 SparseCore", lines=[])]


def test_hand_built_planes():
    red = xplane.reduce_planes(_planes())
    assert red.devices == 1
    assert red.window_s == pytest.approx(10_000e-9)
    # busy inside the window: [1000,1200) + [1600,2200) + [5200,6000)
    assert red.busy_s == pytest.approx((200 + 600 + 800) * 1e-9)
    assert red.idle_share == pytest.approx(1 - 1600 / 10_000)
    assert red.op_s["conv"] == pytest.approx(1300e-9) and red.op_calls["conv"] == 2
    assert red.time_of(r"^jit_cloud", "module") == (pytest.approx(800e-9), 1)
    # gaps [1200,1600) before any program; [2200,5200) between the two;
    # [6000,11000) after the last
    assert red.gaps_by_span == {"start -> edge": pytest.approx(400e-9),
                                "edge -> cloud": pytest.approx(3000e-9),
                                "cloud -> end": pytest.approx(5000e-9)}
    b = red.breakdown()
    assert b["device_ops"][0][0] == "conv" and len(b["device_ops"]) <= 10


def test_window_given_on_the_host_clock():
    planes = _planes()
    planes[0].lines[0].events = planes[0].lines[0].events[1:]  # no window span
    red = xplane.reduce_planes(planes, window=(2000, 6000))
    assert red.window_s == pytest.approx(4000e-9)
    assert red.busy_s == pytest.approx((200 + 800) * 1e-9)
    assert red.gaps_by_span == {"edge -> cloud": pytest.approx(3000e-9)}


def test_no_window_span_is_an_error():
    planes = _planes()
    planes[0].lines[0].events = planes[0].lines[0].events[1:]
    with pytest.raises(ValueError):
        xplane.reduce_planes(planes)


def test_recorded_served_trace():
    red = xplane.reduce_trace(DATA)
    assert red.devices == 1
    assert 0 < red.busy_s < red.window_s
    assert red.time_of(r"exit_gate", "module")[1] > 0
    assert red.time_of(r"_encode_wire", "module")[1] > 0
    assert red.time_of(r"_decode_wire", "module")[1] > 0
    assert sum(red.gaps_by_span.values()) == pytest.approx(red.window_s - red.busy_s,
                                                           rel=1e-6)
    b = red.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
