"""The reduction from a profiler trace to the per-layer metrics' inputs:
on synthetic planes whose answer is known, and on a small trace recorded
on a TPU v5e by ``record_trace.py`` and committed beside this file."""

from collections import namedtuple
from pathlib import Path

import pytest

from chipbench import bench, trace_reduce

FIXTURE = Path(__file__).with_name("data") / "small.xplane.pb"
Plane = namedtuple("Plane", "name lines")
Line = namedtuple("Line", "name events")
Event = namedtuple("Event", "name start_ns duration_ns")


def _planes():
    host = Plane("/host:CPU", [Line("python", [
        Event("chipbench.window", 100, 1000),
        Event("chipbench.batch", 100, 200),
        Event("chipbench.dispatch", 300, 50),
        Event("chipbench.block", 350, 700),
        Event("other", 0, 5000)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Event("jit_step_fn(1)", 300, 600)]),
        Line("XLA Ops", [
            Event("%while.3 = (s32[]) while(...)", 300, 400),
            Event("%fusion.1 = f32[4] fusion(...)", 320, 100),   # inside while
            Event("%pairwise_sqdist_kernel.4 = f32[1,4,4] custom-call()",
                  750, 150),
            Event("%copy.2 = f32[8] copy(...)", 1050, 100)])])   # clipped
    return [host, dev]


def test_synthetic_trace():
    red = trace_reduce.reduce_planes(_planes())
    assert red["window_s"] == pytest.approx(1000e-9)
    # outermost ops only: while 400 + kernel 150 + copy clipped to 50
    assert red["busy_s"] == pytest.approx(600e-9)
    assert red["ops"] == pytest.approx({"while.3": 400e-9,
                                        "pairwise_sqdist_kernel.4": 150e-9,
                                        "copy.2": 50e-9})
    # gaps: [100, 300) batch, [700, 750) block, [900, 1050) block
    assert red["gaps"] == [
        ("chipbench.batch", pytest.approx(200e-9), pytest.approx(0.0)),
        ("chipbench.block", pytest.approx(150e-9), pytest.approx(800e-9)),
        ("chipbench.block", pytest.approx(50e-9), pytest.approx(600e-9))]
    out = trace_reduce.breakdown(red)
    assert out["device_ops"][0] == ["while.3", pytest.approx(400e-9)]
    assert out["idle_gaps"][0] == ["chipbench.batch at 0.000 s",
                                   pytest.approx(200e-9)]


def test_metrics_from_synthetic_trace():
    red = trace_reduce.reduce_planes(_planes())
    ctx = {"trace": red, "host": {"steps": 1, "window_s": 1e-6,
                                  "blocked_s": 0.7e-6},
           "chips": 1, "peaks": bench.peaks("TPU v5 lite"),
           "step_flops": 1e6, "gram_bytes": 819.0}
    assert bench.metric_reader("idle_share")(ctx) == pytest.approx(40.0)
    assert bench.metric_reader("gram_kernel_ms")(ctx) == pytest.approx(150e-6)
    # 819 bytes at 819 GB/s take 1 ns of the kernel's 150
    assert bench.metric_reader("gram_roofline")(ctx) == pytest.approx(100 / 150)
    assert bench.metric_reader("mfu")(ctx) == pytest.approx(
        100 * 1e6 / (1e-6 * 197e12))
    assert bench.metric_reader("host_ms_per_step")(ctx) == pytest.approx(3e-4)


def test_no_window_is_an_error():
    planes = _planes()
    planes[0] = Plane("/host:CPU", [Line("python", [])])
    with pytest.raises(ValueError, match="chipbench.window"):
        trace_reduce.reduce_planes(planes)


def test_recorded_trace():
    red = trace_reduce.reduce(FIXTURE)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"] < 0.1
    # outermost operations never overlap: their times add up to busy
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"])
    idle = sum(g for _, g, _ in red["gaps"])
    assert idle + red["busy_s"] == pytest.approx(red["window_s"])
    # each round's 3 ms host pause sits in its batch span
    long_gaps = [g for g in red["gaps"] if g[1] > 2.5e-3]
    assert len(long_gaps) >= 4
    assert all(name == "chipbench.batch" for name, _, _ in long_gaps)
    assert any(k.startswith("pairwise_sqdist_kernel") for k in red["ops"])
    out = trace_reduce.breakdown(red)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
