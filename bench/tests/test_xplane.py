"""The trace reduction on a trace recorded on the chip: one
``simulate`` call of ``websearch.call`` at 30 requests on one TPU v5e,
traced with the options the benchmark uses.  The numbers it must give
were read off the trace once; the reduction may not drift from them."""

import os

import pytest

from harness import xplane

TRACE = os.path.join(os.path.dirname(__file__), "data", "chip_call.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xplane.read(TRACE)


def test_planes_and_marks(trace):
    devices, marks = trace
    assert len(devices) == 1
    d = devices[0]
    assert d.starts.size == 15294
    assert (d.ends >= d.starts).all()
    assert (d.starts[1:] >= d.starts[:-1]).all()
    assert [n for _, _, n in d.modules] == ["jit_convert_element_type",
                                            "jit_fcfs_core_fwd"]
    assert list(marks) == ["bench.call 1 probe"]


def test_reduction_over_the_call(trace):
    devices, marks = trace
    (lo, hi), = marks["bench.call 1 probe"]
    red = xplane.Reduced(window=(lo, hi), devices=devices,
                         spans=[(lo, hi, "inside call 0 (simulate)")])
    assert red.window_s == pytest.approx(0.011890758, abs=1e-9)
    # The core program runs 6.53 ms of the 11.89 ms call; the loop's
    # own op (a while) covers it; busy adds a 0.6-µs conversion program.
    assert red.core_s() == pytest.approx(0.006533742, abs=1e-9)
    assert red.busy_s() == pytest.approx(0.006534335, abs=1e-9)
    assert red.busy_s() <= red.window_s
    name, secs = red.top_ops(1)[0]
    assert name.startswith("%while") and secs == pytest.approx(0.006529328,
                                                               abs=1e-9)
    gaps = red.idle_gaps(3)
    assert [g[0] for g in gaps] == ["inside call 0 (simulate)"] * 3
    assert gaps[0][1] == pytest.approx(0.002658626, abs=1e-9)
    # Busy and idle add up to the window.
    idle = sum(s for _, s in red.idle_gaps(10 ** 6))
    assert idle + red.busy_s() == pytest.approx(red.window_s, abs=1e-9)


def test_window_outside_the_trace_reads_idle(trace):
    devices, _ = trace
    red = xplane.Reduced(window=(100.0, 101.0), devices=devices, spans=[])
    assert red.busy_s() == 0.0 and red.core_s() == 0.0
    assert red.idle_gaps() == [("between calls", 1.0)]
