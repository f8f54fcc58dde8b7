"""The metric arithmetic and the metric readers on fixed inputs."""

import numpy as np
import pytest

from harness import arith, driver, spec, xplane


def test_median_and_rate():
    assert arith.median([3.0, 1.0, 2.0, 10.0]) == 2.5
    assert arith.rate(96000, 8.0) == 12000.0
    assert arith.rate(5, 0.0) is None


def test_segments_merge_overlaps():
    s, e = xplane._segments(np.array([0.0, 0.5, 3.0, 3.5]),
                            np.array([1.0, 2.0, 4.0, 3.6]))
    assert s.tolist() == [0.0, 3.0] and e.tolist() == [2.0, 4.0]


def _ctx(cell, calls, window, trace=None, dispatches=0):
    return driver.Context(cell=cell, calls=calls, window=window,
                          setup={"setup_s": 12.5, "char_s": 0.25,
                                 "compile_s": 3.0},
                          counters={"kernel_dispatches": dispatches},
                          trace=trace, traced_calls=calls[:1])


def _rec(i, t0, t1, n, error=""):
    return driver.CallRecord(i, 0, 100 + i, t0, t1, n,
                             {("m", (0.0, 0.0), k): None for k in range(n)},
                             error)


def test_end_to_end_readers():
    cell = spec.load_cell("websearch.grid")
    calls = [_rec(0, 10.0, 14.0, 12), _rec(1, 14.0, 17.0, 12),
             _rec(2, 17.0, 19.0, 12, error="boom")]
    ctx = _ctx(cell, calls, (10.0, 19.0), dispatches=12)
    read = lambda name: driver._load_reader(name)(ctx)  # noqa: E731
    # 24 good cells x 8,000 requests over the 9 s window.
    assert read("sim_requests_per_s") == 24 * 8000 / 9.0
    assert read("call_p50_s") == 3.5
    assert read("setup_s") == 12.5
    assert read("dispatches_per_cell.grid") == 0.5
    assert read("setup_char_s") == 0.25
    assert read("setup_compile_s") == 3.0
    # Without a trace the trace readers find nothing to read.
    assert driver._load_reader("core_ms_per_cell.call")(ctx) is None
    assert driver._load_reader("device_idle_share.call")(ctx) is None


def _device(ops, modules=()):
    names = sorted({n for _, _, n in ops})
    return xplane.Device(np.array([s for s, _, _ in ops]),
                         np.array([e for _, e, _ in ops]),
                         np.array([names.index(n) for _, _, n in ops]),
                         names, list(modules))


def test_trace_readers():
    cell = spec.load_cell("websearch.call")
    dev = _device([(0.0, 1.0, "%while"), (0.5, 1.25, "%copy")],
                  [(0.0, 1.2, "jit_fcfs_core_fwd"), (1.3, 1.4, "jit_other")])
    red = xplane.Reduced(window=(0.0, 2.0), devices=[dev],
                         spans=[(0.0, 1.2, "inside call 0"),
                                (1.2, 2.0, "inside call 1")])
    ctx = _ctx(cell, [_rec(0, 0.0, 1.2, 1), _rec(1, 1.2, 2.0, 1)],
               (0.0, 2.0), trace=red)
    read = lambda name: driver._load_reader(name)(ctx)  # noqa: E731
    assert read("core_ms_per_cell.call") == pytest.approx(1200.0)
    # Busy: ops and programs together, [0, 1.25] and [1.3, 1.4].
    assert read("device_idle_share.call") == pytest.approx(32.5)
    assert red.top_ops() == [("%while", 1.0), ("%copy", 0.75)]
    gaps = red.idle_gaps()
    assert [g[0] for g in gaps] == ["inside call 1"] * 2
    assert [g[1] for g in gaps] == pytest.approx([0.6, 0.05])


def test_trace_window_clips():
    dev = _device([(0.0, 1.0, "%a"), (3.0, 4.0, "%b")],
                  [(0.0, 4.0, "jit_fcfs_core_fwd")])
    red = xplane.Reduced(window=(0.5, 3.5), devices=[dev], spans=[])
    # The program spans the window though its ops cover 1 s of it: a
    # trace that stops inside a loop lacks the loop op.
    assert red.busy_s() == pytest.approx(3.0)
    assert red.core_s() == pytest.approx(3.0)
    assert red.top_ops() == [("%a", 0.5), ("%b", 0.5)]
    assert red.idle_gaps() == []
    bare = xplane.Reduced(window=(0.5, 3.5),
                          devices=[_device([(0.0, 1.0, "%a"),
                                            (3.0, 4.0, "%b")])], spans=[])
    assert bare.busy_s() == pytest.approx(1.0)
    assert bare.idle_gaps() == [("between calls", 2.0)]


def test_no_core_program_reads_nothing():
    cell = spec.load_cell("websearch.call")
    red = xplane.Reduced(window=(0.0, 1.0),
                         devices=[_device([(0.0, 0.5, "%copy")])], spans=[])
    ctx = _ctx(cell, [_rec(0, 0.0, 1.0, 1)], (0.0, 1.0), trace=red)
    assert driver._load_reader("core_ms_per_cell.call")(ctx) is None


def test_op_and_module_names():
    assert xplane.op_name("%fusion.131 = s32[8] fusion(%x)") == "%fusion.131"
    assert xplane.module_name("jit_fcfs_core_fwd(5277)") == "jit_fcfs_core_fwd"


def test_call_seeds_differ_and_repeat():
    a = [driver.call_seed(2 ** 31 + 7, 1, i) for i in range(50)]
    assert len(set(a)) == 50 and all(0 <= s < 2 ** 62 for s in a)
    assert a == [driver.call_seed(2 ** 31 + 7, 1, i) for i in range(50)]
    assert driver.call_seed(5, 0, 0) != driver.call_seed(5, 1, 0)
