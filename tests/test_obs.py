"""The in-process recorder (:mod:`repro.flashsim.obs`): spans at the
simulator's step boundaries, the dispatch counters, and that recording
never changes a result."""

import collections
import glob
import time

import numpy as np
import pytest

from repro.flashsim import obs
from repro.flashsim.config import OperatingCondition
from repro.flashsim.simtime import on_grid
from repro.flashsim.ssd import simulate, simulate_batch
from repro.kernels.fcfs_core import ops as kops

AGED = OperatingCondition(365.0, 1000.0)
FRESH = OperatingCondition(0.0, 0.0)
MECHS = ("baseline", "pr2ar2")


def _one():
    return simulate("websearch", AGED, "pr2", n_requests=200,
                    engine="batched")


def _grid():
    return simulate_batch("websearch", (AGED, FRESH), mechanisms=MECHS,
                          seeds=(0, 1), n_requests=200, engine="batched",
                          fuse=True)


def _children(rec, i):
    return [s.name for s in rec.spans if s.parent == i]


class TestOff:
    def test_no_recording_records_nothing(self):
        with obs.recording() as rec:
            pass
        assert obs.span("flashsim.call") is obs.span("flashsim.core")
        assert not obs.active()
        _one()
        assert rec.spans == [] and rec.counters == {}

    @pytest.mark.parametrize("run", [_one, _grid], ids=["simulate",
                                                        "simulate_batch"])
    def test_stats_bit_identical_on_and_off(self, run):
        off = run()
        with obs.recording() as rec:
            on = run()
        assert rec.spans
        assert on == off
        if isinstance(on, dict):
            assert all(st.fused_cells > 1 for st in on.values())

    def test_recordings_do_not_nest(self):
        with obs.recording():
            with pytest.raises(RuntimeError):
                with obs.recording():
                    pass
        assert not obs.active()


class TestSpanTree:
    def test_simulate(self):
        _one()
        with obs.recording() as rec:
            _one()
        names = collections.Counter(s.name for s in rec.spans)
        assert set(names.values()) == {1}
        assert rec.spans[0].name == "flashsim.call"
        assert rec.spans[0].parent == -1
        assert rec.spans[0].attrs == {"api": "simulate"}
        assert {s.call for s in rec.spans} == {0}
        assert _children(rec, 0) == [
            "flashsim.trace", "flashsim.sim_init", "flashsim.prepare",
            "flashsim.pack", "flashsim.dispatch", "flashsim.assemble",
            "flashsim.finalize"]
        idx = {s.name: i for i, s in enumerate(rec.spans)}
        assert _children(rec, idx["flashsim.prepare"]) == [
            "flashsim.expand", "flashsim.sample_attempts"]
        assert _children(rec, idx["flashsim.dispatch"]) == [
            "flashsim.stage", "flashsim.core", "flashsim.unpack"]
        core = rec.spans[idx["flashsim.core"]].attrs
        assert core["lanes"] == 8 and core["new_variant"] is False
        assert core["steps"] <= core["capsteps"]
        # Spans nest and cover the call: no child outlives its parent.
        for s in rec.spans[1:]:
            p = rec.spans[s.parent]
            assert p.t0_ns <= s.t0_ns <= s.t1_ns <= p.t1_ns

    def test_fused_grid_and_call_ids(self):
        _grid()
        with obs.recording() as rec:
            _grid()
            _one()
        summary = rec.summary()
        k = rec.counters["dispatches"]
        seeds = 2
        cells = 2 * len(MECHS) * seeds
        want = {"flashsim.call": 2, "flashsim.trace": seeds + 1,
                "flashsim.expand": seeds + 1,
                "flashsim.sim_init": cells + 1,
                "flashsim.prepare": cells + 1,
                "flashsim.sample_attempts": cells + 1,
                # one for the router's preparation, one per chunk
                "flashsim.pack": (1 + (k - 1)) + 1,
                "flashsim.dispatch": k, "flashsim.stage": k,
                "flashsim.core": k, "flashsim.unpack": k,
                "flashsim.assemble": k,
                "flashsim.finalize": cells + 1}
        assert {n: c for n, (c, _, _) in summary.items()} == want
        roots = [i for i, s in enumerate(rec.spans) if s.parent < 0]
        assert [rec.spans[i].attrs["api"] for i in roots] == [
            "simulate_batch", "simulate"]
        assert [s.call for s in rec.spans] == sorted(
            s.call for s in rec.spans)
        assert {s.call for s in rec.spans} == {0, 1}
        for s in rec.spans:
            if s.parent >= 0:
                assert s.call == rec.spans[s.parent].call
        # The grid's 8 cells fuse into fewer dispatches.
        assert 1 < k - 1 < cells
        # The call's own time is what no step span covers.
        own = rec.self_ms()
        assert all(o >= 0.0 for o in own)
        total = sum(s.ms for s in rec.spans if s.parent < 0)
        assert sum(own) == pytest.approx(total)


class TestCounters:
    def test_dispatches_is_kernel_dispatches(self):
        d0 = kops.KERNEL_DISPATCHES
        with obs.recording() as rec:
            _grid()
        assert rec.counters["dispatches"] == kops.KERNEL_DISPATCHES - d0
        assert rec.counters["dispatches"] == sum(
            1 for s in rec.spans if s.name == "flashsim.core")

    def test_lanes_counted_only_while_recording(self):
        before = {n: obs.total(n) for n in ("lanes", "lane_steps_run")}
        d0 = obs.total("dispatches")
        _one()
        assert obs.total("dispatches") == d0 + 1
        assert {n: obs.total(n) for n in before} == before

    def test_lane_steps_of_a_hand_built_chunk(self):
        # Per lane: one admission per op, plus attempts + 1 pops for a
        # read, 2 for a write, 1 for an erase.
        cell_a = [_lane([(0, 3)]), _lane([(1, 1)])]           # 5, 3
        cell_b = [_lane([(2, 1)]), _lane([(0, 1), (0, 1)])]   # 2, 6
        stacked = np.concatenate([kops.pad_ops(c, maxp=16)
                                  for c in (cell_a, cell_b)])
        assert list(kops.lane_steps(stacked)) == [5, 3, 2, 6]
        assert kops.count_steps(stacked) == kops.lane_steps(stacked).max()
        timing = np.tile([[8.0, 4.0, 0.0]], (4, 1))
        with obs.recording() as rec:
            kops.fused_core(stacked, 2, False, timing, prio=False)
        c = rec.counters
        assert c["lanes"] == 4
        assert c["lane_steps_run"] == 4 * 6
        assert c["lane_steps_useful"] == 5 + 3 + 2 + 6

    def test_new_variant_counted_once(self):
        # A static key no other test dispatches: 3 lanes over 5 dies.
        ops = kops.pad_ops([_lane([(0, 2)]), _lane([(1, 1)]),
                            _lane([(0, 1), (2, 1)])])
        timing = np.tile([[8.0, 4.0, 0.0]], (3, 1))
        with obs.recording() as first:
            kops.fused_core(ops, 5, True, timing, prio=False)
        with obs.recording() as again:
            kops.fused_core(ops, 5, True, timing, prio=False)
        was, = [s for s in first.spans if s.name == "flashsim.core"]
        core, = [s for s in again.spans if s.name == "flashsim.core"]
        assert first.counters["core_variants_new"] == 1
        assert was.attrs["new_variant"] is True
        assert "core_variants_new" not in again.counters
        assert core.attrs["new_variant"] is False
        assert core.attrs["variant"] == was.attrs["variant"]


def _lane(ops):
    """A lane's (P, 7) table of ``(kind, attempts)`` ops 10 µs apart
    (kind 0 read, 1 write, 2 erase), alternating dies 0 and 1."""
    rows = [[on_grid(10.0 * i), k, i % 2, 660.0, a, 48.0, float(k == 0)]
            for i, (k, a) in enumerate(ops)]
    return np.asarray(rows, np.float64)


def test_span_lands_on_the_profilers_clock(tmp_path):
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.recording() as rec:
            with obs.span("flashsim.prepare"):
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = [ev.duration_ns
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name == "flashsim.prepare"]
    assert len(found) == 1
    assert abs(found[0] * 1e-6 - rec.spans[0].ms) < 0.2
