"""Simulated time on the 2**-10 us tick grid (:mod:`repro.flashsim.simtime`).

The event core's time inputs are rounded onto the grid once, where a run
is prepared; the lockstep core's host wrapper converts them to int64
ticks exactly and raises on anything off the grid — it never rounds.
"""

import math

import numpy as np
import pytest

from repro.flashsim.config import OperatingCondition
from repro.flashsim.simtime import (MAX_TICKS, TICK_US, from_ticks, on_grid,
                                    to_ticks)
from repro.flashsim.ssd import SSDSim, resolve_trace
from repro.core.retry import RetryPolicy
from repro.kernels.fcfs_core import fcfs_core
from repro.kernels.fcfs_core.ops import pad_ops

AGED = OperatingCondition(365.0, 1000.0)


def _on(x) -> bool:
    x = np.asarray(x, np.float64)
    return bool(np.all(x / TICK_US == np.rint(x / TICK_US)))


class TestGrid:
    def test_on_grid_rounds_to_nearest_tick(self):
        assert on_grid(15.4) == 15770 / 1024
        assert on_grid(0.5 / 1024) == 0.0           # half to even
        assert isinstance(on_grid(61.3), float)
        x = np.random.default_rng(0).uniform(0.0, 1e7, 1000)
        g = on_grid(x)
        assert _on(g) and np.all(np.abs(g - x) <= TICK_US / 2)
        assert np.array_equal(on_grid(g), g)

    def test_ticks_round_trip_exactly(self):
        g = on_grid(np.random.default_rng(1).uniform(0.0, 1e9, 1000))
        t = to_ticks(g)
        assert t.dtype == np.int64
        assert np.array_equal(from_ticks(t), g)

    @pytest.mark.parametrize("bad", [0.1, math.inf, math.nan,
                                     MAX_TICKS * TICK_US],
                             ids=["off-grid", "inf", "nan", "past-range"])
    def test_to_ticks_raises_never_rounds(self, bad):
        with pytest.raises(ValueError, match="tick grid"):
            to_ticks(np.array([1.0, bad]))

    def test_on_grid_sums_are_exact_in_f64(self):
        """The interpreter's f64 add on on-grid values is the int64 tick
        add — what lets both engines agree bit for bit."""
        rng = np.random.default_rng(2)
        a, b = on_grid(rng.uniform(0, 1e6, 500)), on_grid(rng.uniform(0, 1e3, 500))
        assert np.array_equal(to_ticks(a + b), to_ticks(a) + to_ticks(b))


class TestBoundary:
    def test_core_raises_on_off_grid_table_time(self):
        lane = np.array([[0.1, 0.0, 0.0, 0.0, 1.0, 40.0, 1.0]])
        with pytest.raises(ValueError, match="tick grid"):
            fcfs_core(pad_ops([lane]), 1, False, 16.0, 8.0)

    def test_core_raises_on_off_grid_tdma(self):
        lane = np.array([[0.0, 0.0, 0.0, 0.0, 1.0, 40.0, 1.0]])
        with pytest.raises(ValueError, match="tick grid"):
            fcfs_core(pad_ops([lane]), 1, False, 15.4, 8.0)

    @pytest.mark.parametrize("engine,gc", [("array", None),
                                           ("batched", None),
                                           ("array", "prepass")])
    def test_prepare_puts_core_inputs_on_grid(self, engine, gc):
        from repro.flashsim.ssd import _with_knobs
        from repro.flashsim.config import DEFAULT_SSD

        cfg = _with_knobs(DEFAULT_SSD, None, gc)
        sim = SSDSim(cfg, AGED, RetryPolicy("pr2ar2"), engine=engine)
        prep = sim._prepare(resolve_trace("prn", seed=0, n_requests=300))
        b = prep.bufs
        for col in (b.arrival, b.dur, b.tr, [b.tdma, b.tecc],
                    prep.arrival_us):
            assert _on(col)
        # The raw inputs were off the grid: AR²-scaled tR and tDMA.
        assert not _on(61.3 * sim.tr_scale) and not _on(15.4)
