"""Simulated time: one dyadic tick shared by every engine.

Every time the event core consumes — op arrivals, sense times (tR,
AR²-scaled), program/erase durations, tDMA and tECC — is rounded onto a
grid of ``TICK_US = 2**-10`` µs (about one nanosecond) once, where the
run's inputs are built (:meth:`repro.flashsim.ssd.SSDSim._prepare`).  On
that grid the interpreter's f64 adds, subtractions and maxes are exact
below ``2**43`` µs (about 100 days of simulated time), so the
interpreter, the kernel oracle and the lockstep core — which carries the
same values as int64 ticks (:func:`to_ticks`) — agree bit for bit on
any backend, including one whose f64 is not IEEE.
"""

from __future__ import annotations

import numpy as np

#: Ticks per microsecond (a power of two, so µs <-> ticks is exact).
TICKS_PER_US = 1024
TICK_US = 1.0 / TICKS_PER_US
#: Largest tick count an f64 holds exactly (2**53): the grid's range.
MAX_TICKS = 2 ** 53


def on_grid(x):
    """Round ``x`` (µs; a float or an array) to the nearest tick."""
    if isinstance(x, float):
        return round(x * TICKS_PER_US) / TICKS_PER_US
    return np.rint(np.asarray(x, np.float64) * TICKS_PER_US) / TICKS_PER_US


def to_ticks(x) -> np.ndarray:
    """Exact int64 tick counts of on-grid µs values.

    Raises ``ValueError`` on a value off the grid, non-finite, or beyond
    ``MAX_TICKS`` — it never rounds: inputs are put on the grid once,
    by :func:`on_grid`, where the run is prepared.
    """
    t = np.asarray(x, np.float64) * TICKS_PER_US
    bad = ~np.isfinite(t) | (np.abs(t) >= MAX_TICKS) | (t != np.rint(t))
    if bad.any():
        v = np.asarray(x, np.float64)[bad].flat[0]
        raise ValueError(
            f"simulated time {v!r} us is off the 2**-10 us tick grid "
            f"(round it with repro.flashsim.simtime.on_grid)")
    return t.astype(np.int64)


def from_ticks(t) -> np.ndarray:
    """µs (f64) of int64 tick counts — exact below ``MAX_TICKS``."""
    return np.asarray(t, np.int64).astype(np.float64) / TICKS_PER_US
