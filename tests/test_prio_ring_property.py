"""Property-based check: dual priority rings == the sched.py pop law.

Randomized padded op tables (lane count, op mix, scheduling classes,
aging bound — including the boundary corners 0, 1, and effectively-
infinite) drawn by hypothesis; every draw must produce **bitwise**
equality between the lockstep kernel's priority lowering
(:func:`repro.kernels.fcfs_core.fcfs_core` with ``age_bound``) and the
pure-Python oracle (:func:`repro.kernels.fcfs_core.fcfs_core_ref`),
whose queue closures restate ``AgedHostPrioQueue.pop_next`` from
:mod:`repro.flashsim.sched` verbatim.  End-to-end SimStats equality of
the same policies is separately drawn in ``test_batched_property.py``
style by :mod:`test_batched_engine`; this suite attacks the ring
mechanics directly, where shrinking finds minimal counterexamples.
Skipped when the optional ``hypothesis`` dependency is absent (mirrors
``test_properties.py``).
"""

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional dependency 'hypothesis' not installed; "
           "property tests skipped",
)

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.flashsim.simtime import on_grid
from repro.kernels.fcfs_core import fcfs_core, fcfs_core_ref
from repro.kernels.fcfs_core.ops import pad_ops

_draws = st.tuples(
    st.integers(0, 2 ** 31 - 1),         # table rng seed
    st.integers(1, 4),                   # lanes
    st.integers(1, 4),                   # dies per lane
    st.integers(1, 30),                  # max ops per lane
    st.sampled_from([0.0, 1.0, 2.0, 4.0, 7.0, 1e18]),  # aging bound
    st.booleans(),                       # pipelined
    st.floats(0.1, 0.9),                 # host-read (hp) fraction
)


def _table(rng, n_ops, n_dies, hp_frac):
    arr = np.sort(on_grid(rng.uniform(0.0, 300.0, n_ops)))
    kind = rng.choice([0.0, 0.0, 1.0, 2.0], size=n_ops)
    die = rng.integers(0, n_dies, n_ops).astype(np.float64)
    dur = on_grid(rng.uniform(10.0, 60.0, n_ops))
    att = rng.integers(1, 6, n_ops).astype(np.float64)
    tr = on_grid(rng.uniform(5.0, 25.0, n_ops))
    hp = np.where((kind == 0.0) & (rng.random(n_ops) < hp_frac),
                  1.0, 0.0)
    return np.stack([arr, kind, die, dur, att, tr, hp], axis=1)


@settings(max_examples=25, deadline=None)
@given(_draws)
def test_priority_rings_match_sched_reference(draw):
    seed, n_lanes, n_dies, max_ops, bound, pipelined, hp_frac = draw
    rng = np.random.default_rng(seed)
    lanes = [_table(rng, int(rng.integers(1, max_ops + 1)), n_dies,
                    hp_frac)
             for _ in range(n_lanes)]
    ops = pad_ops(lanes)
    got = fcfs_core(ops, n_dies, pipelined, 3.0, 5.0, age_bound=bound)
    want = fcfs_core_ref(ops, n_dies, pipelined, 3.0, 5.0,
                         age_bound=bound)
    for g, w in zip(got, want):
        assert np.array_equal(g, w)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.booleans())
def test_fifo_lowering_unchanged_by_hp_column(seed, pipelined):
    # fcfs must ignore the scheduling class entirely: the same table
    # with hp scrambled lowers to the identical single-ring run.
    rng = np.random.default_rng(seed)
    t = _table(rng, int(rng.integers(2, 20)), 3, 0.5)
    t2 = t.copy()
    t2[:, 6] = 1.0 - t2[:, 6]
    a = fcfs_core(pad_ops([t]), 3, pipelined, 3.0, 5.0)
    b = fcfs_core(pad_ops([t2]), 3, pipelined, 3.0, 5.0)
    for g, w in zip(a, b):
        assert np.array_equal(g, w)
