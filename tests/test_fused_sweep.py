"""Fused sweep core: cross-cell vectorization contracts (ISSUE 10).

The fused path stacks the padded op tables of many batched-eligible
cells along the kernel's lane axis and runs them in one dispatch.  Its
contract has three halves, all pinned here:

  * **Bit-parity** — for every eligible (mechanism x condition x seed)
    grid, fused results are *fully* equal (SimStats dataclass equality)
    to the sequential batched engine, cell by cell, and
    :func:`sweep_to_json` is byte-identical for any fusion decision and
    worker count.
  * **Never silent** — ineligible cells run per-cell exactly as before:
    ``engine="batched"`` misconfigurations raise
    :class:`BatchedUnsupported`, ``engine="auto"`` fallbacks record
    their reason on ``SimStats.engine_fallback_reason``; ragged grids
    (mixed schedulers, a faulted cell) fuse the eligible subset only.
  * **Fewer dispatches** — a fused grid launches one kernel per
    step-homogeneous chunk of each static-shape group
    (``KERNEL_DISPATCHES`` accounting), with the cell axis capped so
    the stacked lane count stays inside the scatter-friendly regime;
    cap-boundary grid sizes stay bit-identical.

The widened kernel itself is additionally property-pinned against the
cell-axis oracle (:func:`repro.kernels.fcfs_core.ref.fused_core_ref`)
on randomized multi-cell tables with per-cell timing scalars.
"""

import dataclasses
import json

import pytest

from repro.flashsim.config import (
    DEFAULT_SSD,
    FaultConfig,
    OperatingCondition,
)
from repro.flashsim.engine_batched import (
    BatchedUnsupported,
    _fuse_cell_cap,
)
from repro.flashsim.runtime import (
    Cell,
    _on_device,
    run_cells,
    sweep_to_json,
)
from repro.flashsim.ssd import compare_mechanisms, simulate_batch

AGED = OperatingCondition(365.0, 1000.0)
MODEST = OperatingCondition(30.0, 0.0)

#: Mixed pipelined classes: baseline/sota serial, pr2ar2 pipelined —
#: a fused grid over these must split into two static groups.
MECHS = ("baseline", "sota", "pr2ar2")


def _grid(fuse, conds=(AGED, MODEST), mechs=MECHS, seeds=(0, 1), n=200,
          **kw):
    return simulate_batch(
        "websearch", conds, mechanisms=mechs, seeds=seeds, n_requests=n,
        engine="batched", fuse=fuse, **kw,
    )


class TestFusedParity:
    """Full SimStats equality, fused vs sequential batched."""

    def test_full_grid_equality(self):
        fused, seq = _grid(True), _grid(False)
        assert list(fused) == list(seq)
        for key in seq:
            assert fused[key] == seq[key], key
        assert all(st.fused_cells > 1 for st in fused.values())
        assert all(st.fused_cells == 0 for st in seq.values())

    def test_compare_mechanisms_equality(self):
        mechs = ("baseline", "sota", "pr2", "ar2", "pr2ar2", "sota+pr2ar2")
        kw = dict(mechanisms=mechs, seed=0, n_requests=300,
                  engine="batched")
        fused = compare_mechanisms("oltp", AGED, fuse=True, **kw)
        seq = compare_mechanisms("oltp", AGED, fuse=False, **kw)
        assert list(fused) == list(seq)
        for m in mechs:
            assert fused[m] == seq[m], m
        # {baseline, sota, ar2} serial + {pr2, pr2ar2, sota+pr2ar2}
        # pipelined -> two static groups of 3, which the deterministic
        # step-homogeneity chunker further splits: the retry-reducing
        # mechanisms (sota, sota+pr2ar2) run >1.5x fewer lockstep steps
        # than their classmates, so each group fuses as a pair plus a
        # singleton.
        assert [fused[m].fused_cells for m in mechs] == [2, 1, 2, 2, 2, 1]
        assert all(st.fused_cells >= 1 for st in fused.values())

    @pytest.mark.parametrize("scheduler", [
        "host_prio", "host_prio_aged:3",
    ])
    def test_priority_schedulers(self, scheduler):
        fused = _grid(True, seeds=(0,), scheduler=scheduler)
        seq = _grid(False, seeds=(0,), scheduler=scheduler)
        for key in seq:
            assert fused[key] == seq[key], key

    def test_gc_prepass(self):
        fused = _grid(True, seeds=(0,), gc="prepass")
        seq = _grid(False, seeds=(0,), gc="prepass")
        for key in seq:
            assert fused[key] == seq[key], key

    def test_explicit_batched_still_raises(self):
        """Fusion never converts a hard rejection into a fallback."""
        with pytest.raises(BatchedUnsupported):
            _grid(True, seeds=(0,), scheduler="tokens")

    def test_auto_fallback_records_reason(self):
        out = simulate_batch(
            "websearch", (AGED,), mechanisms=("baseline",), seeds=(0,),
            n_requests=150, engine="auto", scheduler="tokens", fuse=True,
        )
        st = next(iter(out.values()))
        assert st.engine_selected == "array"
        assert st.engine_fallback_reason
        assert st.fused_cells == 0


class TestCrossCellFusion:
    """run_cells fuses eligible "simulate" cells sharing trace + config."""

    @staticmethod
    def _cells(mechs, fuse=None, **kw):
        return [
            Cell("simulate", "websearch", (AGED,), (m,), 5, DEFAULT_SSD,
                 200, "batched", None, None, False, fuse=fuse, **kw)
            for m in mechs
        ]

    def test_cross_cell_parity_and_counters(self):
        cells = self._cells(("baseline", "sota", "pr2ar2", "pr2ar2"))
        fused = run_cells(cells, workers=1)
        seq = run_cells(self._cells(
            ("baseline", "sota", "pr2ar2", "pr2ar2"), fuse=False),
            workers=1)
        assert fused == seq
        # {baseline, sota} share the serial static group but sota's
        # retry reduction puts it >1.5x under baseline's step bound, so
        # the chunker runs each alone; the identical pr2ar2 pair fuses.
        assert [st.fused_cells for st in fused] == [1, 1, 2, 2]

    def test_ragged_mixed_schedulers(self):
        eligible = self._cells(("baseline", "sota"))
        ineligible = [dataclasses.replace(c, engine="auto",
                                          scheduler="tokens")
                      for c in self._cells(("baseline",))]
        results = run_cells(eligible + ineligible, workers=1)
        seq = run_cells(
            [dataclasses.replace(c, fuse=False)
             for c in eligible + ineligible], workers=1)
        assert results == seq
        # Eligible cells route through the fused path (step-split into
        # singleton chunks here — see the chunker note above) while the
        # ineligible cell falls back per-cell with its reason recorded.
        assert [st.fused_cells for st in results[:2]] == [1, 1]
        assert results[2].engine_selected == "array"
        assert results[2].engine_fallback_reason
        assert results[2].fused_cells == 0

    def test_faulted_cell_falls_back_alone(self):
        faults = FaultConfig(uncorrectable_prob=0.01)
        eligible = self._cells(("baseline", "sota"))
        faulted = [dataclasses.replace(c, engine="auto", faults=faults)
                   for c in self._cells(("baseline",))]
        results = run_cells(eligible + faulted, workers=1)
        assert [st.fused_cells for st in results] == [1, 1, 0]
        assert results[2].engine_selected == "array"
        assert results[2].engine_fallback_reason

    def test_singleton_not_fused(self):
        [st] = run_cells(self._cells(("baseline",)), workers=1)
        assert st.fused_cells == 0


class TestBucketsAndDispatch:
    """Cell-axis chunking/cap policy and dispatch accounting."""

    @pytest.mark.parametrize("n_seeds", [7, 8, 9])
    def test_cap_boundaries_stay_bit_identical(self, n_seeds):
        """Seed grids straddling the fused cell cap (8 on the default
        8-channel geometry: one under, exactly at, one over) hold
        parity, and an over-cap grid splits into a full chunk plus the
        remainder rather than stacking past the cache knee."""
        cap = _fuse_cell_cap(DEFAULT_SSD.n_channels)
        assert cap == 8
        seeds = tuple(range(n_seeds))
        kw = dict(conds=(AGED,), mechs=("baseline",), seeds=seeds, n=150)
        fused, seq = _grid(True, **kw), _grid(False, **kw)
        for key in seq:
            assert fused[key] == seq[key], key
        sizes = sorted(st.fused_cells for st in fused.values())
        full, rem = divmod(n_seeds, cap)
        want = sorted([cap] * (cap * full) + [rem] * rem)
        assert sizes == want

    def test_mixed_condition_grid_parity(self):
        """Condition-heterogeneous grids hold parity however the
        step-homogeneity chunker splits them (AGED cells run many more
        retry steps than MODEST ones)."""
        conds = (AGED, MODEST, OperatingCondition(120.0, 500.0))
        fused = _grid(True, conds=conds, mechs=MECHS, seeds=(0,), n=150)
        seq = _grid(False, conds=conds, mechs=MECHS, seeds=(0,), n=150)
        for key in seq:
            assert fused[key] == seq[key], key
        assert all(st.fused_cells >= 1 for st in fused.values())

    def test_single_dispatch_per_chunk(self):
        from repro.kernels.fcfs_core import ops as kops

        kw = dict(conds=(AGED,), mechs=MECHS, seeds=(0, 1, 2), n=150)
        _grid(True, **kw)                      # warm caches
        before = kops.KERNEL_DISPATCHES
        _grid(True, **kw)
        fused_n = kops.KERNEL_DISPATCHES - before
        before = kops.KERNEL_DISPATCHES
        _grid(False, **kw)
        seq_n = kops.KERNEL_DISPATCHES - before
        # Seeds of one (workload, condition, mechanism) combo run
        # near-identical step counts, so each mechanism's three seeds
        # share one dispatch: 3 launches for the 9-cell grid vs one per
        # cell sequentially.
        assert fused_n == 3
        assert seq_n == 9


class TestSweepJsonByteIdentity:
    """sweep_to_json is invariant across workers x fusion decisions."""

    def _blob(self, workers, fuse):
        return sweep_to_json(_grid(
            fuse, mechs=("baseline", "pr2ar2"), seeds=(0, 1), n=150,
            workers=workers,
        ))

    def test_workers_and_fusion_invariant(self):
        blobs = {(wk, fz): self._blob(wk, fz)
                 for wk in (1, 2) for fz in (True, False)}
        vals = list(blobs.values())
        assert all(v == vals[0] for v in vals[1:])
        payload = json.loads(vals[0])
        assert len(payload) == 2 * 2 * 2
        # Observability fields must not leak into the canonical bytes.
        for cell in payload.values():
            assert "fused_cells" not in cell
            assert "engine_selected" not in cell


class TestDeviceRouting:
    """Which cells may run the lockstep core — those stay in the process
    that holds the accelerator (run_cells never hands them to a pool)."""

    def test_auto_ineligible_stays_off_device(self):
        cell = Cell("batch", "websearch", (AGED,), MECHS, 0,
                    DEFAULT_SSD, 200, "auto", "tokens", None, False)
        assert not _on_device(cell)

    def test_array_engine_stays_off_device(self):
        cell = Cell("batch", "websearch", (AGED,), MECHS, 0,
                    DEFAULT_SSD, 200, "array", None, None, False)
        assert not _on_device(cell)

    @pytest.mark.parametrize("engine", ["batched", "auto"])
    def test_eligible_cells_on_device(self, engine):
        cell = Cell("batch", "websearch", (AGED, MODEST), MECHS, 0,
                    DEFAULT_SSD, 200, engine, None, None, False)
        assert _on_device(cell)

    def test_fuse_off_still_on_device(self):
        cell = Cell("batch", "websearch", (AGED, MODEST), MECHS, 0,
                    DEFAULT_SSD, 200, "batched", None, None, False,
                    fuse=False)
        assert _on_device(cell)
