"""Sharded-runtime contracts: shard equivalence + worker determinism.

The two acceptance properties of the sharded simulation runtime
(ISSUE 5):

  * **Shard equivalence** — the per-channel sharded event core
    (``shard=True``) produces *exactly* the monolithic engine's SimStats
    (full dataclass equality, GC counters included) across every
    scheduler x GC-mode combination, on synthetic traces and on both
    checked-in MSR-format excerpts.
  * **Worker determinism** — ``simulate_batch`` through the process-pool
    sweep executor returns identical cells in identical order for any
    worker count: the canonical JSON serialization is byte-identical
    for ``workers in {1, 2, 4}``.
"""

import dataclasses
import json

import pytest

from repro.core.retry import RetryPolicy
from repro.flashsim.config import (
    DEFAULT_SSD,
    OperatingCondition,
    SSDConfig,
)
from repro.flashsim.engine import merge_shard_results
from repro.flashsim.runtime import (
    Cell,
    host_fingerprint,
    run_cells,
    sweep_cell_key,
    sweep_to_json,
)
from repro.flashsim.sched import SCHEDULERS
from repro.flashsim.ssd import (
    SSDSim,
    _with_knobs,
    compare_mechanisms,
    simulate,
    simulate_batch,
)
from repro.flashsim.workloads import cached_trace, make_workloads

AGED = OperatingCondition(365.0, 1000.0)
MODEST = OperatingCondition(30.0, 0.0)

GC_MODES = ("off", "prepass", "online")

#: Checked-in MSR-format excerpts (resolved via the tests/data search
#: path fallback baked into the workload registry).
MSR_EXCERPTS = ("msr:web_0", "msr:src1_1")


class TestShardEquivalence:
    """shard=True must be bit-identical to the monolithic event core."""

    @pytest.mark.parametrize("scheduler", SCHEDULERS)
    @pytest.mark.parametrize("gc", GC_MODES)
    def test_synthetic_all_scheduler_gc_combos(self, scheduler, gc):
        """Full SimStats equality (== over every field, GC counters and
        suspension counts included) on a GC-churning write-heavy trace."""
        w = dataclasses.replace(make_workloads()["rsrch"], n_requests=800)
        trace = cached_trace(w, seed=1)
        cfg = _with_knobs(DEFAULT_SSD, scheduler, gc)
        mono = SSDSim(cfg, AGED, RetryPolicy("pr2ar2"), seed=9).run(trace)
        shrd = SSDSim(cfg, AGED, RetryPolicy("pr2ar2"), seed=9).run(
            trace, shard=True)
        assert mono == shrd

    @pytest.mark.parametrize("spec", MSR_EXCERPTS)
    @pytest.mark.parametrize("scheduler", SCHEDULERS)
    @pytest.mark.parametrize("gc", GC_MODES)
    def test_msr_excerpts_all_scheduler_gc_combos(self, spec, scheduler, gc):
        """Both checked-in MSR-format excerpts, ingested end-to-end
        (dense remap + FTL auto-sizing), sharded vs monolithic."""
        a = simulate(spec, AGED, "pr2ar2", seed=0, n_requests=600,
                     scheduler=scheduler, gc=gc)
        b = simulate(spec, AGED, "pr2ar2", seed=0, n_requests=600,
                     scheduler=scheduler, gc=gc, shard=True)
        assert a == b

    @pytest.mark.parametrize("mechanism", ["baseline", "pr2", "sota+pr2ar2"])
    def test_mechanisms_and_conditions(self, mechanism):
        """Serial and pipelined read state machines, aged and modest."""
        w = make_workloads()["websearch"]
        for cond in (AGED, MODEST):
            a = simulate(w, cond, mechanism, seed=3, n_requests=500)
            b = simulate(w, cond, mechanism, seed=3, n_requests=500,
                         shard=True)
            assert a == b

    def test_nondefault_geometry(self):
        """Sharding follows the configured channel count, not the
        default 8 — 2x4 and 1x8 (single channel short-circuits)."""
        w = dataclasses.replace(make_workloads()["prxy"], n_requests=400)
        for cfg in (SSDConfig(n_channels=2, dies_per_channel=4),
                    SSDConfig(n_channels=1, dies_per_channel=8)):
            a = simulate(w, AGED, "pr2ar2", seed=0, cfg=cfg)
            b = simulate(w, AGED, "pr2ar2", seed=0, cfg=cfg, shard=True)
            assert a == b

    def test_per_request_completions_match(self):
        """Stronger than SimStats: the merged completion stream equals
        the monolithic one at every request."""
        import numpy as np

        w = dataclasses.replace(make_workloads()["rsrch"], n_requests=800)
        trace = cached_trace(w, seed=0)
        cfg = _with_knobs(DEFAULT_SSD, "host_prio", "online")
        mono = SSDSim(cfg, AGED, RetryPolicy("baseline"), seed=7)
        shrd = SSDSim(cfg, AGED, RetryPolicy("baseline"), seed=7)
        mono.run(trace)
        shrd.run(trace, shard=True)
        np.testing.assert_array_equal(mono.last_req_done_us,
                                      shrd.last_req_done_us)

    def test_sharded_work_conservation_validated(self):
        """The engine's per-step work-conservation assertion holds inside
        every shard loop."""
        w = dataclasses.replace(make_workloads()["rsrch"], n_requests=600)
        trace = cached_trace(w, seed=1)
        for scheduler in ("fcfs", "preempt"):
            cfg = _with_knobs(DEFAULT_SSD, scheduler, "online")
            sim = SSDSim(cfg, AGED, RetryPolicy("pr2ar2"), seed=9)
            stats = sim.run(trace, validate=True, shard=True)
            assert stats.n_requests == 600

    def test_reference_engine_rejects_shard(self):
        w = make_workloads()["websearch"]
        with pytest.raises(NotImplementedError, match="shard"):
            simulate(w, AGED, "baseline", seed=0, n_requests=100,
                     engine="reference", shard=True)
        with pytest.raises(NotImplementedError, match="shard"):
            simulate_batch(w, (AGED,), mechanisms=("baseline",),
                           seeds=(0,), n_requests=100,
                           engine="reference", shard=True)

    def test_merge_requires_one_result_per_channel(self):
        with pytest.raises(ValueError, match="per channel"):
            merge_shard_results(DEFAULT_SSD, [])


class TestWorkerDeterminism:
    """simulate_batch output must be byte-identical for any workers."""

    def _sweep(self, workers, shard=False):
        w = make_workloads()["websearch"]
        return simulate_batch(
            w, (AGED, MODEST), mechanisms=("baseline", "pr2ar2"),
            seeds=(0, 1, 2), n_requests=300, workers=workers, shard=shard,
        )

    def test_workers_1_2_4_byte_identical(self):
        blobs = {wk: sweep_to_json(self._sweep(wk)) for wk in (1, 2, 4)}
        assert blobs[1] == blobs[2] == blobs[4]
        # and the serialization is loadable, fully keyed JSON
        payload = json.loads(blobs[1])
        assert len(payload) == 2 * 2 * 3

    def test_key_order_is_canonical(self):
        """Dict iteration order (seed -> condition -> mechanism) matches
        the inline sweep's insertion order for every worker count."""
        assert list(self._sweep(1)) == list(self._sweep(4))

    def test_workers_compose_with_shard(self):
        assert sweep_to_json(self._sweep(1)) == \
            sweep_to_json(self._sweep(2, shard=True))

    def test_inline_fallback_env(self, monkeypatch):
        monkeypatch.setenv("REPRO_SWEEP_INLINE", "1")
        forced = self._sweep(4)
        monkeypatch.delenv("REPRO_SWEEP_INLINE")
        assert sweep_to_json(forced) == sweep_to_json(self._sweep(1))

    def test_reference_engine_workers_match_inline(self):
        """The seed-group fan-out is engine-agnostic: the reference
        engine parallelizes too (each worker re-enters the inline
        path), with identical cells."""
        w = make_workloads()["websearch"]
        kw = dict(mechanisms=("baseline",), seeds=(0, 1), n_requests=150,
                  engine="reference")
        a = simulate_batch(w, (AGED,), **kw)
        b = simulate_batch(w, (AGED,), workers=2, **kw)
        assert a == b
        assert list(a) == list(b)

    def test_sweep_cell_key_full_float_precision(self):
        """Conditions differing past 6 significant digits must not
        collapse to one JSON key (repr precision, not %g)."""
        c1 = OperatingCondition(365.00001, 0.0)
        c2 = OperatingCondition(365.00002, 0.0)
        assert sweep_cell_key("baseline", c1, 0) != \
            sweep_cell_key("baseline", c2, 0)

    def test_compare_mechanisms_workers_match_inline(self):
        w = make_workloads()["prn"]
        a = compare_mechanisms(w, AGED, mechanisms=("baseline", "pr2ar2"),
                               seed=0, n_requests=400, gc="prepass")
        b = compare_mechanisms(w, AGED, mechanisms=("baseline", "pr2ar2"),
                               seed=0, n_requests=400, gc="prepass",
                               workers=2)
        assert a == b
        assert list(a) == list(b)


class TestCellExecutor:
    def test_results_in_input_order(self):
        w = make_workloads()["websearch"]
        cells = [
            Cell("simulate", w, (AGED,), ("baseline",), seed, DEFAULT_SSD,
                 n_requests=200)
            for seed in (3, 1, 2)
        ]
        par = run_cells(cells, workers=3)
        inline = run_cells(cells, workers=1)
        assert par == inline
        # distinct seeds produce distinct traces -> distinct stats, so
        # positional equality above proves ordering, not just content
        assert len({s.mean_us for s in inline}) == 3

    def test_chunked_submission_payload_equality(self):
        """Chunking regression contract: pool submission groups several
        cells per task (amortizing per-cell IPC — the BENCH-recorded
        0.92x small-cell slowdown), and the payload stays byte-identical
        to the unchunked inline run for any worker count."""
        from repro.flashsim.runtime import _chunk_pending

        w = make_workloads()["websearch"]
        cells = [
            Cell("simulate", w, (AGED,), (m,), seed, DEFAULT_SSD,
                 n_requests=120)
            for seed in range(5) for m in ("baseline", "pr2ar2")
        ]
        # chunking really happens: 10 cells over 2 workers -> fewer
        # tasks than cells, every cell present exactly once, in order
        chunks = _chunk_pending(dict(enumerate(cells)), workers=2)
        assert len(chunks) < len(cells)
        flat = [i for ch in chunks for i, _ in ch]
        assert flat == list(range(len(cells)))
        blobs = {}
        for wk in (1, 2, 3):
            rs = run_cells(cells, workers=wk)
            blobs[wk] = json.dumps(
                [dataclasses.asdict(r) for r in rs], sort_keys=True)
        assert blobs[1] == blobs[2] == blobs[3]

    def test_cell_kind_validation(self):
        w = make_workloads()["websearch"]
        with pytest.raises(ValueError, match="kind"):
            Cell("fanout", w, (AGED,), ("baseline",), 0)
        with pytest.raises(ValueError, match="one mechanism"):
            Cell("simulate", w, (AGED,), ("baseline", "pr2"), 0)
        with pytest.raises(ValueError, match="one condition"):
            Cell("compare", w, (AGED, MODEST), ("baseline",), 0)

    def test_cell_errors_propagate(self):
        w = make_workloads()["websearch"]
        bad = Cell("simulate", w, (AGED,), ("no-such-mechanism",), 0,
                   n_requests=50)
        with pytest.raises(ValueError):
            run_cells([bad], workers=1)
        with pytest.raises(ValueError):
            run_cells([bad, bad], workers=2)

    def test_sweep_cell_keys_unique(self):
        keys = {
            sweep_cell_key(m, c, s)
            for m in ("baseline", "pr2ar2")
            for c in (AGED, MODEST, OperatingCondition(365.0, 0.0))
            for s in (0, 1)
        }
        assert len(keys) == 12

    def test_host_fingerprint_fields(self):
        fp = host_fingerprint()
        assert set(fp) == {"cpu_model", "cpu_count", "platform", "python",
                           "numpy"}
        assert fp["cpu_count"] >= 1


class TestOneProcessPerChip:
    """Cells that may run the lockstep core never reach a pool worker:
    the accelerator belongs to the process that holds it."""

    @staticmethod
    def _no_pool(monkeypatch):
        from repro.flashsim import runtime

        def refuse(*a, **kw):
            raise AssertionError("a pool was started for device cells")

        monkeypatch.setattr(runtime, "ProcessPoolExecutor", refuse)

    def test_run_cells_batched_workers_2_starts_no_pool(self, monkeypatch):
        cells = [Cell("simulate", "websearch", (AGED,), (m,), 0,
                      DEFAULT_SSD, 150, "batched")
                 for m in ("baseline", "pr2ar2")]
        want = run_cells(cells, workers=1)
        self._no_pool(monkeypatch)
        assert run_cells(cells, workers=2) == want

    def test_run_compare_batched_workers_2_never_forks(self, monkeypatch):
        kw = dict(mechanisms=("baseline", "pr2ar2"), seed=0,
                  n_requests=150, engine="batched", fuse=False)
        want = compare_mechanisms("websearch", AGED, **kw)
        self._no_pool(monkeypatch)
        assert compare_mechanisms("websearch", AGED, workers=2, **kw) == want

    def test_mixed_cells_pool_only_the_array_ones(self, monkeypatch):
        from repro.flashsim import runtime

        device = [Cell("simulate", "websearch", (AGED,), ("pr2ar2",), s,
                       DEFAULT_SSD, 150, "batched") for s in (0, 1)]
        host = [Cell("simulate", "websearch", (AGED,), ("baseline",), s,
                     DEFAULT_SSD, 150, "array") for s in (0, 1)]
        submitted = []

        class Recording(runtime.ProcessPoolExecutor):
            def submit(self, fn, items):
                submitted.extend(c.engine for _, c in items)
                return super().submit(fn, items)

        monkeypatch.setattr(runtime, "ProcessPoolExecutor", Recording)
        cells = device + host
        assert run_cells(cells, workers=2) == run_cells(cells, workers=1)
        assert submitted == ["array", "array"]
