"""Batched lockstep engine: bit-equivalence matrix + explicit rejection.

The contract for ``engine="batched"`` (:mod:`repro.flashsim.engine_batched`)
has two halves, both tested here:

  * on the supported matrix — ring-lowerable scheduling (fcfs,
    host_prio, host_prio_aged[:bound]), gc in {none, prepass}, no
    faults, open loop — every run is **bit-identical** to the array
    interpreter: full :class:`SimStats` dataclass equality, synthetic
    profiles and real MSR excerpts alike;
  * everywhere else the engine **fails fast** with
    :class:`BatchedUnsupported` — never a silent fallback to the
    interpreter.

The lockstep kernel itself is additionally pinned against an
independent pure-Python oracle (:func:`repro.kernels.fcfs_core.
fcfs_core_ref`) on randomized op tables, including the rel=0 /
single-attempt corner where every read senses exactly once and the
aging-boundary corners of the dual priority rings (bound 0 = always
bypass when low work waits, huge bound = plain host_prio).
"""

import dataclasses
from pathlib import Path

import numpy as np
import pytest

from repro.flashsim.config import (
    DEFAULT_SSD,
    FaultConfig,
    OperatingCondition,
    SSDConfig,
)
from repro.flashsim.engine_batched import BatchedUnsupported
from repro.flashsim.sched import SCHEDULERS
from repro.flashsim.simtime import on_grid
from repro.flashsim.ssd import (
    compare_mechanisms,
    simulate,
    simulate_batch,
)
from repro.flashsim.workloads import load_msr_csv

AGED = OperatingCondition(365.0, 1000.0)
MODEST = OperatingCondition(30.0, 0.0)
DATA = Path(__file__).parent / "data"

MECHANISMS = ("baseline", "sota", "pr2", "ar2", "pr2ar2", "sota+pr2ar2")


def _pair(workload="websearch", mechanism="pr2ar2", cond=AGED, seed=0,
          n=800, **kw):
    a = simulate(workload, cond, mechanism, seed=seed, n_requests=n,
                 engine="array", **kw)
    b = simulate(workload, cond, mechanism, seed=seed, n_requests=n,
                 engine="batched", **kw)
    return a, b


class TestSupportedMatrix:
    """Full SimStats equality wherever support is claimed."""

    @pytest.mark.parametrize("mechanism", MECHANISMS)
    def test_all_mechanisms_bit_identical(self, mechanism):
        a, b = _pair(mechanism=mechanism)
        assert a == b

    @pytest.mark.parametrize("gc", [None, "prepass"])
    @pytest.mark.parametrize("workload", ["websearch", "oltp", "prxy"])
    def test_workloads_and_gc_modes(self, workload, gc):
        a, b = _pair(workload=workload, gc=gc)
        assert a == b

    @pytest.mark.parametrize("scheduler", [
        "host_prio", "host_prio_aged", "host_prio_aged:3",
    ])
    @pytest.mark.parametrize("gc", [None, "prepass"])
    def test_priority_schedulers_bit_identical(self, scheduler, gc):
        a, b = _pair(gc=gc, scheduler=scheduler)
        assert a == b
        assert b.fast_path_events > 0

    def test_priority_reordering_is_exercised(self):
        # Parity must not be vacuous: on a write-heavy profile the
        # priority rings genuinely reorder grants, so host-read
        # latency differs from fcfs — and batched still matches the
        # interpreter bit for bit on both.
        a_f, b_f = _pair(workload="prn", n=600, gc="prepass")
        a_p, b_p = _pair(workload="prn", n=600, gc="prepass",
                         scheduler="host_prio")
        assert a_f == b_f and a_p == b_p
        assert a_p.read_p99_us != a_f.read_p99_us

    def test_modest_condition(self):
        a, b = _pair(cond=MODEST)
        assert a == b

    def test_shard_flag_is_a_noop(self):
        # engine="batched" IS the per-channel decomposition; shard=True
        # selects the same lockstep run, still equal to the array core.
        a, b = _pair(shard=True)
        assert a == b
        _, b2 = _pair(shard=False)
        assert b == b2

    @pytest.mark.parametrize("spec,gc", [
        ("web_0", None), ("src1_1", None), ("src1_1", "prepass"),
    ])
    def test_msr_excerpts_bit_identical(self, spec, gc):
        trace = load_msr_csv(DATA / f"{spec}.csv.gz")
        a = simulate(spec, AGED, "pr2ar2", seed=3, trace=trace,
                     engine="array", gc=gc)
        b = simulate(spec, AGED, "pr2ar2", seed=3, trace=trace,
                     engine="batched", gc=gc)
        assert a == b

    def test_fast_path_counter(self):
        a, b = _pair()
        assert a.fast_path_events == 0
        assert b.fast_path_events > 0
        # the counter is bookkeeping, not physics: excluded from
        # equality so supported-matrix runs compare clean
        assert a == b

    def test_compare_mechanisms_batched(self):
        a = compare_mechanisms("websearch", AGED, seed=1, n_requests=600,
                               engine="array")
        b = compare_mechanisms("websearch", AGED, seed=1, n_requests=600,
                               engine="batched")
        assert list(a) == list(b)
        assert all(a[m] == b[m] for m in a)

    def test_simulate_batch_batched(self):
        conds = (AGED, MODEST)
        a = simulate_batch("websearch", conds, mechanisms=("baseline",
                           "pr2ar2"), seeds=(0, 1), n_requests=400,
                           engine="array")
        b = simulate_batch("websearch", conds, mechanisms=("baseline",
                           "pr2ar2"), seeds=(0, 1), n_requests=400,
                           engine="batched")
        assert list(a) == list(b)
        assert all(a[k] == b[k] for k in a)


class TestConfigEngineField:
    """SSDConfig.engine selects the core when engine= is left unset."""

    def test_cfg_engine_routes_batched(self):
        cfg = dataclasses.replace(DEFAULT_SSD, engine="batched")
        b = simulate("websearch", AGED, "baseline", n_requests=400,
                     cfg=cfg)
        assert b.fast_path_events > 0
        a = simulate("websearch", AGED, "baseline", n_requests=400)
        assert a == b

    def test_explicit_engine_overrides_cfg(self):
        cfg = dataclasses.replace(DEFAULT_SSD, engine="batched")
        a = simulate("websearch", AGED, "baseline", n_requests=400,
                     cfg=cfg, engine="array")
        assert a.fast_path_events == 0

    def test_invalid_engine_rejected_at_construction(self):
        with pytest.raises(ValueError, match="engine"):
            SSDConfig(engine="vectorized")


class TestExplicitRejection:
    """Unsupported configurations raise BatchedUnsupported — loudly."""

    def test_is_a_notimplementederror(self):
        assert issubclass(BatchedUnsupported, NotImplementedError)

    @pytest.mark.parametrize(
        "scheduler",
        [s for s in SCHEDULERS if s in ("tokens", "preempt")])
    def test_unlowerable_schedulers(self, scheduler):
        with pytest.raises(BatchedUnsupported, match="ring-lowerable"):
            simulate("websearch", AGED, "baseline", n_requests=200,
                     engine="batched", scheduler=scheduler)

    def test_online_gc(self):
        with pytest.raises(BatchedUnsupported, match="online"):
            simulate("prxy", AGED, "baseline", n_requests=200,
                     engine="batched", gc="online")

    def test_faults(self):
        with pytest.raises(BatchedUnsupported, match="fault"):
            simulate("websearch", AGED, "baseline", n_requests=200,
                     engine="batched", faults=FaultConfig())

    def test_closed_loop(self):
        with pytest.raises(BatchedUnsupported, match="open-loop"):
            simulate("websearch", AGED, "baseline", n_requests=200,
                     engine="batched", ncq_depth=8)

    def test_validate_flag(self):
        with pytest.raises(BatchedUnsupported, match="validate"):
            simulate("websearch", AGED, "baseline", n_requests=200,
                     engine="batched", validate=True)

    def test_compare_mechanisms_rejects_too(self):
        with pytest.raises(BatchedUnsupported):
            compare_mechanisms("websearch", AGED, n_requests=200,
                               engine="batched", scheduler="tokens")


def _with_select(values):
    """Each value on the platform's carry lowering (None), then forced
    to the TPU's ``"select"`` lowering."""
    return ([(v, None) for v in values]
            + [(v, "select") for v in values])


class TestKernelVsReference:
    """Lockstep kernel vs the independent pure-Python oracle, bitwise,
    on the platform's carry lowering and on the TPU's."""

    @staticmethod
    def _random_table(rng, n_ops, n_dies, attempts):
        arr = np.sort(on_grid(rng.uniform(0.0, 400.0, n_ops)))
        kind = rng.choice([0.0, 0.0, 1.0, 2.0], size=n_ops)
        die = rng.integers(0, n_dies, n_ops).astype(np.float64)
        dur = on_grid(rng.uniform(10.0, 60.0, n_ops))
        att = (np.full(n_ops, 1.0) if attempts == 1
               else rng.integers(1, 6, n_ops).astype(np.float64))
        tr = on_grid(rng.uniform(5.0, 25.0, n_ops))
        # hp: host-read class for ~half the reads (GC copy-back reads
        # are low class, so reads with hp=0 are legal and exercised).
        hp = np.where((kind == 0.0) & (rng.random(n_ops) < 0.5),
                      1.0, 0.0)
        return np.stack([arr, kind, die, dur, att, tr, hp], axis=1)

    @staticmethod
    def _force(monkeypatch, lowering):
        # None keeps the carry lowering this platform picks.
        from repro.kernels.fcfs_core import ops

        if lowering is not None:
            monkeypatch.setattr(ops, "carry_lowering",
                                lambda platform, lanes: lowering)

    @pytest.mark.parametrize("pipelined,lowering", _with_select(
        [False, True]), ids=["False", "True", "False-select",
                             "True-select"])
    @pytest.mark.parametrize("attempts", [1, None],
                             ids=["rel0-single-attempt", "multi-attempt"])
    def test_bitwise_parity_random_tables(self, pipelined, attempts,
                                          lowering, monkeypatch):
        from repro.kernels.fcfs_core import fcfs_core, fcfs_core_ref
        from repro.kernels.fcfs_core.ops import pad_ops

        self._force(monkeypatch, lowering)
        rng = np.random.default_rng(42 if pipelined else 7)
        n_dies = 4
        for _ in range(3):
            lanes = [self._random_table(rng, int(rng.integers(3, 24)),
                                        n_dies, attempts)
                     for _ in range(4)]
            ops = pad_ops(lanes)
            got = fcfs_core(ops, n_dies, pipelined, 3.0, 5.0)
            want = fcfs_core_ref(ops, n_dies, pipelined, 3.0, 5.0)
            for g, w in zip(got, want):
                assert np.array_equal(g, w)

    @pytest.mark.parametrize(
        "age_bound,lowering", _with_select([0.0, 1.0, 4.0, 1e18]),
        ids=["bound0", "bound1", "bound4", "unbounded", "bound0-select",
             "bound1-select", "bound4-select", "unbounded-select"])
    @pytest.mark.parametrize("pipelined", [False, True])
    def test_priority_rings_parity_random_tables(self, pipelined,
                                                 age_bound, lowering,
                                                 monkeypatch):
        # Aging-boundary corners: bound 0 bypasses whenever low work
        # waits behind a host read, bound 1e18 never does (plain
        # host_prio); 1 and 4 sit on the counter-reset boundary.
        from repro.kernels.fcfs_core import fcfs_core, fcfs_core_ref
        from repro.kernels.fcfs_core.ops import pad_ops

        self._force(monkeypatch, lowering)

        rng = np.random.default_rng(int(age_bound) % 97 +
                                    (13 if pipelined else 0))
        n_dies = 3
        for _ in range(3):
            lanes = [self._random_table(rng, int(rng.integers(4, 28)),
                                        n_dies, None)
                     for _ in range(4)]
            ops = pad_ops(lanes)
            got = fcfs_core(ops, n_dies, pipelined, 3.0, 5.0,
                            age_bound=age_bound)
            want = fcfs_core_ref(ops, n_dies, pipelined, 3.0, 5.0,
                                 age_bound=age_bound)
            for g, w in zip(got, want):
                assert np.array_equal(g, w)

    def test_empty_and_single_lane_corners(self, monkeypatch):
        from repro.kernels.fcfs_core import fcfs_core, fcfs_core_ref
        from repro.kernels.fcfs_core.ops import pad_ops

        rng = np.random.default_rng(0)
        lanes = [np.zeros((0, 7)), self._random_table(rng, 5, 2, None)]
        ops = pad_ops(lanes)
        for lowering in (None, "select"):
            with monkeypatch.context() as m:
                self._force(m, lowering)
                for bound in (None, 2.0):
                    got = fcfs_core(ops, 2, False, 3.0, 5.0,
                                    age_bound=bound)
                    want = fcfs_core_ref(ops, 2, False, 3.0, 5.0,
                                         age_bound=bound)
                    for g, w in zip(got, want):
                        assert np.array_equal(g, w)


class TestCarryLowering:
    """The core's carry lowering follows the device's platform and the
    lane count, nothing else."""

    @pytest.mark.parametrize("platform,lanes,want", [
        ("tpu", 1, "select"), ("tpu", 64, "select"),
        ("tpu", 4096, "select"), ("cpu", 8, "unrolled"),
        ("cpu", 64, "unrolled"), ("cpu", 65, "scatter"),
    ])
    def test_choice(self, platform, lanes, want):
        from repro.kernels.fcfs_core import ops

        assert ops.carry_lowering(platform, lanes) == want

    @pytest.mark.parametrize("platform,want", [("tpu", "select"),
                                               ("cpu", "unrolled")])
    def test_dispatch_takes_the_platforms_lowering(self, monkeypatch,
                                                   platform, want):
        # The platform is patched: a chip is not needed to see which
        # lowering a TPU dispatch asks for, and the CPU runs it exactly.
        from repro.kernels.fcfs_core import fcfs_core, fcfs_core_ref, ops
        from repro.kernels.fcfs_core.ops import pad_ops

        seen = []
        core_jit = ops._core_jit

        def spy(*args, **kw):
            seen.append(kw["lowering"])
            return core_jit(*args, **kw)

        monkeypatch.setattr(ops, "_platform", lambda x: platform)
        monkeypatch.setattr(ops, "_core_jit", spy)
        rng = np.random.default_rng(5)
        table = pad_ops([TestKernelVsReference._random_table(
            rng, 12, 3, None) for _ in range(3)])
        got = fcfs_core(table, 3, True, 3.0, 5.0, age_bound=2.0)
        want_stats = fcfs_core_ref(table, 3, True, 3.0, 5.0, age_bound=2.0)
        assert seen == [want]
        for g, w in zip(got, want_stats):
            assert np.array_equal(g, w)


class TestCompileCache:
    """The persistent compile cache honours JAX_COMPILATION_CACHE_DIR and
    otherwise lands at one fixed directory inside the checkout."""

    CHECKOUT = Path(__file__).resolve().parents[1]

    def test_env_dir_wins(self, monkeypatch, tmp_path):
        from repro.kernels.fcfs_core import ops

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert ops.compile_cache_dir() == str(tmp_path)

    def test_default_is_fixed_in_checkout(self, monkeypatch):
        from repro.kernels.fcfs_core import ops

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert ops.compile_cache_dir() == str(self.CHECKOUT / ".jax_cache")

    @pytest.mark.parametrize("env", [False, True], ids=["unset", "set"])
    def test_enable_sets_the_dir_only_when_env_unset(self, monkeypatch,
                                                     tmp_path, env):
        import jax

        from repro.kernels.fcfs_core import ops

        before = jax.config.jax_compilation_cache_dir
        if env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(ops, "_COMP_CACHE_READY", False)
        try:
            ops._enable_persistent_cache()
            want = before if env else str(self.CHECKOUT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == want
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
