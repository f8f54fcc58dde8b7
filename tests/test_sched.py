"""Scheduler-layer invariants: fcfs equivalence, priorities, preemption.

The die-queue scheduler (repro.flashsim.sched) must (a) leave the default
``fcfs`` policy bit-identical to the pre-refactor engine, (b) conserve
work under every policy (no idle die with a runnable op), (c) never
starve host reads under ``host_prio``, (d) account suspend/resume time
exactly (elapsed + residual == original duration), and (e) keep GC page
ops (rid == -1) out of host-read percentiles under every policy and GC
mode.
"""

import dataclasses

import numpy as np
import pytest

from repro.core.retry import RetryPolicy
from repro.flashsim.config import (
    DEFAULT_SSD,
    GCConfig,
    OperatingCondition,
    SSDConfig,
)
from repro.flashsim.ftl import OP_ERASE, OP_READ, FTLSchedule, FTLStats
from repro.flashsim.sched import (
    DEFAULT_TOKEN_BUDGETS,
    SCHEDULERS,
    AgedHostPrioQueue,
    FCFSQueue,
    HostPrioQueue,
    TokenBudgetQueue,
    get_scheduler,
)
from repro.flashsim.simtime import on_grid
from repro.flashsim.ssd import SSDSim, _with_knobs, simulate
from repro.flashsim.workloads import (
    RequestTrace,
    Workload,
    cached_trace,
    make_workloads,
)

AGED = OperatingCondition(365.0, 1000.0)
GC_SSD = SSDConfig(gc=GCConfig(enabled=True))

STAT_FIELDS = (
    "mean_us", "p50_us", "p95_us", "p99_us", "read_mean_us", "read_p99_us",
    "n_requests", "mean_read_attempts", "die_util", "channel_util",
)


def _stats_tuple(s):
    return tuple(getattr(s, f) for f in STAT_FIELDS)


class TestQueuePolicies:
    def test_registry(self):
        assert SCHEDULERS == ("fcfs", "host_prio", "host_prio_aged",
                              "tokens", "preempt")
        assert not get_scheduler("fcfs").prioritized
        assert get_scheduler("host_prio").prioritized
        assert get_scheduler("host_prio_aged").prioritized
        assert not get_scheduler("host_prio_aged").preemptive
        assert get_scheduler("tokens").prioritized
        assert not get_scheduler("tokens").preemptive
        assert get_scheduler("preempt").preemptive
        with pytest.raises(ValueError, match="unknown scheduler"):
            get_scheduler("sjf")
        with pytest.raises(ValueError, match="unknown scheduler"):
            SSDConfig(scheduler="edf")
        # the aged policy takes a ':bound' suffix; tokens a ':r,w' one
        assert get_scheduler("host_prio_aged:8").name == "host_prio_aged:8"
        SSDConfig(scheduler="host_prio_aged:8")
        assert get_scheduler("tokens:6,2").name == "tokens:6,2"
        SSDConfig(scheduler="tokens:6,2")
        with pytest.raises(ValueError, match="unknown scheduler"):
            get_scheduler("fcfs:3")
        with pytest.raises(ValueError, match="age bound"):
            get_scheduler("host_prio_aged:many")
        # bad bounds fail at config time, not mid-simulation
        for bad in ("host_prio_aged:0", "host_prio_aged:-3"):
            with pytest.raises(ValueError, match="age bound"):
                get_scheduler(bad)
            with pytest.raises(ValueError, match="age bound"):
                SSDConfig(scheduler=bad)
        # trailing-colon names are not silently coerced to base policies
        for bad in ("fcfs:", "host_prio:", "host_prio_aged:", "tokens:"):
            with pytest.raises(ValueError, match="unknown scheduler"):
                get_scheduler(bad)
        # malformed token budgets fail at config time too
        for bad in ("tokens:3", "tokens:1,2,3", "tokens:a,b"):
            with pytest.raises(ValueError, match="token budgets"):
                get_scheduler(bad)
        for bad in ("tokens:0,2", "tokens:4,-1"):
            with pytest.raises(ValueError, match=">= 1"):
                get_scheduler(bad)
            with pytest.raises(ValueError, match=">= 1"):
                SSDConfig(scheduler=bad)

    def test_fcfs_queue_is_a_deque(self):
        q = FCFSQueue()
        q.append(3)
        q.append(7)
        assert len(q) == 2 and bool(q)
        assert q.pop_next() == 3 and q.pop_next() == 7
        assert not q

    def test_host_prio_queue_ordering(self):
        host = [True, False, True, False]
        q = HostPrioQueue(host)
        for op in (1, 0, 3, 2):        # mixed arrival order
            q.append(op)
        assert q.has_host()
        assert len(q) == 4
        # host reads (0, 2) drain first in FIFO order, then others (1, 3)
        assert [q.pop_next() for _ in range(4)] == [0, 2, 1, 3]
        q.append(1)
        q.resume_push(3)               # suspended op returns to the front
        assert not q.has_host()
        assert [q.pop_next(), q.pop_next()] == [3, 1]


class TestFCFSEquivalence:
    """The refactor contract: fcfs + prepass stays bit-identical."""

    @pytest.mark.parametrize("workload", ["websearch", "prxy"])
    @pytest.mark.parametrize("mechanism", ["baseline", "pr2ar2"])
    def test_fcfs_matches_reference_engine(self, workload, mechanism):
        """Explicit scheduler="fcfs" through the layered engine still
        reproduces the seed closure engine exactly (the parity cells of
        tests/test_flashsim_equiv.py)."""
        w = make_workloads()[workload]
        a = simulate(w, AGED, mechanism, seed=0, n_requests=400,
                     engine="array", scheduler="fcfs")
        r = simulate(w, AGED, mechanism, seed=0, n_requests=400,
                     engine="reference")
        assert _stats_tuple(a) == _stats_tuple(r)

    def test_explicit_knobs_match_defaults(self):
        w = make_workloads()["oltp"]
        base = simulate(w, AGED, "pr2ar2", seed=1, n_requests=300)
        knob = simulate(w, AGED, "pr2ar2", seed=1, n_requests=300,
                        scheduler="fcfs", gc="off")
        assert _stats_tuple(base) == _stats_tuple(knob)

    def test_prepass_gc_pinned_regression(self):
        """Bit-exact pins on churning GC cells, re-captured when simulated
        time moved onto the 2**-10 us tick grid: the layered fcfs engine
        must keep reproducing them."""
        w = dataclasses.replace(make_workloads()["rsrch"], n_requests=2500)
        s = simulate(w, AGED, "baseline", seed=0, cfg=GC_SSD)
        assert s.mean_us == 21191.361431640624
        assert s.p99_us == 201956.70213867162
        assert s.read_p99_us == 176763.65718749998
        assert s.mean_read_attempts == 13.865079365079366
        assert s.wa == 2.615843949044586
        assert (s.gc_invocations, s.blocks_erased) == (292, 292)

        w = dataclasses.replace(make_workloads()["prn"], n_requests=2500)
        s = simulate(w, AGED, "baseline", seed=0, cfg=GC_SSD)
        assert s.mean_us == 7650.070820703125
        assert s.read_p99_us == 150456.23523437456
        assert s.wa == 1.3831828442437923
        assert (s.gc_invocations, s.blocks_erased) == (102, 102)

    def test_host_prio_equals_fcfs_on_pure_read_trace(self):
        """With nothing but host reads every op is in the priority class,
        so host_prio degenerates to FIFO — bit-identical to fcfs."""
        w = Workload("allread", read_ratio=1.0, iops=14000, burstiness=2.0,
                     mean_pages=1.6, n_requests=400)
        a = simulate(w, AGED, "pr2ar2", seed=0, scheduler="fcfs")
        b = simulate(w, AGED, "pr2ar2", seed=0, scheduler="host_prio")
        assert _stats_tuple(a) == _stats_tuple(b)

    def test_reference_engine_rejects_schedulers(self):
        w = make_workloads()["websearch"]
        with pytest.raises(NotImplementedError, match="scheduler"):
            simulate(w, AGED, "baseline", seed=0, n_requests=100,
                     engine="reference", scheduler="host_prio")


class TestWorkConservation:
    """Engine-validated invariant: no idle die while its queue holds a
    runnable op — checked after every admission and event under all
    (scheduler x GC-mode) combinations."""

    @pytest.mark.parametrize("scheduler", SCHEDULERS)
    @pytest.mark.parametrize("gc", ["off", "prepass", "online"])
    def test_no_idle_die_with_ready_op(self, scheduler, gc):
        w = dataclasses.replace(make_workloads()["rsrch"], n_requests=800)
        trace = cached_trace(w, seed=1)
        cfg = _with_knobs(DEFAULT_SSD, scheduler, gc)
        sim = SSDSim(cfg, AGED, RetryPolicy("pr2ar2"), seed=9)
        stats = sim.run(trace, validate=True)   # raises on violation
        assert stats.n_requests == 800

    @pytest.mark.parametrize("scheduler", SCHEDULERS)
    def test_all_requests_complete(self, scheduler):
        w = dataclasses.replace(make_workloads()["prn"], n_requests=1000)
        trace = cached_trace(w, seed=0)
        cfg = _with_knobs(GC_SSD, scheduler, None)
        sim = SSDSim(cfg, AGED, RetryPolicy("baseline"), seed=7)
        sim.run(trace)
        assert (sim.last_req_done_us >= trace.arrival_us).all()


class TestHostPrioritization:
    def test_no_host_read_starvation_under_gc(self):
        """host_prio: every host read completes, and the worst read wait
        collapses relative to FCFS (reads no longer drain behind the
        whole GC backlog)."""
        w = dataclasses.replace(make_workloads()["rsrch"], n_requests=2500)
        trace = cached_trace(w, seed=0)
        out = {}
        for sched in ("fcfs", "host_prio"):
            cfg = _with_knobs(GC_SSD, sched, None)
            sim = SSDSim(cfg, AGED, RetryPolicy("baseline"), seed=7)
            stats = sim.run(trace)
            resp = sim.last_req_done_us - trace.arrival_us
            assert (sim.last_req_done_us >= trace.arrival_us).all()
            out[sched] = (stats, float(resp[trace.is_read].max()))
        fcfs_stats, fcfs_worst = out["fcfs"]
        prio_stats, prio_worst = out["host_prio"]
        assert prio_worst < fcfs_worst / 2
        assert prio_stats.read_p99_us < fcfs_stats.read_p99_us / 2
        # Work stays conserved: GC/write traffic still completes, so die
        # busy time is policy-invariant up to suspension-free reordering.
        assert prio_stats.wa == fcfs_stats.wa

    def test_host_writes_not_prioritized(self):
        """host_prio boosts reads only: on a write-heavy trace the overall
        mean (write-dominated) must not improve at the reads' expense
        beyond what contention relief explains — writes still queue FIFO
        behind GC."""
        w = dataclasses.replace(make_workloads()["prn"], n_requests=1500)
        fcfs = simulate(w, AGED, "baseline", seed=0, cfg=GC_SSD)
        prio = simulate(w, AGED, "baseline", seed=0, cfg=GC_SSD,
                        scheduler="host_prio")
        assert prio.read_p99_us < fcfs.read_p99_us
        # reads jumped ahead; writes absorbed the wait: the write-heavy
        # overall p99 may not collapse the way the read tail does
        assert prio.p99_us > prio.read_p99_us


def _micro_erase_vs_read():
    """One die, one channel: an erase at t=0 and a host read at t=100."""
    cfg = SSDConfig(n_channels=1, dies_per_channel=1)
    trace = RequestTrace(
        arrival_us=np.array([100.0]),
        is_read=np.array([True]),
        n_pages=np.array([1], np.int64),
        start_page=np.array([0], np.int64),
    )
    stats = FTLStats(
        host_reads=1, host_progs=0, prefill_progs=0, gc_page_reads=0,
        gc_page_progs=0, blocks_erased=1, gc_invocations=1,
        write_amplification=1.0, blocks_per_die=4, pages_per_block=16,
        footprint_pages=1, max_block_pe=1.0,
    )
    schedule = FTLSchedule(
        arrival_us=np.array([0.0, 100.0]),
        rid=np.array([-1, 0], np.int64),
        die=np.array([0, 0], np.int64),
        chan=np.array([0, 0], np.int64),
        ptype=np.array([0, 0], np.int64),
        kind=np.array([OP_ERASE, OP_READ], np.int64),
        dur_us=np.array([3000.0, 0.0]),
        wear_pec=np.array([0.0, 0.0]),
        n_requests=1,
        stats=stats,
    )
    return cfg, trace, schedule


class TestPreemption:
    def test_erase_suspend_resume_accounting(self):
        """A host read arriving mid-erase suspends it; elapsed + residual
        must sum to the original t_erase — total die busy time is exactly
        policy-invariant — while the read finishes far earlier."""
        cfg, trace, schedule = _micro_erase_vs_read()
        runs = {}
        for sched in ("fcfs", "preempt"):
            c = dataclasses.replace(cfg, scheduler=sched)
            sim = SSDSim(c, OperatingCondition(0.0, 0.0),
                         RetryPolicy("baseline"), seed=3)
            stats = sim.run(trace, schedule=schedule, validate=True)
            runs[sched] = (sim, stats)
        sim_f, st_f = runs["fcfs"]
        sim_p, st_p = runs["preempt"]
        # identical RNG stream -> identical attempt draw for the read
        assert st_f.mean_read_attempts == st_p.mean_read_attempts
        # suspend happened exactly once, and only under preempt
        assert sim_f.last_gc_suspensions == 0
        assert sim_p.last_gc_suspensions == 1
        assert st_p.gc_suspensions == 1
        # time accounting: elapsed-before-suspend + residual == t_erase,
        # so total die busy time matches fcfs exactly (work conserved)
        assert sim_p.last_die_busy_us == pytest.approx(
            sim_f.last_die_busy_us, rel=1e-12)
        # the read no longer waits out the 3 ms erase
        read_f = float(sim_f.last_req_done_us[0]) - 100.0
        read_p = float(sim_p.last_req_done_us[0]) - 100.0
        assert read_f > 2900.0
        assert read_p < 300.0

    def test_erase_resumes_after_double_suspension(self):
        """Two host reads staggered across the erase: each suspends the
        residual anew; accounting still sums exactly."""
        cfg, trace, schedule = _micro_erase_vs_read()
        trace = RequestTrace(
            arrival_us=np.array([100.0, 1500.0]),
            is_read=np.array([True, True]),
            n_pages=np.array([1, 1], np.int64),
            start_page=np.array([0, 1], np.int64),
        )
        schedule = dataclasses.replace(
            schedule,
            arrival_us=np.array([0.0, 100.0, 1500.0]),
            rid=np.array([-1, 0, 1], np.int64),
            die=np.array([0, 0, 0], np.int64),
            chan=np.array([0, 0, 0], np.int64),
            ptype=np.array([0, 0, 0], np.int64),
            kind=np.array([OP_ERASE, OP_READ, OP_READ], np.int64),
            dur_us=np.array([3000.0, 0.0, 0.0]),
            wear_pec=np.zeros(3),
            n_requests=2,
        )
        runs = {}
        for sched in ("fcfs", "preempt"):
            c = dataclasses.replace(cfg, scheduler=sched)
            sim = SSDSim(c, OperatingCondition(0.0, 0.0),
                         RetryPolicy("baseline"), seed=3)
            sim.run(trace, schedule=schedule, validate=True)
            runs[sched] = sim
        assert runs["preempt"].last_gc_suspensions == 2
        assert runs["preempt"].last_die_busy_us == pytest.approx(
            runs["fcfs"].last_die_busy_us, rel=1e-12)
        assert (runs["preempt"].last_req_done_us
                < runs["fcfs"].last_req_done_us).all()

    def test_gc_read_suspends_at_attempt_boundaries(self):
        """Aged-condition GC reads retry ~14x; under preempt a waiting
        host read cuts in at a boundary.  Macro check: suspensions occur
        and the read tail tightens beyond host_prio."""
        w = dataclasses.replace(make_workloads()["rsrch"], n_requests=2500)
        prio = simulate(w, AGED, "baseline", seed=0, cfg=GC_SSD,
                        scheduler="host_prio")
        pre = simulate(w, AGED, "baseline", seed=0, cfg=GC_SSD,
                       scheduler="preempt")
        assert pre.gc_suspensions > 0
        assert prio.gc_suspensions == 0
        assert pre.read_p99_us < prio.read_p99_us
        assert pre.wa == prio.wa    # prepass mapping is policy-invariant

    @pytest.mark.parametrize("mechanism", ["baseline", "pr2ar2"])
    def test_preempt_beats_fcfs_read_tail(self, mechanism):
        w = dataclasses.replace(make_workloads()["rsrch"], n_requests=2500)
        fcfs = simulate(w, AGED, mechanism, seed=0, cfg=GC_SSD)
        pre = simulate(w, AGED, mechanism, seed=0, cfg=GC_SSD,
                       scheduler="preempt")
        assert pre.read_p99_us < fcfs.read_p99_us / 2


class TestReadP99ExcludesGC:
    """Regression (satellite): SimStats.read_p99_us is computed over host
    requests only — GC page-ops (rid == -1) must never leak into host
    percentiles under any scheduler policy or GC mode, preemption
    included."""

    @pytest.mark.parametrize("scheduler", SCHEDULERS)
    @pytest.mark.parametrize("gc", ["prepass", "online"])
    def test_read_p99_over_host_requests_only(self, scheduler, gc):
        w = dataclasses.replace(make_workloads()["prn"], n_requests=1200)
        trace = cached_trace(w, seed=0)
        cfg = _with_knobs(DEFAULT_SSD, scheduler, gc)
        sim = SSDSim(cfg, AGED, RetryPolicy("pr2ar2"), seed=7)
        stats = sim.run(trace)
        # completion vector covers exactly the host requests
        assert sim.last_req_done_us.shape == (1200,)
        assert stats.n_requests == 1200
        # GC ops ran (rid == -1 traffic existed) ...
        assert stats.gc_page_reads > 0
        # ... and the reported read p99 recomputes from host reads alone
        resp = (sim.last_req_done_us - on_grid(trace.arrival_us)
                + cfg.host_overhead_us)
        expect = float(np.percentile(resp[trace.is_read], 99))
        assert stats.read_p99_us == expect
        assert stats.p99_us == float(np.percentile(resp, 99))


class TestAgedHostPrio:
    """Satellite: the starvation-bounded host-priority policy."""

    def test_queue_ages_low_class_after_bound(self):
        host = [i % 2 == 0 for i in range(12)]   # even ops are host reads
        q = AgedHostPrioQueue(host, age_bound=2)
        q.append(1)                              # lo (GC/program)
        for op in (0, 2, 4, 6):                  # hi backlog
            q.append(op)
        # two hi pops bypass the waiting lo op, then it ages to the front
        assert [q.pop_next() for _ in range(3)] == [0, 2, 1]
        # counter reset: hi resumes afterwards
        assert q.pop_next() == 4

    def test_queue_counter_resets_when_low_drains(self):
        host = [True, False, True, True, True]
        q = AgedHostPrioQueue(host, age_bound=2)
        q.append(1)
        q.append(0)
        q.append(2)
        assert q.pop_next() == 0     # bypass 1
        assert q.pop_next() == 2     # bypass 2
        assert q.pop_next() == 1     # aged (hi empty anyway)
        # fresh wait: the bound applies anew to the next lo arrival
        q.append(1)
        for op in (3, 4):
            q.append(op)
        assert [q.pop_next() for _ in range(3)] == [3, 4, 1]

    @staticmethod
    def _sustained_read_phase():
        """Single die: one 3 ms erase queued at t=0.5us behind a read,
        then a 100%-read phase (80 reads, one per 20us) that keeps the
        high-priority class non-empty for the whole window — the
        starvation scenario for plain host_prio."""
        cfg = SSDConfig(n_channels=1, dies_per_channel=1)
        n_reads = 80
        arr_reads = 20.0 * np.arange(n_reads)
        trace = RequestTrace(
            arrival_us=arr_reads,
            is_read=np.ones(n_reads, bool),
            n_pages=np.ones(n_reads, np.int64),
            start_page=np.arange(n_reads, dtype=np.int64),
        )
        stats = FTLStats(
            host_reads=n_reads, host_progs=0, prefill_progs=0,
            gc_page_reads=0, gc_page_progs=0, blocks_erased=1,
            gc_invocations=1, write_amplification=1.0, blocks_per_die=4,
            pages_per_block=16, footprint_pages=n_reads, max_block_pe=1.0,
        )
        arrival = np.concatenate(([arr_reads[0]], [0.5], arr_reads[1:]))
        rid = np.concatenate(([0], [-1], np.arange(1, n_reads))).astype(np.int64)
        kind = np.concatenate(([OP_READ], [OP_ERASE],
                               np.full(n_reads - 1, OP_READ))).astype(np.int64)
        dur = np.where(kind == OP_ERASE, 3000.0, 0.0)
        z = np.zeros(n_reads + 1, np.int64)
        schedule = FTLSchedule(
            arrival_us=arrival, rid=rid, die=z, chan=z, ptype=z, kind=kind,
            dur_us=dur, wear_pec=np.zeros(n_reads + 1), n_requests=n_reads,
            stats=stats,
        )
        return cfg, trace, schedule

    def test_no_starvation_under_sustained_reads(self):
        """Satellite acceptance: under a sustained 100%-read phase,
        plain host_prio starves the queued erase until the read phase
        drains; host_prio_aged:8 serves it after at most 8 bypassing
        reads — visible as a >= 2 ms erase-sized gap inside the first
        few read completions, with exact work conservation either way."""
        cfg, trace, schedule = self._sustained_read_phase()
        done = {}
        for sched in ("host_prio", "host_prio_aged:8"):
            c = dataclasses.replace(cfg, scheduler=sched)
            sim = SSDSim(c, OperatingCondition(0.0, 0.0),
                         RetryPolicy("baseline"), seed=3)
            sim.run(trace, schedule=schedule, validate=True)
            done[sched] = np.sort(sim.last_req_done_us)
        gaps_prio = np.diff(done["host_prio"])
        gaps_aged = np.diff(done["host_prio_aged:8"])
        # host_prio: no erase-sized hole between read completions — the
        # erase waited out the entire read phase (starved)
        assert gaps_prio.max() < 2000.0
        # aged: the erase ran inside the read phase, after <= bound + the
        # in-flight read; at most 9 reads complete before the 3 ms hole
        hole = int(np.argmax(gaps_aged >= 2000.0))
        assert gaps_aged[hole] >= 2000.0, "erase never aged into the phase"
        assert hole + 1 <= 9, f"{hole + 1} reads completed before the erase"
        # and the erase still completes in both runs: the last read of the
        # aged run finishes ~t_erase later than under host_prio
        assert done["host_prio_aged:8"][-1] > done["host_prio"][-1] + 2000.0


class TestTokenBudget:
    """Satellite: per-die read/write token-budget scheduler."""

    def test_budget_enforcement_under_full_backlog(self):
        """With both classes backlogged, a round serves exactly r reads
        then w writes, repeating — the configured bandwidth split."""
        host = [i < 8 for i in range(12)]        # ops 0-7 reads, 8-11 lo
        q = TokenBudgetQueue(host, r_budget=3, w_budget=2)
        for op in range(12):
            q.append(op)
        got = [q.pop_next() for _ in range(10)]
        #       round 1: 3 reads, 2 writes | round 2: 3 reads, 2 writes
        assert got == [0, 1, 2, 8, 9, 3, 4, 5, 10, 11]
        # low class drained: remaining reads flow FIFO
        assert [q.pop_next() for _ in range(2)] == [6, 7]
        assert not q

    def test_writes_never_exceed_budget_while_reads_wait(self):
        """A waiting read sees at most w consecutive low-priority
        dispatches (once the read class drains, the write tail is
        uncontended and flows freely)."""
        host = [i % 2 == 0 for i in range(40)]
        q = TokenBudgetQueue(host, r_budget=2, w_budget=1)
        for op in range(40):
            q.append(op)
        run_lo = worst = 0
        while q:
            contended = bool(q.hi)
            if host[q.pop_next()]:
                run_lo = 0
            elif contended:
                run_lo += 1
                worst = max(worst, run_lo)
        assert worst == 1

    def test_uncontended_classes_reset_the_round(self):
        """Budgets meter contention only: an empty low class serves
        reads immediately and restarts the round."""
        host = [True, True, True, True, False]
        q = TokenBudgetQueue(host, r_budget=2, w_budget=1)
        q.append(0)
        q.append(1)
        assert [q.pop_next(), q.pop_next()] == [0, 1]   # uncontended
        q.append(2)
        q.append(3)
        q.append(4)                                     # lo arrives
        # fresh round: 2 reads, then the write
        assert [q.pop_next() for _ in range(3)] == [2, 3, 4]

    def test_queue_rejects_bad_budgets(self):
        with pytest.raises(ValueError, match=">= 1"):
            TokenBudgetQueue([True], r_budget=0, w_budget=1)

    def test_default_budgets(self):
        q = TokenBudgetQueue([True])
        assert (q.r_budget, q.w_budget) == DEFAULT_TOKEN_BUDGETS

    def test_pure_read_trace_equals_fcfs(self):
        """All ops in the read class: tokens degenerates to FIFO —
        bit-identical to fcfs (mirrors the host_prio parity test)."""
        w = Workload("allread", read_ratio=1.0, iops=14000, burstiness=2.0,
                     mean_pages=1.6, n_requests=400)
        a = simulate(w, AGED, "pr2ar2", seed=0, scheduler="fcfs")
        b = simulate(w, AGED, "pr2ar2", seed=0, scheduler="tokens:4,1")
        assert _stats_tuple(a) == _stats_tuple(b)

    def test_work_conserved_and_wa_invariant_under_gc(self):
        """Engine-validated work conservation (every step) plus the
        prepass-mapping invariant: WA must not depend on the policy."""
        w = dataclasses.replace(make_workloads()["rsrch"], n_requests=1200)
        trace = cached_trace(w, seed=0)
        fcfs = SSDSim(GC_SSD, AGED, RetryPolicy("baseline"), seed=7)
        f_stats = fcfs.run(trace)
        cfg = _with_knobs(GC_SSD, "tokens:6,2", None)
        tok = SSDSim(cfg, AGED, RetryPolicy("baseline"), seed=7)
        t_stats = tok.run(trace, validate=True)    # raises on violation
        assert t_stats.wa == f_stats.wa
        assert (t_stats.gc_invocations, t_stats.blocks_erased) == \
            (f_stats.gc_invocations, f_stats.blocks_erased)
        assert (tok.last_req_done_us >= trace.arrival_us).all()

    def test_reads_jump_gc_backlog_but_writes_keep_slots(self):
        """Against fcfs, the read tail collapses (reads bypass the GC
        burst); against host_prio, GC/write work is serviced no later —
        the budget guarantees low-priority slots during read phases."""
        w = dataclasses.replace(make_workloads()["rsrch"], n_requests=2500)
        fcfs = simulate(w, AGED, "baseline", seed=0, cfg=GC_SSD)
        tok = simulate(w, AGED, "baseline", seed=0, cfg=GC_SSD,
                       scheduler="tokens:8,1")
        assert tok.read_p99_us < fcfs.read_p99_us / 2
        assert tok.wa == fcfs.wa

    def test_no_starvation_under_sustained_reads(self):
        """The erase-vs-read-phase scenario that starves plain host_prio
        (see TestAgedHostPrio above): with tokens:4,1 the erase gets its
        slot within one round — at most 4 reads complete first."""
        cfg, trace, schedule = TestAgedHostPrio._sustained_read_phase()
        done = {}
        for sched in ("host_prio", "tokens:4,1"):
            c = dataclasses.replace(cfg, scheduler=sched)
            sim = SSDSim(c, OperatingCondition(0.0, 0.0),
                         RetryPolicy("baseline"), seed=3)
            sim.run(trace, schedule=schedule, validate=True)
            done[sched] = np.sort(sim.last_req_done_us)
        gaps = np.diff(done["tokens:4,1"])
        hole = int(np.argmax(gaps >= 2000.0))
        assert gaps[hole] >= 2000.0, "erase never ran inside the phase"
        assert hole + 1 <= 5, f"{hole + 1} reads completed before the erase"
        # host_prio starves it until the phase drains (regression anchor)
        assert np.diff(done["host_prio"]).max() < 2000.0
