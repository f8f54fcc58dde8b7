"""Simulator engine microbenchmark — emits ``BENCH_sim.json``.

Times the array event-core (repro.flashsim.ssd.SSDSim) against the retired
seed engine (repro.flashsim.engine_ref.SSDSimRef) on the exact cell grid
of ``benchmarks/e2e_response_time``:

  * 6 workloads @ aged (1y retention / 1K P/E) x 6 mechanisms, and
  * read-dominant workloads @ 3 modest conditions x {sota, sota+pr2ar2},

with every characterization table warmed first, so the recorded numbers
isolate the DES hot path.  The seed path is measured faithfully to the
original ``compare_mechanisms``: the trace is regenerated per mechanism
and attempt counts are sampled per request inside the engine; the array
path shares one trace + expansion per cell and samples attempts in one
batched pass.

``BENCH_sim.json`` records per-cell wall times, event counts, events/sec,
and the aggregate speedup — the perf trajectory of the simulator is
tracked through this file from PR 1 onward.  Because those absolute
numbers are machine-dependent, every file also records (PR 5):

  * a **host fingerprint** (CPU model, core count, python/numpy
    versions) — a BENCH_sim.json measured on a different machine class
    is visibly a different machine, not a regression;
  * a **pinned reference cell** re-measured in the same run: the first
    e2e cell's events/sec divided into every other cell
    (``rel_throughput``) cancels the machine entirely, and
    ``host_factor`` (measured / pinned reference throughput at the
    acceptance size) quantifies how the current host compares to the
    machine class that set the in-repo pin.  Cross-machine comparisons
    should use ``rel_throughput`` and ``host_factor``-normalized
    numbers, never raw wall times.

Seven sweeps ride along:

  * **claim cells** (PR 3): the paper's headline reductions (PR²+AR² vs
    baseline @ aged; SOTA+PR²+AR² vs SOTA @ modest) re-measured as
    mean ± 95% CI over ``--seeds`` independent traces, with the paper
    check as a CI-overlap test instead of a point comparison;
  * **GC cells** (PR 2, multi-seed since PR 3): each write-heavy profile
    with the page-mapping FTL off and on — write amplification and the
    host-read p99 inflation GC contention causes, mean ± 95% CI;
  * **scheduler cells** (PR 3): the GC profiles under online GC across
    the die-queue policies (fcfs / host_prio / preempt) — the
    host-read-priority acceptance: host_prio and preempt must cut the
    fcfs read-p99 inflation by >= 2x at equal (±10%) WA;
  * **workload (real-trace replay) cells** (PR 4): the checked-in
    MSR-format excerpts (tests/data) replayed end-to-end through the
    ingestion -> dense-remap -> FTL-auto-sizing path, baseline vs
    PR²/AR² with prepass GC.  Seed variation comes from an 0.85
    Bernoulli subsample per seed (deterministic files have no seed of
    their own), reported as mean ± 95% CI; the acceptance is that every
    mechanism produces finite stats and the FTL engages (WA > 1);
  * **fault cells** (PR 6): read-dominant profiles @ aged under the
    seeded fault model (:mod:`repro.flashsim.faults`) across a
    ``mispredict_scale`` ladder — the AR² misprediction-rate vs
    latency-win tradeoff (mean ± 95% CI over seeds) plus the
    recovery-latency p99.  The acceptance: mispredictions actually fire
    at the derived rate, the win erodes (never inverts) as the rate
    grows, and nothing is unrecoverable at the paper-default ECC margin;
  * **shard-scaling cells** (PR 8, extended PR 9): the batched lockstep
    core (``engine="batched"``) vs the array interpreter, wall vs
    channel count {1, 2, 4, 8} on the websearch reference cell —
    per-cell bit-parity (full SimStats equality per seed) and
    fast-path-activated flags, best-of-3 walls as mean ± 95% CI over
    seeds, throughput normalized to this run's 8-channel array cell.
    The acceptance rides on the 8-channel cell: batched events/sec
    >= 1.5x the interpreter.  Since PR 9 the block also carries
    ``scheduler_cells_8ch`` (the 8-channel cell under the dual priority
    rings — host_prio / host_prio_aged — acceptance: batched >= 1.3x
    under host_prio) and ``small_cell_sweep`` (an n=500 grid through
    ``run_cells`` at ``engine="array"`` vs ``engine="auto"``: auto must
    select batched everywhere and the batched sweep wall must not lose
    — the dispatch-overhead gate);
  * **fused sweep cells** (PR 10): the cross-cell fused dispatch path
    vs the sequential batched engine vs the array interpreter on two
    (mechanism x condition x seed) grids through ``run_cells`` — the
    n=500 small-cell grid where fixed dispatch cost dominates (the
    acceptance: fused >= 1.5x the sequential batched sweep wall with
    full per-cell bit parity against both other variants) and an
    n=8000 claim grid where the lockstep loop dominates (recorded, not
    gated).  Walls are interleaved rounds with the collector parked
    (mean ± 95% CI + best); kernel-launch accounting
    (``fused_dispatches`` vs ``sequential_dispatches``) pins that the
    speedup is amortized dispatch overhead, not changed math.

The claim/GC/scheduler/trace sweeps all execute through the parallel
sweep runtime (:mod:`repro.flashsim.runtime`); ``--workers N`` fans
their cells across a process pool.  With ``N > 1`` the paper-claim grid
is additionally re-run at ``workers=1`` and the file records the
measured ``speedup`` plus a ``cells_equal`` flag (per-cell results must
be identical for every worker count — the CI bench-smoke lane asserts
byte-equality of the deterministic payload between a workers=1 and a
workers=2 run via ``benchmarks/bench_compare.py``).  On a single-core
host (fingerprint ``cpu_count < 2``) the parallel block is gated: it
records ``skipped`` + ``skipped_reason`` instead of a speedup that
could only measure process overhead.

Usage: PYTHONPATH=src python -m benchmarks.microbench_sim [--n 8000]
           [--seeds 5] [--quick] [--workers 4] [--skip-reference]
           [--skip-gc] [--skip-traces] [--out BENCH_sim.json]

  --n N             requests per cell (default 8000, the acceptance size)
  --seeds K         seeds per claim/GC/scheduler/workload cell (default 5)
  --quick           tiny grid (CI smoke; n defaults to 1200, 2 seeds)
  --workers N       process-pool workers for the sweep cells (default 4;
                    1 in --quick); N > 1 also records the parallel-sweep
                    speedup block
  --skip-reference  only measure the array engine (no speedup column)
  --skip-gc         skip the FTL/GC + scheduler sweep cells
  --skip-traces     skip the real-trace replay cells
  --out PATH        output JSON path (default BENCH_sim.json in cwd)
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import time

import numpy as np

from repro.core.retry import RetryPolicy
from repro.flashsim.config import (DEFAULT_SSD, FaultConfig, GCConfig,
                                   HostCacheConfig, SSDConfig)
from repro.flashsim.engine_ref import SSDSimRef
from repro.flashsim.runtime import Cell, host_fingerprint, run_cells
from repro.flashsim.ssd import (
    SSDSim,
    expand_trace,
    simulate_batch,
)
from repro.flashsim.workloads import (
    GC_PROFILES,
    PROFILES,
    Subsample,
    cached_trace,
    generate_trace,
    get_source,
    trace_stats,
)

from benchmarks.e2e_response_time import (
    AGED,
    MODEST,
    PAPER_AVG_VS_BASELINE,
    PAPER_AVG_VS_SOTA,
    PAPER_MAX_VS_BASELINE,
    PAPER_MAX_VS_SOTA,
    TOL,
)

ALL_MECHS = ("baseline", "sota", "pr2", "ar2", "pr2ar2", "sota+pr2ar2")
SCHED_POLICIES = ("fcfs", "host_prio", "preempt")

#: The pinned reference cell: the FIRST e2e cell (websearch @ aged x all
#: six mechanisms) at the acceptance size REFERENCE_N, re-measured in
#: every run.  REFERENCE_EVENTS_PER_SEC is its array-engine throughput
#: on the machine class that set the pin (PR 5); host_factor =
#: measured / pinned tells every later reader how fast the current host
#: is relative to that class, and per-cell ``rel_throughput`` (cell
#: ev/s / reference-cell ev/s, same run) is machine-independent.
REFERENCE_N = 8000
REFERENCE_EVENTS_PER_SEC = 395_000

#: Requests per GC cell in --quick mode.  GC intensity is non-monotonic
#: in trace length (capacity auto-sizes with the footprint, which grows
#: with n); 2500 sits past the near-dead zone around ~2k requests, where
#: both write-heavy presets reliably churn (prn: ~100 invocations,
#: rsrch: ~300 at seed 0).
GC_QUICK_N = 2500

#: Two-sided 95% t critical values by degrees of freedom (n_seeds - 1).
_T95 = {1: 12.706, 2: 4.303, 3: 3.182, 4: 2.776, 5: 2.571, 6: 2.447,
        7: 2.365, 8: 2.306, 9: 2.262, 10: 2.228, 11: 2.201, 12: 2.179,
        13: 2.160, 14: 2.145, 15: 2.131, 16: 2.120, 17: 2.110, 18: 2.101,
        19: 2.093, 20: 2.086}


def mean_ci95(xs):
    """(mean, 95% CI half-width) of a small sample (t-distribution).

    One seed yields a degenerate (mean, 0.0) — the claim check then
    reduces to a point comparison.  Beyond 21 seeds the critical value
    is approximated by the dof=30 entry (2.042, within 1% of the true
    value for any larger sample; never the understating z=1.96).
    """
    xs = np.asarray(list(xs), dtype=float)
    n = xs.size
    m = float(xs.mean())
    if n < 2:
        return m, 0.0
    t = _T95.get(n - 1, 2.042)
    return m, t * float(xs.std(ddof=1)) / math.sqrt(n)


def ci_overlaps(mean, half, target, tol):
    """CI-overlap test: [mean±half] intersects [target±tol]."""
    return mean - half <= target + tol and target - tol <= mean + half


# -- engine timing cells (single-seed; the PR 1 speedup trajectory) -------


def e2e_cells(quick: bool = False):
    """The (workload, condition, mechanisms) grid of the e2e benchmark."""
    cells = []
    profiles = PROFILES[:2] if quick else PROFILES
    for w in profiles:
        cells.append((w, AGED, ALL_MECHS))
    modest = MODEST[:1] if quick else MODEST
    for cond in modest:
        for w in (w for w in profiles if w.read_dominant):
            cells.append((w, cond, ("sota", "sota+pr2ar2")))
    return cells


def warm_characterization(cells):
    """Build every (condition, mechanism) attempt table before timing."""
    t0 = time.perf_counter()
    for _, cond, mechs in cells:
        for m in mechs:
            SSDSim(condition=cond, policy=RetryPolicy(m))
    return time.perf_counter() - t0


def bench_cell(w, cond, mechs, n_requests, seed, skip_reference):
    w = dataclasses.replace(w, n_requests=n_requests)

    # Array path: one trace + one expansion shared by all mechanisms.
    t0 = time.perf_counter()
    trace = cached_trace(w, seed=seed)
    expansion = expand_trace(trace)
    events_array = 0
    stats_array = {}
    for m in mechs:
        sim = SSDSim(condition=cond, policy=RetryPolicy(m), seed=seed + 7)
        stats_array[m] = sim.run(trace, expansion=expansion)
        events_array += sim.events_processed
    wall_array = time.perf_counter() - t0

    row = {
        "workload": w.name,
        "condition": cond.label(),
        "mechanisms": list(mechs),
        "n_requests": n_requests,
        "wall_array_s": round(wall_array, 4),
        "events_array": events_array,
        "events_per_sec_array": round(events_array / wall_array),
    }

    if not skip_reference:
        # Seed path, faithful to the original compare_mechanisms: trace
        # regenerated per mechanism, per-request sampling in the engine.
        t0 = time.perf_counter()
        events_ref = 0
        stats_ref = {}
        for m in mechs:
            trace_m = generate_trace(w, seed=seed)
            ref = SSDSimRef(condition=cond, policy=RetryPolicy(m),
                            seed=seed + 7)
            stats_ref[m] = ref.run(trace_m)
            events_ref += ref.events_processed
        wall_ref = time.perf_counter() - t0
        row["wall_seed_s"] = round(wall_ref, 4)
        row["events_seed"] = events_ref
        row["speedup"] = round(wall_ref / wall_array, 2)
        # Cross-engine sanity: identical attempt statistics per mechanism.
        row["attempts_match"] = all(
            abs(stats_array[m].mean_read_attempts
                - stats_ref[m].mean_read_attempts) < 1e-9
            for m in mechs
        )
    return row


# -- paper-claim cells: mean ± 95% CI over seeds --------------------------


def bench_claim_cells(n_requests, seeds, quick=False, workers=1):
    """Re-measure the paper's headline reductions across >= 2 seeds.

    Per seed: the PR²+AR²-vs-baseline reduction averaged over the six
    profiles @ aged, and the SOTA+PR²+AR²-vs-SOTA reduction averaged
    over read-dominant profiles @ modest conditions.  The claim check is
    a CI-overlap test against the paper figure ± the historical point
    tolerance.
    """
    profiles = PROFILES[:3] if quick else PROFILES
    modest = MODEST[:1] if quick else MODEST
    per_workload = []
    red_base = {s: [] for s in seeds}   # seed -> per-workload reductions
    red_sota = {s: [] for s in seeds}
    for w in profiles:
        grid = simulate_batch(
            w, (AGED,), mechanisms=("baseline", "pr2ar2"),
            seeds=seeds, n_requests=n_requests, workers=workers,
        )
        rs = [
            1.0 - grid[("pr2ar2", AGED, s)].mean_us
            / grid[("baseline", AGED, s)].mean_us
            for s in seeds
        ]
        for s, r in zip(seeds, rs):
            red_base[s].append(r)
        m, h = mean_ci95(rs)
        per_workload.append({
            "workload": w.name, "condition": AGED.label(),
            "metric": "pr2ar2_vs_baseline",
            "mean_reduction": round(m, 4), "ci95": round(h, 4),
            "n_seeds": len(seeds),
        })
    for w in (w for w in profiles if w.read_dominant):
        grid = simulate_batch(
            w, modest, mechanisms=("sota", "sota+pr2ar2"),
            seeds=seeds, n_requests=n_requests, workers=workers,
        )
        for cond in modest:
            rs = [
                1.0 - grid[("sota+pr2ar2", cond, s)].mean_us
                / grid[("sota", cond, s)].mean_us
                for s in seeds
            ]
            for s, r in zip(seeds, rs):
                red_sota[s].append(r)
            m, h = mean_ci95(rs)
            per_workload.append({
                "workload": w.name, "condition": cond.label(),
                "metric": "sota+pr2ar2_vs_sota",
                "mean_reduction": round(m, 4), "ci95": round(h, 4),
                "n_seeds": len(seeds),
            })

    # Per-seed grid averages -> CI over seeds (seed = independent trace).
    avg_b = [float(np.mean(red_base[s])) for s in seeds]
    max_b = [float(np.max(red_base[s])) for s in seeds]
    avg_s = [float(np.mean(red_sota[s])) for s in seeds]
    max_s = [float(np.max(red_sota[s])) for s in seeds]
    mb, hb = mean_ci95(avg_b)
    mxb, hxb = mean_ci95(max_b)
    ms, hs = mean_ci95(avg_s)
    mxs, hxs = mean_ci95(max_s)
    summary = {
        "n_seeds": len(seeds),
        "avg_vs_baseline": {"mean": round(mb, 4), "ci95": round(hb, 4),
                            "paper": PAPER_AVG_VS_BASELINE},
        "max_vs_baseline": {"mean": round(mxb, 4), "ci95": round(hxb, 4),
                            "paper": PAPER_MAX_VS_BASELINE},
        "avg_vs_sota": {"mean": round(ms, 4), "ci95": round(hs, 4),
                        "paper": PAPER_AVG_VS_SOTA},
        "max_vs_sota": {"mean": round(mxs, 4), "ci95": round(hxs, 4),
                        "paper": PAPER_MAX_VS_SOTA},
        "claim_ci_overlap_ok": bool(
            ci_overlaps(mb, hb, PAPER_AVG_VS_BASELINE, TOL)
            and ci_overlaps(mxb, hxb, PAPER_MAX_VS_BASELINE, TOL + 0.04)
            and ci_overlaps(ms, hs, PAPER_AVG_VS_SOTA, TOL)
            and ci_overlaps(mxs, hxs, PAPER_MAX_VS_SOTA, TOL + 0.04)
        ),
    }
    return per_workload, summary


# -- GC cells: FTL off/on, mean ± CI over seeds ---------------------------


def bench_gc_cell(w, cond, n_requests, seeds, workers=1):
    """FTL off vs on for one write-heavy profile: WA + read-tail impact,
    mean ± 95% CI over seeds.

    Runs baseline and pr2ar2 under both configurations so the row also
    records how much of the GC-induced read tail the paper's combined
    mechanism claws back.  The (mechanism x seed x FTL-on/off) runs are
    independent cells scheduled through the sweep runtime (``workers``).
    """
    w = dataclasses.replace(w, n_requests=n_requests)
    cfg_gc = SSDConfig(gc=GCConfig(enabled=True))
    row = {
        "workload": w.name,
        "condition": cond.label(),
        "n_requests": n_requests,
        "span_pages": w.span_pages,
        "n_seeds": len(seeds),
    }
    mechs = ("baseline", "pr2ar2")
    cells = [
        Cell("simulate", w, (cond,), (mech,), s, cfg)
        for mech in mechs
        for s in seeds
        for cfg in (DEFAULT_SSD, cfg_gc)
    ]
    t0 = time.perf_counter()
    results = iter(run_cells(cells, workers=workers))
    row["wall_s"] = None    # filled after the drain below
    wa_list, gc_inv = [], []
    for mech in mechs:
        p99_off, p99_on, infl, mean_on = [], [], [], []
        for s in seeds:
            off = next(results)
            on = next(results)
            p99_off.append(off.read_p99_us)
            p99_on.append(on.read_p99_us)
            infl.append(on.read_p99_us / off.read_p99_us)
            mean_on.append(on.mean_us)
            if mech == "baseline":
                wa_list.append(on.wa)
                gc_inv.append(on.gc_invocations)
        mi, hi_ = mean_ci95(infl)
        row[mech] = {
            "read_p99_off_us": round(float(np.mean(p99_off)), 1),
            "read_p99_on_us": round(float(np.mean(p99_on)), 1),
            "read_p99_inflation_mean": round(mi, 2),
            "read_p99_inflation_ci95": round(hi_, 2),
            "mean_on_us": round(float(np.mean(mean_on)), 1),
        }
    row["wall_s"] = round(time.perf_counter() - t0, 3)
    wm, wh = mean_ci95(wa_list)
    row.update(
        wa_mean=round(wm, 3), wa_ci95=round(wh, 3),
        gc_invocations_mean=round(float(np.mean(gc_inv)), 1),
    )
    # The acceptance properties of the FTL subsystem (per-seed, all seeds):
    row["ok_wa_gt_1"] = bool(min(wa_list) > 1.0)
    row["ok_read_p99_higher"] = bool(
        min(row[m]["read_p99_inflation_mean"] for m in ("baseline", "pr2ar2"))
        > 1.0
    )
    return row


# -- scheduler cells: online GC x die-queue policy ------------------------


def bench_sched_cell(w, cond, n_requests, seeds, mech="baseline",
                     workers=1):
    """Online GC under fcfs / host_prio / preempt for one GC profile.

    Inflation is host-read p99 with GC on over GC off (same seed, same
    scheduler-independent off-run).  The acceptance: host_prio and
    preempt cut fcfs inflation >= 2x at equal (±10%) WA.  The off-runs
    and every (policy x seed) on-run are independent cells scheduled
    through the sweep runtime (``workers``).
    """
    w = dataclasses.replace(w, n_requests=n_requests)
    row = {
        "workload": w.name,
        "condition": cond.label(),
        "mechanism": mech,
        "n_requests": n_requests,
        "n_seeds": len(seeds),
        "gc_mode": "online",
    }
    cells = [Cell("simulate", w, (cond,), (mech,), s) for s in seeds]
    cells += [
        Cell("simulate", w, (cond,), (mech,), s, scheduler=sched,
             gc="online")
        for sched in SCHED_POLICIES
        for s in seeds
    ]
    t0 = time.perf_counter()
    results = run_cells(cells, workers=workers)
    wall = time.perf_counter() - t0
    off_p99 = {s: st.read_p99_us for s, st in zip(seeds, results)}
    on_runs = iter(results[len(seeds):])
    row["wall_s"] = round(wall, 3)
    wa_by_policy = {}
    for sched in SCHED_POLICIES:
        infl, wa, stalls, susp = [], [], [], []
        for s in seeds:
            on = next(on_runs)
            infl.append(on.read_p99_us / off_p99[s])
            wa.append(on.wa)
            stalls.append(on.write_stalls)
            susp.append(on.gc_suspensions)
        mi, hi_ = mean_ci95(infl)
        wam, wah = mean_ci95(wa)
        wa_by_policy[sched] = wam
        row[sched] = {
            "read_p99_inflation_mean": round(mi, 2),
            "read_p99_inflation_ci95": round(hi_, 2),
            "wa_mean": round(wam, 3),
            "wa_ci95": round(wah, 3),
            "write_stalls_mean": round(float(np.mean(stalls)), 1),
            "gc_suspensions_mean": round(float(np.mean(susp)), 1),
        }
    f = row["fcfs"]["read_p99_inflation_mean"]
    row["inflation_cut_host_prio"] = round(
        f / row["host_prio"]["read_p99_inflation_mean"], 2)
    row["inflation_cut_preempt"] = round(
        f / row["preempt"]["read_p99_inflation_mean"], 2)
    row["ok_wa_equal"] = bool(
        max(wa_by_policy.values()) <= min(wa_by_policy.values()) * 1.10
    )
    row["ok_p99_cut_2x"] = bool(
        row["inflation_cut_host_prio"] >= 2.0
        and row["inflation_cut_preempt"] >= 2.0
    )
    return row


# -- workload cells: real-trace replay through ingestion + FTL ------------

#: Checked-in MSR-format excerpts (tests/data/) replayed per PR.  The
#: registry resolves them via the search path (cwd/tests/data when run
#: from the repo root); dense footprint remap is the file-scheme default,
#: which is what FTL auto-OP sizing needs for sparse real address spaces.
TRACE_SPECS = ("msr:web_0", "msr:src1_1")
TRACE_MECHS = ("baseline", "pr2", "ar2", "pr2ar2")

#: Per-seed Bernoulli keep probability: the seed axis for deterministic
#: file traces (each seed replays an independent 85% subsample).
TRACE_SAMPLE = 0.85


def bench_trace_cell(spec, cond, seeds, workers=1):
    """Replay one checked-in excerpt end-to-end: compare_mechanisms with
    prepass GC (FTL auto-sized from the remapped dense footprint),
    baseline vs PR²/AR², mean ± 95% CI over subsample seeds.  One
    compare cell per seed, scheduled through the sweep runtime."""
    src = get_source(spec)
    src_stats = trace_stats(src.trace(0))
    # Composable form (not string concatenation) so parameterized specs
    # in TRACE_SPECS keep working; the chain is identical to ?sample=.
    sub = src.with_transforms(Subsample(TRACE_SAMPLE))
    row = {
        "workload": spec,
        "condition": cond.label(),
        "mechanisms": list(TRACE_MECHS),
        "gc_mode": "prepass",
        "n_seeds": len(seeds),
        "sample": TRACE_SAMPLE,
        "source": {
            "n_requests": src_stats.n_requests,
            # iops is inf for a degenerate zero-time-span excerpt
            "iops": round(src_stats.iops) if math.isfinite(src_stats.iops)
            else None,
            "read_ratio": round(src_stats.read_ratio, 3),
            "mean_pages": round(src_stats.mean_pages, 2),
            "footprint_pages": src_stats.footprint_pages,
            "burstiness": round(src_stats.mmpp_burstiness, 2),
        },
    }
    per_mech = {m: {"mean_us": [], "read_p99_us": []} for m in TRACE_MECHS}
    wa_list, finite = [], True
    cells = [
        Cell("compare", sub, (cond,), TRACE_MECHS, s, gc="prepass")
        for s in seeds
    ]
    t0 = time.perf_counter()
    grids = run_cells(cells, workers=workers)
    wall = time.perf_counter() - t0
    for grid in grids:
        for m, st in grid.items():
            for f in ("mean_us", "p50_us", "p99_us", "read_p99_us", "wa"):
                if not np.isfinite(float(getattr(st, f))):
                    finite = False
            per_mech[m]["mean_us"].append(st.mean_us)
            per_mech[m]["read_p99_us"].append(st.read_p99_us)
        wa_list.append(grid["baseline"].wa)
    row["wall_s"] = round(wall, 3)
    for m in TRACE_MECHS:
        mm, mh = mean_ci95(per_mech[m]["mean_us"])
        pm, _ = mean_ci95(per_mech[m]["read_p99_us"])
        row[m] = {
            "mean_us": round(mm, 1), "mean_us_ci95": round(mh, 1),
            "read_p99_us": round(pm, 1),
        }
    reds = [
        1.0 - a / b
        for a, b in zip(per_mech["pr2ar2"]["mean_us"],
                        per_mech["baseline"]["mean_us"])
    ]
    rm, rh = mean_ci95(reds)
    wam, wah = mean_ci95(wa_list)
    row.update(
        pr2ar2_reduction_mean=round(rm, 4),
        pr2ar2_reduction_ci95=round(rh, 4),
        wa_mean=round(wam, 3), wa_ci95=round(wah, 3),
    )
    row["ok_finite"] = bool(finite)
    row["ok_wa_gt_1"] = bool(min(wa_list) > 1.0)
    return row


# -- fault cells: AR² misprediction rate vs latency win -------------------

#: Multipliers on the derived AR² misprediction probability.  0.0 is the
#: no-misprediction upper bound on the AR² win; the derived rate (1.0)
#: is the paper-realistic point; 4.0 stresses the tradeoff.
FAULT_MISPREDICT_SCALES = (0.0, 1.0, 4.0)


def bench_fault_cell(w, cond, n_requests, seeds, workers=1):
    """AR² misprediction-rate vs latency-win tradeoff, mean ± 95% CI.

    For each ``mispredict_scale`` the paper's combined mechanism
    (pr2ar2) runs against baseline under the seeded fault model: every
    misprediction costs one extra nominal-tR re-read on the die, so
    rising scales erode the reduced-tR latency win.  Uncorrectable
    reads stay on the *derived* ECC probability — the acceptance being
    that nothing is lost at the paper-default margin
    (``unrecoverable == 0``).  ``recovery_p99_us`` is the p99 response
    over recovery-affected requests.  One compare cell per
    (scale, seed), scheduled through the sweep runtime (``workers``).
    """
    w = dataclasses.replace(w, n_requests=n_requests)
    mechs = ("baseline", "pr2ar2")
    row = {
        "workload": w.name,
        "condition": cond.label(),
        "mechanisms": list(mechs),
        "n_requests": n_requests,
        "n_seeds": len(seeds),
        "mispredict_scales": list(FAULT_MISPREDICT_SCALES),
    }
    cells = [
        Cell("compare", w, (cond,), mechs, s,
             faults=FaultConfig(mispredict_scale=scale))
        for scale in FAULT_MISPREDICT_SCALES
        for s in seeds
    ]
    t0 = time.perf_counter()
    results = iter(run_cells(cells, workers=workers))
    unrecoverable_total = 0
    win_by_scale = {}
    for scale in FAULT_MISPREDICT_SCALES:
        rate, win, rec_p99, mis = [], [], [], []
        for s in seeds:
            grid = next(results)
            st, base = grid["pr2ar2"], grid["baseline"]
            rate.append(st.mispredicted_reads / st.n_requests)
            win.append(1.0 - st.mean_us / base.mean_us)
            rec_p99.append(st.recovery_p99_us)
            mis.append(st.mispredicted_reads)
            unrecoverable_total += st.unrecoverable + base.unrecoverable
        rm, rh = mean_ci95(rate)
        wm, wh = mean_ci95(win)
        win_by_scale[scale] = wm
        row[f"scale_{scale:g}"] = {
            "mispredict_rate_mean": round(rm, 5),
            "mispredict_rate_ci95": round(rh, 5),
            "mispredicted_reads_mean": round(float(np.mean(mis)), 1),
            "latency_win_mean": round(wm, 4),
            "latency_win_ci95": round(wh, 4),
            "recovery_p99_us_mean": round(float(np.mean(rec_p99)), 1),
        }
    row["wall_s"] = round(time.perf_counter() - t0, 3)
    row["unrecoverable_total"] = unrecoverable_total
    row["ok_unrecoverable_zero"] = bool(unrecoverable_total == 0)
    row["ok_mispredicted_fired"] = bool(
        row["scale_1"]["mispredicted_reads_mean"] > 0
    )
    row["ok_win_erodes"] = bool(
        win_by_scale[FAULT_MISPREDICT_SCALES[0]]
        >= win_by_scale[FAULT_MISPREDICT_SCALES[-1]]
    )
    return row


# -- closed-loop cells: throughput-vs-QD ladder ---------------------------

#: NCQ depths of the saturation ladder (powers of two through the knee).
CLOSED_QD_LADDER = (1, 2, 4, 8, 16, 32, 64, 128, 256)
CLOSED_QD_LADDER_QUICK = (1, 4, 16, 64, 256)
#: Fixed depth for the PR² overlap-win and host-cache rungs: past the
#: linear region, before open-loop convergence.
CLOSED_WIN_QD = 8


def bench_closed_loop_cell(w, cond, n_requests, seeds, quick=False,
                           workers=1):
    """Closed-loop frontend: throughput-vs-QD ladder, mean ± 95% CI.

    Every rung replays one GC write-cliff profile through the NCQ-gated
    frontend (``gc="prepass"``) for baseline and pr2ar2; an open-loop
    compare cell per seed anchors the QD-bounded-p99 check and a
    write-back-cache rung at ``CLOSED_WIN_QD`` records the absorption
    counters.  Acceptance flags:

    * ``ok_throughput_monotone`` — mean pr2ar2 throughput never drops as
      the queue deepens (and the ladder shows a knee: the top rung no
      longer scales linearly);
    * ``ok_qd_bounded_p99`` — the device-side read p99 at every bounded
      rung (QD <= 16) stays at or below the open-loop read p99 (admission
      control bounds device queueing on the GC write cliff);
    * ``ok_pr2_overlap_win`` — at ``CLOSED_WIN_QD`` the pipelined
      mechanism (CACHE READ: next sense under the current DMA transfer)
      beats serial baseline on closed-loop throughput.
    """
    ladder = CLOSED_QD_LADDER_QUICK if quick else CLOSED_QD_LADDER
    win_qd = (CLOSED_WIN_QD if CLOSED_WIN_QD in ladder
              else ladder[len(ladder) // 2])
    w = dataclasses.replace(w, n_requests=n_requests)
    mechs = ("baseline", "pr2ar2")
    hc = HostCacheConfig(capacity_pages=max(64, n_requests // 8))
    cells = [
        Cell("compare", w, (cond,), mechs, s, gc="prepass", ncq_depth=qd)
        for qd in ladder
        for s in seeds
    ]
    cells += [Cell("compare", w, (cond,), mechs, s, gc="prepass")
              for s in seeds]                       # open-loop anchor
    cells += [Cell("compare", w, (cond,), mechs, s, gc="prepass",
                   ncq_depth=win_qd, host_cache=hc)
              for s in seeds]                       # write-back cache rung
    t0 = time.perf_counter()
    results = iter(run_cells(cells, workers=workers))
    row = {
        "workload": w.name,
        "condition": cond.label(),
        "n_requests": n_requests,
        "n_seeds": len(seeds),
        "qd_ladder": list(ladder),
        "win_qd": win_qd,
    }
    iops_by_qd = {}
    rungs = []
    for qd in ladder:
        iops_b, iops_p, dev_p99, wait = [], [], [], []
        for s in seeds:
            grid = next(results)
            st, base = grid["pr2ar2"], grid["baseline"]
            iops_b.append(base.throughput_iops)
            iops_p.append(st.throughput_iops)
            dev_p99.append(st.read_device_p99_us)
            wait.append(st.hostq_wait_mean_us)
        im, ih = mean_ci95(iops_p)
        bm, bh = mean_ci95(iops_b)
        dm, dh = mean_ci95(dev_p99)
        iops_by_qd[qd] = im
        rungs.append({
            "qd": qd,
            "throughput_iops_mean": round(im, 1),
            "throughput_iops_ci95": round(ih, 1),
            "baseline_iops_mean": round(bm, 1),
            "baseline_iops_ci95": round(bh, 1),
            "read_device_p99_us_mean": round(dm, 1),
            "read_device_p99_us_ci95": round(dh, 1),
            "hostq_wait_mean_us": round(float(np.mean(wait)), 1),
        })
    row["rungs"] = rungs
    open_p99 = []
    for s in seeds:
        grid = next(results)
        open_p99.append(grid["pr2ar2"].read_p99_us)
    om, oh = mean_ci95(open_p99)
    row["open_loop_read_p99_us_mean"] = round(om, 1)
    row["open_loop_read_p99_us_ci95"] = round(oh, 1)
    hit_p, absw, stalls, mean_c = [], [], [], []
    for s in seeds:
        grid = next(results)
        st = grid["pr2ar2"]
        hit_p.append(st.cache_hit_pages)
        absw.append(st.cache_absorbed_writes)
        stalls.append(st.cache_stalled_writes)
        mean_c.append(st.mean_us)
    row["cache_rung"] = {
        "qd": win_qd,
        "capacity_pages": hc.capacity_pages,
        "absorbed_writes_mean": round(float(np.mean(absw)), 1),
        "hit_pages_mean": round(float(np.mean(hit_p)), 1),
        "stalled_writes_mean": round(float(np.mean(stalls)), 1),
        "mean_us": round(float(np.mean(mean_c)), 1),
    }
    row["wall_s"] = round(time.perf_counter() - t0, 3)

    ladder_iops = [iops_by_qd[qd] for qd in ladder]
    # 2% slack: past saturation, deeper queues reshuffle GC interleaving
    # and the plateau can dip fractionally.
    monotone = all(b >= a * 0.98
                   for a, b in zip(ladder_iops, ladder_iops[1:]))
    has_knee = ladder_iops[-1] < ladder_iops[-2] * 1.5
    row["ok_throughput_monotone"] = bool(monotone and has_knee)
    bounded = [r for r in rungs if r["qd"] <= 16]
    row["ok_qd_bounded_p99"] = bool(all(
        r["read_device_p99_us_mean"] <= om * (1 + 1e-9) for r in bounded
    ))
    win = next(r for r in rungs if r["qd"] == win_qd)
    row["pr2_overlap_speedup"] = round(
        win["throughput_iops_mean"] / win["baseline_iops_mean"], 3)
    row["ok_pr2_overlap_win"] = bool(row["pr2_overlap_speedup"] > 1.0)
    return row


# -- parallel-sweep cells: the runtime's workers speedup ------------------


def bench_parallel_sweep(n_requests, seeds, quick, workers):
    """Measure the sweep executor: the paper-claim grid at workers=1 vs
    workers=N on the same host, same run.

    The acceptance contract has two halves: per-cell results must be
    *identical* (``cells_equal`` — SimStats dataclass equality over the
    whole grid), and the wall-clock ``speedup`` is recorded alongside
    the host fingerprint (a 2-core/CPU-quota'd host cannot show the
    >= 2x a 4-core host does; the fingerprint makes that legible).

    On a single-core host the speedup half of the contract is
    unmeasurable — extra workers can only add process overhead, and a
    recorded sub-1x "speedup" reads as a runtime regression when it is
    purely a host property.  The block is therefore *gated* on the
    fingerprint: with ``cpu_count < 2`` it carries ``skipped`` +
    ``skipped_reason`` instead of misleading numbers (result equality
    across worker counts stays covered by ``bench_compare``'s
    deterministic-payload diff, which runs regardless).
    """
    cpus = int(host_fingerprint().get("cpu_count") or 1)
    if cpus < 2:
        return {
            "workers": workers,
            "skipped": True,
            "skipped_reason": (
                f"cpu_count={cpus} < 2: parallel-sweep speedup is not "
                "measurable on a single-core host; worker-count result "
                "equality is asserted by bench_compare instead"),
        }
    profiles = PROFILES[:2] if quick else PROFILES
    mechs = ("baseline", "pr2ar2")
    grids, walls = {}, {}
    for wk in (1, workers):
        t0 = time.perf_counter()
        grids[wk] = {
            w.name: simulate_batch(
                w, (AGED,), mechanisms=mechs, seeds=seeds,
                n_requests=n_requests, workers=wk,
            )
            for w in profiles
        }
        walls[wk] = time.perf_counter() - t0
    return {
        "workers": workers,
        "sweep_cells": len(profiles) * len(mechs) * len(seeds),
        "n_requests": n_requests,
        "wall_workers1_s": round(walls[1], 3),
        "wall_workersN_s": round(walls[workers], 3),
        "speedup": round(walls[1] / walls[workers], 2),
        "cells_equal": bool(grids[1] == grids[workers]),
    }


# -- shard-scaling cells: lockstep batched core vs the interpreter --------


def _engine_pair_row(cfg, w, seeds, mech):
    """Array-vs-batched measurement for one config: best-of-3 walls per
    (seed, engine), per-seed bit parity (full SimStats equality),
    fast-path-activated flag, and the events/sec speedup mean ± CI."""
    walls = {"array": [], "batched": []}
    eps = {"array": [], "batched": []}
    ratios, parity = [], True
    fast_path = True
    # warm every (cfg, engine, seed) triple: each seed's trace can land
    # in a different static-shape bucket (capsteps/capq), so one warm
    # run per config still leaves jit compiles inside the timed loop
    for s in seeds:
        for eng in ("array", "batched"):
            SSDSim(cfg, AGED, RetryPolicy(mech), seed=s + 7,
                   engine=eng).run(cached_trace(w, seed=s))
    for s in seeds:
        trace = cached_trace(w, seed=s)
        stats = {}
        for eng in ("array", "batched"):
            # best-of-3: scheduler jitter on a shared host is ±30%
            # one-sided slowdown; min is the standard estimator of
            # the undisturbed wall
            best = None
            for _ in range(3):
                sim = SSDSim(cfg, AGED, RetryPolicy(mech), seed=s + 7,
                             engine=eng)
                t0 = time.perf_counter()
                stats[eng] = sim.run(trace)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            walls[eng].append(best)
            eps[eng].append(sim.events_processed / best)
        parity = parity and stats["array"] == stats["batched"]
        fast_path = fast_path and \
            stats["batched"].fast_path_events > 0
        ratios.append(eps["batched"][-1] / eps["array"][-1])
    row = {"bit_parity": bool(parity),
           "fast_path_active": bool(fast_path)}
    for eng in ("array", "batched"):
        wm, wh = mean_ci95(walls[eng])
        em, eh = mean_ci95(eps[eng])
        row[eng] = {
            "wall_mean_s": round(wm, 4), "wall_ci95_s": round(wh, 4),
            "events_per_sec_mean": round(em),
            "events_per_sec_ci95": round(eh),
        }
    rm, rh = mean_ci95(ratios)
    row["batched_speedup_mean"] = round(rm, 3)
    row["batched_speedup_ci95"] = round(rh, 3)
    return row


def bench_small_cell_sweep(seeds, n_requests=500):
    """Sweep-level dispatch overhead: tiny cells, where fixed per-run
    cost (trace prep, kernel dispatch, shape-bucket padding, jit cache
    lookup) dominates the event loop.

    The same grid — 2 workloads x {baseline, pr2ar2} x {fcfs,
    host_prio} x seeds at n=500 — is pushed through ``run_cells`` twice:
    ``engine="array"`` and ``engine="auto"`` (auto must resolve to
    batched on every cell of this grid, and each returned SimStats
    records that in ``engine_selected``).  With the persistent compile
    cache and shape-bucketed padding the batched sweep must not lose to
    the interpreter even at this size — the evidence that the batched
    core's fixed overhead is gone at sweep level, not just amortized at
    n=8000.  Best-of-3 sweep walls; results must be equal cell-for-cell.
    """
    grid_w = [p for p in PROFILES if p.name in ("websearch", "oltp")]
    mechs = ("baseline", "pr2ar2")
    scheds = (None, "host_prio")

    def grid(engine):
        return [Cell("simulate", w, (AGED,), (m,), s,
                     n_requests=n_requests, engine=engine, scheduler=sc)
                for w in grid_w for m in mechs for sc in scheds
                for s in seeds]

    results, walls = {}, {}
    for eng in ("array", "auto"):
        cells = grid(eng)
        run_cells(cells)  # warm: char tables + every jit shape bucket
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            results[eng] = run_cells(cells)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        walls[eng] = best
    equal = results["array"] == results["auto"]
    auto_batched = all(r.engine_selected == "batched"
                       for r in results["auto"])
    speedup = walls["array"] / walls["auto"]
    return {
        "n_requests": n_requests,
        "cells": len(results["array"]),
        "seeds": len(seeds),
        "workloads": [w.name for w in grid_w],
        "mechanisms": list(mechs),
        "schedulers": ["fcfs" if s is None else s for s in scheds],
        "wall_array_s": round(walls["array"], 3),
        "wall_batched_s": round(walls["auto"], 3),
        "sweep_speedup": round(speedup, 3),
        "cells_equal": bool(equal),
        "auto_selected_batched_all": bool(auto_batched),
        "acceptance_small_cell_ok": bool(
            speedup >= 1.0 and equal and auto_batched),
    }


# -- fused sweep cells: cross-cell vectorized dispatch (ISSUE 10) ---------


def _fused_grid_row(grid_w, mechs, scheds, seeds, n_requests, rounds):
    """One fused-sweep measurement grid: fused vs sequential-batched vs
    array through ``run_cells``, interleaved timing rounds (drift
    cancels), per-cell bit-parity flags, and the fused dispatch count.
    """
    from repro.kernels.fcfs_core import ops as kops

    def mk(engine, fuse):
        return [Cell("simulate", w, (AGED,), (m,), s,
                     n_requests=n_requests, engine=engine, scheduler=sc,
                     fuse=fuse)
                for w in grid_w for m in mechs for sc in scheds
                for s in seeds]

    variants = {"fused": ("batched", True),
                "sequential": ("batched", False),
                "array": ("array", None)}
    results = {}
    for name, (eng, fz) in variants.items():   # warm: char + jit buckets
        results[name] = run_cells(mk(eng, fz))
    before = kops.KERNEL_DISPATCHES
    run_cells(mk("batched", True))
    fused_dispatches = kops.KERNEL_DISPATCHES - before
    before = kops.KERNEL_DISPATCHES
    run_cells(mk("batched", False))
    sequential_dispatches = kops.KERNEL_DISPATCHES - before

    # Interleaved rounds with the collector parked: adjacent
    # measurements see the same host state, and GC pauses (pure jitter
    # at these sub-second walls) hit no variant.
    walls = {name: [] for name in variants}
    gc.collect()
    gc.disable()
    try:
        for _ in range(rounds):
            for name, (eng, fz) in variants.items():
                cells = mk(eng, fz)
                t0 = time.perf_counter()
                results[name] = run_cells(cells)
                walls[name].append(time.perf_counter() - t0)
    finally:
        gc.enable()

    parity_vs_sequential = [bool(a == b) for a, b in
                            zip(results["fused"], results["sequential"])]
    parity_vs_array = [bool(a == b) for a, b in
                       zip(results["fused"], results["array"])]
    n_cells = len(results["fused"])
    row = {
        "n_requests": n_requests,
        "cells": n_cells,
        "seeds": len(seeds),
        "rounds": rounds,
        "workloads": [w.name for w in grid_w],
        "mechanisms": list(mechs),
        "schedulers": ["fcfs" if s is None else s for s in scheds],
        "fused_dispatches": fused_dispatches,
        "sequential_dispatches": sequential_dispatches,
        "fused_cells_per_dispatch": sorted(
            {r.fused_cells for r in results["fused"]}),
        "parity_vs_sequential": parity_vs_sequential,
        "parity_vs_array": parity_vs_array,
        "parity_all": bool(all(parity_vs_sequential)
                           and all(parity_vs_array)),
    }
    # Machine-free normalization: cell throughput (requests/s) relative
    # to the same run's array sweep.
    thr = {}
    for name in variants:
        wm, wh = mean_ci95(walls[name])
        best = min(walls[name])
        thr[name] = n_cells * n_requests / best
        row[name] = {
            "wall_mean_s": round(wm, 4),
            "wall_ci95_s": round(wh, 4),
            "wall_best_s": round(best, 4),
        }
    for name in variants:
        row[name]["rel_throughput"] = round(thr[name] / thr["array"], 3)
    row["speedup_vs_sequential"] = round(
        row["sequential"]["wall_best_s"] / row["fused"]["wall_best_s"], 3)
    row["speedup_vs_array"] = round(
        row["array"]["wall_best_s"] / row["fused"]["wall_best_s"], 3)
    return row


def bench_fused_sweep_cells(seeds, n_claim, quick=False):
    """Fused sweep core vs the sequential batched engine vs the array
    interpreter (ISSUE 10).

    Two grids, both pushed through ``run_cells`` three ways —
    ``engine="batched"`` with fusion on (cross-cell stacked dispatches),
    fusion off (one dispatch per cell), and ``engine="array"``:

      * the **small-cell grid** — the n=500 dispatch-overhead grid of
        :func:`bench_small_cell_sweep` (2 workloads x {baseline, pr2ar2}
        x {fcfs, host_prio} x seeds), where fixed per-dispatch cost
        dominates and fusion pays most; the acceptance rides here:
        ``speedup_vs_sequential >= 1.5`` with every parity flag true;
      * the **claim grid** — the paper-claim mechanism pair over the
        claim profiles at the acceptance size (n=8000), where the
        lockstep event loop dominates and fusion's win shrinks to the
        amortized dispatch overhead (recorded, not gated).

    Walls are interleaved rounds (mean ± 95% CI + best); per-cell
    bit-parity flags compare full SimStats equality fused-vs-sequential
    and fused-vs-array; ``fused_dispatches`` vs
    ``sequential_dispatches`` records the kernel-launch accounting
    (``KERNEL_DISPATCHES``).  ``rel_throughput`` normalizes each
    variant's request throughput to the same run's array sweep, so
    cross-machine comparisons stay machine-free.
    """
    grid_w = [p for p in PROFILES if p.name in ("websearch", "oltp")]
    mechs = ("baseline", "pr2ar2")
    # Claim grid first: its long runs leave the process (allocator
    # pools, jit caches, branch predictors) fully hot before the gated
    # small-grid measurement — the first grid measured in a fresh
    # process reads consistently slow for every variant.
    claim_w = PROFILES[:2] if quick else PROFILES
    claim = _fused_grid_row(claim_w, mechs, (None,), seeds, n_claim,
                            2 if quick else 3)
    small = _fused_grid_row(grid_w, mechs, (None, "host_prio"), seeds,
                            500, 3 if quick else 8)
    return {
        "small_cell_grid": small,
        "claim_grid": claim,
        "speedup_small_grid": small["speedup_vs_sequential"],
        "speedup_claim_grid": claim["speedup_vs_sequential"],
        "parity_all": bool(small["parity_all"] and claim["parity_all"]),
        "acceptance_fused_sweep_ok": bool(
            small["speedup_vs_sequential"] >= 1.5
            and small["parity_all"] and claim["parity_all"]),
    }


def bench_shard_scaling(n_requests, seeds):
    """Single-cell engine scaling: wall vs channel count, the array
    interpreter vs the lockstep batched core
    (:mod:`repro.flashsim.engine_batched`), websearch @ aged.

    Per (n_channels, engine) cell: mean ± 95% CI of wall seconds and
    events/sec over the seeds, plus per-seed bit-parity (full SimStats
    dataclass equality between the engines) and whether the lockstep fast
    path actually ran (``fast_path_events`` counter).  ``rel_throughput``
    normalizes every cell against this run's 8-channel array cell, so
    the scaling shape is machine-free; absolute walls are host-dependent
    (the top-level fingerprint records the core count — a CPU-quota'd
     1-core container cannot show multi-core scaling, but the batched
    speedup is in-process and holds regardless).

    Two companion blocks ride along:

      * ``scheduler_cells_8ch`` — the 8-channel cell re-measured under
        the dual priority rings (host_prio, host_prio_aged): the
        priority lowering must keep bit parity *and* keep paying at
        8 channels (acceptance: batched >= 1.3x array under host_prio);
      * ``small_cell_sweep`` — :func:`bench_small_cell_sweep`, the
        n=500 dispatch-overhead gate.

    The headline acceptance gate rides on the 8-channel fcfs cell:
    ``batched_speedup_mean >= 1.5`` (events/sec, batched / array).
    """
    w0 = next(p for p in PROFILES if p.name == "websearch")
    w = dataclasses.replace(w0, n_requests=n_requests)
    mech = "baseline"
    channel_rows = []
    for c in (1, 2, 4, 8):
        cfg = dataclasses.replace(DEFAULT_SSD, n_channels=c)
        channel_rows.append(
            {"n_channels": c, **_engine_pair_row(cfg, w, seeds, mech)})
    sched_rows = []
    for sched in ("host_prio", "host_prio_aged"):
        cfg = dataclasses.replace(DEFAULT_SSD, n_channels=8,
                                  scheduler=sched)
        sched_rows.append(
            {"scheduler": sched, "n_channels": 8,
             **_engine_pair_row(cfg, w, seeds, mech)})
    ref_eps = next(r for r in channel_rows if r["n_channels"] == 8
                   )["array"]["events_per_sec_mean"]
    for r in channel_rows + sched_rows:
        for eng in ("array", "batched"):
            r[eng]["rel_throughput"] = round(
                r[eng]["events_per_sec_mean"] / ref_eps, 3)
    ch8 = channel_rows[-1]
    hp8 = next(r for r in sched_rows if r["scheduler"] == "host_prio")
    all_rows = channel_rows + sched_rows
    return {
        "workload": w0.name,
        "condition": AGED.label(),
        "mechanism": mech,
        "n_requests": n_requests,
        "seeds": len(seeds),
        "channels": channel_rows,
        "scheduler_cells_8ch": sched_rows,
        "bit_parity_all": bool(all(r["bit_parity"] for r in all_rows)),
        "fast_path_all": bool(
            all(r["fast_path_active"] for r in all_rows)),
        "speedup_8ch_mean": ch8["batched_speedup_mean"],
        "speedup_8ch_ci95": ch8["batched_speedup_ci95"],
        "acceptance_8ch_speedup_ok": bool(
            ch8["batched_speedup_mean"] >= 1.5),
        "speedup_8ch_host_prio_mean": hp8["batched_speedup_mean"],
        "speedup_8ch_host_prio_ci95": hp8["batched_speedup_ci95"],
        "acceptance_8ch_host_prio_ok": bool(
            hp8["batched_speedup_mean"] >= 1.3),
        "small_cell_sweep": bench_small_cell_sweep(seeds),
        # multi-core *process* scaling is a different (host-gated)
        # claim; this cell's speedup is single-process lockstep
        "host_dependent": "wall times; see top-level host fingerprint",
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=None,
                    help="requests per cell (default 8000; 1200 in --quick)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seeds", type=int, default=None,
                    help="seeds per claim/GC/scheduler cell "
                         "(default 5; 2 in --quick)")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--workers", type=int, default=None,
                    help="process-pool workers for the sweep cells "
                         "(default 4; 1 in --quick)")
    ap.add_argument("--skip-reference", action="store_true")
    ap.add_argument("--skip-gc", action="store_true")
    ap.add_argument("--skip-traces", action="store_true")
    ap.add_argument("--out", default="BENCH_sim.json")
    args = ap.parse_args()
    n = args.n if args.n is not None else (1200 if args.quick else 8000)
    n_seeds = args.seeds if args.seeds is not None else (2 if args.quick else 5)
    if n_seeds < 1:
        ap.error("--seeds must be >= 1")
    workers = args.workers if args.workers is not None else \
        (1 if args.quick else 4)
    if workers < 1:
        ap.error("--workers must be >= 1")
    seeds = tuple(range(args.seed, args.seed + n_seeds))

    cells = e2e_cells(args.quick)
    warm_s = warm_characterization(cells)
    print(f"# characterization warm: {warm_s:.1f}s ({len(cells)} cells)")

    rows = []
    for w, cond, mechs in cells:
        row = bench_cell(w, cond, mechs, n, args.seed, args.skip_reference)
        rows.append(row)
        spd = f" speedup={row['speedup']:5.2f}x" if "speedup" in row else ""
        print(
            f"{w.name:10s} @ {cond.label():>10s} x{len(mechs)} mechs: "
            f"array {row['wall_array_s']:6.3f}s "
            f"({row['events_per_sec_array'] / 1e6:.2f}M ev/s){spd}"
        )

    t0 = time.perf_counter()
    claim_rows, claim_summary = bench_claim_cells(n, seeds, args.quick,
                                                  workers=workers)
    print(
        f"# claim CI ({len(seeds)} seeds, {time.perf_counter() - t0:.1f}s): "
        f"vs baseline -{100 * claim_summary['avg_vs_baseline']['mean']:.1f}%"
        f"±{100 * claim_summary['avg_vs_baseline']['ci95']:.1f} "
        f"(paper -35.7%) | vs SOTA "
        f"-{100 * claim_summary['avg_vs_sota']['mean']:.1f}%"
        f"±{100 * claim_summary['avg_vs_sota']['ci95']:.1f} (paper -21.8%) "
        f"-> {'OK' if claim_summary['claim_ci_overlap_ok'] else 'MISMATCH'}"
    )

    gc_rows, sched_rows = [], []
    gc_carried = False
    if args.skip_gc:
        # Don't clobber the recorded GC trajectory: carry the previous
        # file's GC cells forward (flagged so readers know they're stale).
        try:
            with open(args.out) as f:
                prev = json.load(f)
            gc_rows = prev.get("gc_cells", [])
            sched_rows = prev.get("sched_cells", [])
            gc_carried = bool(gc_rows or sched_rows)
        except (OSError, ValueError):
            pass
    else:
        n_gc = GC_QUICK_N if args.quick else n
        gc_profiles = GC_PROFILES[:1] if args.quick else GC_PROFILES
        for w in gc_profiles:
            row = bench_gc_cell(w, AGED, n_gc, seeds, workers=workers)
            gc_rows.append(row)
            print(
                f"GC {w.name:8s} @ {row['condition']:>10s}: "
                f"WA={row['wa_mean']:.2f}±{row['wa_ci95']:.2f} "
                f"read_p99 x{row['baseline']['read_p99_inflation_mean']:.1f}"
                f"±{row['baseline']['read_p99_inflation_ci95']:.1f} "
                f"(pr2ar2 x{row['pr2ar2']['read_p99_inflation_mean']:.1f}) "
                f"ok={row['ok_wa_gt_1'] and row['ok_read_p99_higher']}"
            )
        for w in gc_profiles:
            row = bench_sched_cell(w, AGED, n_gc, seeds, workers=workers)
            sched_rows.append(row)
            print(
                f"SCHED {w.name:8s} online-GC inflation: "
                f"fcfs x{row['fcfs']['read_p99_inflation_mean']:.1f} -> "
                f"host_prio x{row['host_prio']['read_p99_inflation_mean']:.1f} "
                f"(cut {row['inflation_cut_host_prio']:.0f}x) -> "
                f"preempt x{row['preempt']['read_p99_inflation_mean']:.1f} "
                f"(cut {row['inflation_cut_preempt']:.0f}x) "
                f"wa_eq={row['ok_wa_equal']} ok={row['ok_p99_cut_2x']}"
            )

    trace_rows = []
    trace_carried = False
    if args.skip_traces:
        try:
            with open(args.out) as f:
                prev = json.load(f)
            trace_rows = prev.get("trace_cells", [])
            trace_carried = bool(trace_rows)
        except (OSError, ValueError):
            pass
    else:
        specs = TRACE_SPECS[:1] if args.quick else TRACE_SPECS
        for spec in specs:
            row = bench_trace_cell(spec, AGED, seeds, workers=workers)
            trace_rows.append(row)
            print(
                f"TRACE {spec:12s} ({row['source']['n_requests']} reqs, "
                f"rd={row['source']['read_ratio']:.2f}): "
                f"baseline {row['baseline']['mean_us']:.0f}us -> pr2ar2 "
                f"{row['pr2ar2']['mean_us']:.0f}us "
                f"(-{100 * row['pr2ar2_reduction_mean']:.1f}%"
                f"±{100 * row['pr2ar2_reduction_ci95']:.1f}) "
                f"WA={row['wa_mean']:.2f} ok={row['ok_finite']}"
            )

    fault_rows = []
    fprofiles = [w for w in PROFILES if w.read_dominant]
    fprofiles = fprofiles[:1] if args.quick else fprofiles[:2]
    for w in fprofiles:
        row = bench_fault_cell(w, AGED, n, seeds, workers=workers)
        fault_rows.append(row)
        d = row["scale_1"]
        print(
            f"FAULT {w.name:10s} @ {row['condition']:>10s}: mispredict "
            f"{100 * d['mispredict_rate_mean']:.2f}%"
            f"±{100 * d['mispredict_rate_ci95']:.2f} -> win "
            f"{100 * d['latency_win_mean']:.1f}%"
            f"±{100 * d['latency_win_ci95']:.1f} "
            f"(clean {100 * row['scale_0']['latency_win_mean']:.1f}%, "
            f"x4 {100 * row['scale_4']['latency_win_mean']:.1f}%) "
            f"rec_p99 {d['recovery_p99_us_mean']:.0f}us "
            f"ok={row['ok_unrecoverable_zero'] and row['ok_win_erodes']}"
        )

    closed_rows = []
    for w in (GC_PROFILES[:1] if args.quick else GC_PROFILES[:2]):
        n_cl = GC_QUICK_N if args.quick else n
        row = bench_closed_loop_cell(w, AGED, n_cl, seeds,
                                     quick=args.quick, workers=workers)
        closed_rows.append(row)
        knee = row["rungs"][-1]
        ok = (row["ok_throughput_monotone"] and row["ok_qd_bounded_p99"]
              and row["ok_pr2_overlap_win"])
        print(
            f"CLOSED {w.name:8s} QD ladder "
            f"{row['rungs'][0]['throughput_iops_mean']:.0f} -> "
            f"{knee['throughput_iops_mean']:.0f} IOPS "
            f"(x{row['pr2_overlap_speedup']:.2f} vs baseline @QD"
            f"{row['win_qd']}) dev_p99<= "
            f"{row['open_loop_read_p99_us_mean']:.0f}us "
            f"ok={ok}"
        )

    parallel_row = None
    if workers > 1:
        t0 = time.perf_counter()
        parallel_row = bench_parallel_sweep(n, seeds, args.quick, workers)
        if parallel_row.get("skipped"):
            print(f"# parallel sweep skipped: "
                  f"{parallel_row['skipped_reason']}")
        else:
            print(
                f"# parallel sweep ({parallel_row['sweep_cells']} cells, "
                f"{time.perf_counter() - t0:.1f}s): workers=1 "
                f"{parallel_row['wall_workers1_s']:.2f}s -> "
                f"workers={workers} "
                f"{parallel_row['wall_workersN_s']:.2f}s "
                f"(speedup {parallel_row['speedup']:.2f}x, "
                f"equal={parallel_row['cells_equal']})"
            )

    t0 = time.perf_counter()
    shard_scaling = bench_shard_scaling(n, seeds)
    small = shard_scaling["small_cell_sweep"]
    print(
        f"# shard scaling ({time.perf_counter() - t0:.1f}s): "
        f"batched/array @8ch "
        f"{shard_scaling['speedup_8ch_mean']:.2f}x"
        f"±{shard_scaling['speedup_8ch_ci95']:.2f} "
        f"(host_prio {shard_scaling['speedup_8ch_host_prio_mean']:.2f}x"
        f"±{shard_scaling['speedup_8ch_host_prio_ci95']:.2f}) "
        f"parity={shard_scaling['bit_parity_all']} "
        f"fast_path={shard_scaling['fast_path_all']} "
        f"ok={shard_scaling['acceptance_8ch_speedup_ok']}"
        f"/{shard_scaling['acceptance_8ch_host_prio_ok']}"
    )
    print(
        f"# small-cell sweep (n={small['n_requests']}, "
        f"{small['cells']} cells): array {small['wall_array_s']:.2f}s -> "
        f"batched {small['wall_batched_s']:.2f}s "
        f"({small['sweep_speedup']:.2f}x, equal={small['cells_equal']}, "
        f"auto={small['auto_selected_batched_all']}, "
        f"ok={small['acceptance_small_cell_ok']})"
    )

    t0 = time.perf_counter()
    fused_sweep = bench_fused_sweep_cells(seeds, n, quick=args.quick)
    fs_small = fused_sweep["small_cell_grid"]
    fs_claim = fused_sweep["claim_grid"]
    print(
        f"# fused sweep ({time.perf_counter() - t0:.1f}s): small grid "
        f"(n={fs_small['n_requests']}, {fs_small['cells']} cells) "
        f"seq {fs_small['sequential']['wall_best_s']:.2f}s -> fused "
        f"{fs_small['fused']['wall_best_s']:.2f}s "
        f"({fs_small['speedup_vs_sequential']:.2f}x, "
        f"{fs_small['fused_dispatches']}/"
        f"{fs_small['sequential_dispatches']} dispatches) | claim grid "
        f"(n={fs_claim['n_requests']}) "
        f"{fs_claim['speedup_vs_sequential']:.2f}x "
        f"parity={fused_sweep['parity_all']} "
        f"ok={fused_sweep['acceptance_fused_sweep_ok']}"
    )

    total_array = sum(r["wall_array_s"] for r in rows)
    # Reference-cell normalization: cells_detail[0] is the pinned cell
    # (first e2e cell, websearch @ aged x all mechanisms); dividing each
    # cell's throughput by it cancels the machine.
    ref_eps = rows[0]["events_per_sec_array"]
    for r in rows:
        r["rel_throughput"] = round(r["events_per_sec_array"] / ref_eps, 3)
    reference_cell = {
        "workload": rows[0]["workload"],
        "condition": rows[0]["condition"],
        "n_requests": n,
        "events_per_sec_array": ref_eps,
        "pinned_events_per_sec": (
            REFERENCE_EVENTS_PER_SEC if n == REFERENCE_N else None
        ),
        # host_factor > 1: this host is faster than the machine class
        # that set the pin; None off the acceptance size (not comparable).
        "host_factor": (
            round(ref_eps / REFERENCE_EVENTS_PER_SEC, 3)
            if n == REFERENCE_N else None
        ),
    }
    summary = {
        "n_requests": n,
        "cells": len(rows),
        "wall_array_total_s": round(total_array, 3),
        "events_per_sec_array": round(
            sum(r["events_array"] for r in rows) / total_array
        ),
        "characterization_warm_s": round(warm_s, 2),
        "reference_cell": reference_cell,
        "claim": claim_summary,
    }
    summary["shard_scaling"] = {
        "speedup_8ch_mean": shard_scaling["speedup_8ch_mean"],
        "speedup_8ch_ci95": shard_scaling["speedup_8ch_ci95"],
        "bit_parity_all": shard_scaling["bit_parity_all"],
        "fast_path_all": shard_scaling["fast_path_all"],
        "acceptance_8ch_speedup_ok":
            shard_scaling["acceptance_8ch_speedup_ok"],
        "speedup_8ch_host_prio_mean":
            shard_scaling["speedup_8ch_host_prio_mean"],
        "speedup_8ch_host_prio_ci95":
            shard_scaling["speedup_8ch_host_prio_ci95"],
        "acceptance_8ch_host_prio_ok":
            shard_scaling["acceptance_8ch_host_prio_ok"],
        "small_cell_sweep_speedup": small["sweep_speedup"],
        "acceptance_small_cell_ok": small["acceptance_small_cell_ok"],
    }
    summary["fused_sweep"] = {
        "speedup_small_grid": fused_sweep["speedup_small_grid"],
        "speedup_claim_grid": fused_sweep["speedup_claim_grid"],
        "parity_all": fused_sweep["parity_all"],
        "acceptance_fused_sweep_ok":
            fused_sweep["acceptance_fused_sweep_ok"],
    }
    if parallel_row is not None:
        summary["parallel"] = parallel_row
    if not args.skip_reference:
        total_ref = sum(r["wall_seed_s"] for r in rows)
        summary["wall_seed_total_s"] = round(total_ref, 3)
        summary["speedup_total"] = round(total_ref / total_array, 2)
        summary["attempts_match_all"] = all(r["attempts_match"] for r in rows)
    if gc_rows:
        summary["gc_wa_max"] = max(r["wa_mean"] for r in gc_rows)
        summary["gc_acceptance_ok"] = all(
            r["ok_wa_gt_1"] and r["ok_read_p99_higher"] for r in gc_rows
        )
        if gc_carried:
            summary["gc_cells_carried"] = True  # from a previous run
    if sched_rows:
        summary["sched_acceptance_ok"] = all(
            r["ok_p99_cut_2x"] and r["ok_wa_equal"] for r in sched_rows
        )
        summary["sched_min_inflation_cut"] = min(
            min(r["inflation_cut_host_prio"], r["inflation_cut_preempt"])
            for r in sched_rows
        )
    if trace_rows:
        summary["trace_replay_ok"] = all(
            r["ok_finite"] and r["ok_wa_gt_1"] for r in trace_rows
        )
        summary["trace_cells_n"] = len(trace_rows)
        summary["trace_pr2ar2_reduction_mean"] = round(
            float(np.mean([r["pr2ar2_reduction_mean"] for r in trace_rows])),
            4,
        )
        if trace_carried:
            summary["trace_cells_carried"] = True  # from a previous run
    if closed_rows:
        summary["closed_loop_acceptance_ok"] = all(
            r["ok_throughput_monotone"] and r["ok_qd_bounded_p99"]
            and r["ok_pr2_overlap_win"]
            for r in closed_rows
        )
        summary["closed_loop_pr2_speedup_mean"] = round(
            float(np.mean([r["pr2_overlap_speedup"] for r in closed_rows])),
            3,
        )
    if fault_rows:
        summary["fault_acceptance_ok"] = all(
            r["ok_unrecoverable_zero"] and r["ok_mispredicted_fired"]
            and r["ok_win_erodes"]
            for r in fault_rows
        )
        summary["fault_unrecoverable_total"] = sum(
            r["unrecoverable_total"] for r in fault_rows
        )
        summary["fault_win_derived_mean"] = round(
            float(np.mean([r["scale_1"]["latency_win_mean"]
                           for r in fault_rows])), 4,
        )

    out = {"benchmark": "flashsim-des-engine",
           "host": host_fingerprint(),
           "summary": summary,
           "cells_detail": rows, "claim_cells": claim_rows,
           "gc_cells": gc_rows, "sched_cells": sched_rows,
           "trace_cells": trace_rows, "fault_cells": fault_rows,
           "closed_loop_cells": closed_rows,
           "shard_scaling_cells": shard_scaling,
           "fused_sweep_cells": fused_sweep}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(f"# summary: {json.dumps(summary)}")
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
