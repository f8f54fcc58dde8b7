"""Chip smoke test: the simulator's lockstep fast path on one TPU, exactly.

Runs every phase in this one process (the chip belongs to one process)
through the public run APIs with ``engine="batched"`` — the engine that
raises rather than falls back — and holds each cell to full ``SimStats``
equality with the array interpreter (``engine="array"``) on the same
inputs.  Phases:

  a. device check — exits non-zero unless JAX sees a TPU;
  b. characterization of the aged (365 days, 1000 P/E) and fresh
     conditions, reported as set-up time;
  c. single runs: ``simulate("websearch", aged, m, n_requests=N)`` for
     baseline / pr2 / ar2 / pr2ar2 (serial and pipelined lowerings);
  d. write and erase paths: ``prn`` under prepass GC and the
     ``host_prio_aged:8`` priority rings;
  e. fused sweep: ``simulate_batch`` over (fresh, aged) x six mechanisms
     x two seeds, fused chunks of up to 64 lanes;
  f. real-trace replay: ``compare_mechanisms`` on the checked-in MSR
     excerpt ``tests/data/web_0.csv.gz`` under prepass GC.

Each phase prints one JSON line: the device kind, cold and warm wall
seconds of the batched calls, XLA compiles (and persistent-cache hits)
with their seconds, ``n_events``, the equality result, and whether
pr2ar2's mean response time is below baseline's.  These are smoke
observations, not benchmark metrics.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``
only when every phase ran and every cell matched; otherwise it reports
``"ok": false`` and the script exits non-zero.

There is no four-chip phase: the simulator has no path across chips.
Fused lanes stack on one device, ``shard=`` and ``workers=`` are
host-side, and ``repro.distributed`` serves model code that
``repro.flashsim`` never imports.

    python chip_smoke.py [--n 8000] [--seed 0]

Run it twice against one ``JAX_COMPILATION_CACHE_DIR``: the second run
reports persistent-cache hits instead of new compiles.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

AGED = (365.0, 1000.0)
FRESH = (0.0, 0.0)
MECHS = ("baseline", "sota", "pr2", "ar2", "pr2ar2", "sota+pr2ar2")
NOTE = "smoke observations, not benchmark metrics"


class CompileCounter:
    """Counts XLA compiles and persistent-cache hits via jax.monitoring."""

    def __init__(self, jax):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.compiles, self.compile_s, self.cache_hits)


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def fail(error: str, device=None) -> None:
    emit({"ok": False, "error": error, "device": device})
    sys.exit(1)


def _mismatch(a, b) -> list:
    """Names of the compared SimStats fields on which ``a`` and ``b``
    differ."""
    return [f.name for f in dataclasses.fields(a)
            if f.compare and getattr(a, f.name) != getattr(b, f.name)]


def _stats_of(cell, result) -> dict:
    """Flatten a run-API result to ``{(mechanism, cell key): SimStats}``:
    ``simulate`` gives one stats object, ``compare_mechanisms`` a dict by
    mechanism, ``simulate_batch`` a dict by (mechanism, condition, seed).
    """
    if not isinstance(result, dict):
        return {(cell, ""): result}
    return {((k if isinstance(k, str) else k[0]), repr(k)): v
            for k, v in result.items()}


def run_phase(name, calls, counter, device_kind):
    """Run ``calls`` (``{cell: (fn, kwargs)}``) batched twice (cold,
    warm) and once on the array engine; print and return the phase line.
    """
    before = counter.snapshot()
    t0 = time.perf_counter()
    batched = {c: fn(engine="batched", **kw) for c, (fn, kw) in calls.items()}
    cold = time.perf_counter() - t0
    mid = counter.snapshot()
    t0 = time.perf_counter()
    for fn, kw in calls.values():
        fn(engine="batched", **kw)
    warm = time.perf_counter() - t0
    after = counter.snapshot()
    t0 = time.perf_counter()
    array = {c: fn(engine="array", **kw) for c, (fn, kw) in calls.items()}
    array_s = time.perf_counter() - t0

    mismatched, n_events, means = {}, 0, {}
    for cell in calls:
        got = _stats_of(cell, batched[cell])
        want = _stats_of(cell, array[cell])
        if list(got) != list(want):
            raise RuntimeError(f"{name}/{cell}: result keys differ")
        for (mech, key), st in got.items():
            if st.fast_path_events <= 0:
                raise RuntimeError(f"{name}/{cell}{key}: no device events")
            n_events += st.fast_path_events
            bad = _mismatch(st, want[(mech, key)])
            if bad:
                mismatched[f"{cell}{key}"] = {
                    f: [getattr(st, f), getattr(want[(mech, key)], f)]
                    for f in bad}
            means.setdefault(mech, []).append(st.mean_us)
    below = None
    if "baseline" in means and "pr2ar2" in means:
        below = all(p < b for p, b in zip(means["pr2ar2"], means["baseline"]))
    line = {
        "phase": name,
        "device_kind": device_kind,
        "cold_s": cold,
        "warm_s": warm,
        "array_s": array_s,
        "compiles_cold": mid[0] - before[0],
        "compile_s_cold": mid[1] - before[1],
        "cache_hits_cold": mid[2] - before[2],
        "compiles_warm": after[0] - mid[0],
        "n_events": n_events,
        "cells": sum(len(_stats_of(c, r)) for c, r in batched.items()),
        "equal": not mismatched,
        "mismatched": mismatched,
        "pr2ar2_mean_below_baseline": below,
        "note": NOTE,
    }
    emit(line)
    return line


def run_phases(n: int, seed: int, kind: str, counter) -> list:
    """Phases b-f; returns the equality-bearing phase lines (c-f)."""
    from repro.flashsim import (OperatingCondition, compare_mechanisms,
                                simulate, simulate_batch)
    from repro.flashsim.runtime import Cell, prewarm_characterization

    aged, fresh = OperatingCondition(*AGED), OperatingCondition(*FRESH)

    t0 = time.perf_counter()
    c0 = counter.snapshot()
    n_tables = prewarm_characterization(
        [Cell("batch", "websearch", (fresh, aged), MECHS, seed)])
    c1 = counter.snapshot()
    emit({"phase": "b_characterization", "device_kind": kind,
          "setup_s": time.perf_counter() - t0, "tables": n_tables,
          "compiles": c1[0] - c0[0], "compile_s": c1[1] - c0[1],
          "cache_hits": c1[2] - c0[2], "note": NOTE})

    return [
        run_phase("c_single", {
            m: (simulate, dict(workload="websearch", condition=aged,
                               mechanism=m, seed=seed, n_requests=n))
            for m in ("baseline", "pr2", "ar2", "pr2ar2")
        }, counter, kind),
        run_phase("d_write_erase", {
            "pr2ar2": (simulate, dict(
                workload="prn", condition=aged, mechanism="pr2ar2",
                seed=seed, n_requests=min(2500, n), gc="prepass",
                scheduler="host_prio_aged:8")),
        }, counter, kind),
        run_phase("e_fused_sweep", {
            "grid": (simulate_batch, dict(
                workload="websearch", conditions=(fresh, aged),
                mechanisms=MECHS, seeds=(seed, seed + 1), n_requests=n,
                fuse=True)),
        }, counter, kind),
        run_phase("f_msr_replay", {
            "web_0": (compare_mechanisms, dict(
                workload=f"msr:web_0?limit={min(1500, n)}", condition=aged,
                mechanisms=("baseline", "pr2ar2"), seed=seed,
                gc="prepass")),
        }, counter, kind),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=8000,
                    help="requests per synthetic cell (phases c and e)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        fail(f"no TPU: JAX sees {device['platform']}", device)
    counter = CompileCounter(jax)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    try:
        import repro.flashsim  # noqa: F401
    except ImportError as e:
        fail(f"the simulator is not importable beside this script: {e}",
             device)
    emit({"chip_smoke": "start", "device": device, "n": args.n,
          "seed": args.seed,
          "jax_compilation_cache_dir": os.environ.get(
              "JAX_COMPILATION_CACHE_DIR"),
          "note": NOTE})

    try:
        lines = run_phases(args.n, args.seed, device["kind"], counter)
    except Exception as e:
        traceback.print_exc()
        fail(f"{type(e).__name__}: {e}", device)
    total = counter.snapshot()
    emit({"compile_total": {"compiles": total[0], "seconds": total[1],
                            "cache_hits": total[2],
                            "new_compiles": total[0] - total[2]},
          "note": NOTE})
    bad = [ln["phase"] for ln in lines if not ln["equal"]]
    if bad:
        fail(f"SimStats differ from engine='array' in {bad}", device)
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
