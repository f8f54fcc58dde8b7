"""Step time of the lockstep core under each carry lowering, on the op
tables of the benchmark's own call.

Captures the padded op tables that one ``websearch`` call at the aged
corner (8,000 requests, 8 channels x 8 dies, PR2+AR2: the shape of the
``websearch.call`` cell) hands the core, under the fcfs scheduler (one
FIFO ring) and under host_prio (the dual rings).  It then dispatches
them, tiled to more lanes and with the FIFO ring capacity ``capq``
forced larger, under each carry lowering, and prints one JSON line per
dispatch: lanes, capq, lowering and device µs per step.  Only the first
``--steps`` steps run: a step's work depends on the shapes alone, never
on the data, so a prefix times it.  Tables stay on the device; the wall
is the median of ``--repeats`` blocking runs after a warm-up.

``--calls`` also times whole calls under each lowering: the call cell's
``simulate`` and the grid cell's 12-cell ``simulate_batch``.
``--trace-steps N`` profiles N steps of the natural-size call (8 lanes)
under each scheduler and lowering and splits a step into op execution
and the idle gaps between ops, by the op each gap precedes.

    PYTHONPATH=src python -m benchmarks.core_lowering --trace-steps 512
    PYTHONPATH=src JAX_PLATFORMS=cpu python -m benchmarks.core_lowering \\
        --lanes 8 24 --capq 256 --calls
"""

from __future__ import annotations

import argparse
import glob
import json
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.flashsim.config import OperatingCondition
from repro.flashsim.ssd import simulate, simulate_batch
from repro.kernels.fcfs_core import ops
from repro.kernels.fcfs_core.kernel import LOWERINGS

AGED = OperatingCondition(365.0, 1000.0)
N_REQUESTS = 8000


def capture(scheduler):
    """The (ops, n_dies, pipelined, timing, prio) of every core dispatch
    that one call at the aged corner makes."""
    seen, real = [], ops._dispatch

    def record(o, n_dies, pipelined, timing, prio, caps=None, steps=None):
        seen.append((o, n_dies, pipelined, timing, prio))
        return real(o, n_dies, pipelined, timing, prio, caps=caps,
                    steps=steps)

    ops._dispatch = record
    try:
        simulate("websearch", AGED, "pr2ar2", seed=0, n_requests=N_REQUESTS,
                 engine="batched", scheduler=scheduler)
    finally:
        ops._dispatch = real
    return seen


def staged(o, n_dies, pipelined, timing, prio, lanes, capq, steps,
           lowering):
    """A blocking no-argument run of ``steps`` steps of the core on ``o``
    tiled to ``lanes`` lanes, its table already on the device."""
    reps = -(-lanes // o.shape[0])
    o = np.concatenate([o] * reps)[:lanes]
    timing = np.concatenate([timing] * reps)[:lanes]
    _, capw, capsteps = ops.dispatch_caps(o, n_dies, ops.count_steps(o))
    with jax.enable_x64(True):
        table = jnp.asarray(ops._tick_table(ops.augment_ops(o, pipelined)))
        tim = jnp.asarray(ops._tick_timing(timing))
        n = jnp.int32(steps)

    def run():
        with jax.enable_x64(True):
            return jax.block_until_ready(ops._core_jit(
                table, n, tim, n_dies=n_dies, capq=capq, capw=capw,
                capsteps=capsteps, pipelined=pipelined, prio=prio,
                lowering=lowering))

    return run


def step_times(args, tables):
    for sched, (o, n_dies, pipelined, timing, prio) in tables.items():
        natural = ops.ring_caps(o, n_dies)[0]
        for capq in args.capq or [natural]:
            capq = max(capq, natural)
            for lanes in args.lanes:
                for lowering in args.lowerings:
                    if lowering == "unrolled" and lanes > args.max_unrolled:
                        continue
                    run = staged(o, n_dies, pipelined, timing, prio, lanes,
                                 capq, args.steps, lowering)
                    t0 = time.perf_counter()
                    run()
                    first = time.perf_counter() - t0
                    walls = []
                    for _ in range(args.repeats):
                        t0 = time.perf_counter()
                        run()
                        walls.append(time.perf_counter() - t0)
                    wall = statistics.median(walls)
                    print(json.dumps({
                        "probe": "step", "scheduler": sched, "lanes": lanes,
                        "maxp": o.shape[1], "capq": capq,
                        "lowering": lowering, "steps": args.steps,
                        "first_s": first, "wall_s": wall,
                        "us_per_step": wall / args.steps * 1e6}), flush=True)


def split(path, steps):
    """µs per step of op execution and of the gaps before each op, over
    the longest ``while`` execution of the trace at ``path``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ev = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ev += [(e.start_ns, e.start_ns + e.duration_ns,
                            e.name.split(" = ", 1)[0][:40])
                           for e in line.events]
    loops = [e for e in ev if e[2].startswith("%while")]
    if not loops:  # XLA:CPU profiles hold no per-op device events
        return {"note": "no while loop among the device's op events"}
    lo, hi, _ = max(loops, key=lambda e: e[1] - e[0])
    inner = sorted(e for e in ev
                   if lo <= e[0] and e[1] <= hi and e not in loops)
    busy, gaps, reach = 0.0, {}, lo
    for s, e, name in inner:
        if s > reach:
            gaps[name] = gaps.get(name, 0.0) + (s - reach)
        busy += max(0.0, e - max(s, reach))
        reach = max(reach, e)
    us = 1e-3 / steps
    top = sorted(gaps.items(), key=lambda g: -g[1])[:4]
    return {"while_us_per_step": (hi - lo) * us,
            "op_busy_us_per_step": busy * us,
            "gap_us_per_step": sum(gaps.values()) * us,
            "events_per_step": len(inner) / steps,
            "largest_gaps_us_per_step": [(n, g * us) for n, g in top]}


def traced(args, tables):
    for sched, (o, n_dies, pipelined, timing, prio) in tables.items():
        capq = ops.ring_caps(o, n_dies)[0]
        for lowering in args.lowerings:
            run = staged(o, n_dies, pipelined, timing, prio, o.shape[0],
                         capq, args.trace_steps, lowering)
            run()
            d = tempfile.mkdtemp(prefix="core-lowering-")
            jax.profiler.start_trace(d)
            run()
            jax.profiler.stop_trace()
            path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
            print(json.dumps({"probe": "split", "scheduler": sched,
                              "lanes": o.shape[0], "maxp": o.shape[1],
                              "capq": capq, "lowering": lowering,
                              "steps": args.trace_steps,
                              **split(path, args.trace_steps)}), flush=True)


def calls(args):
    grid = [OperatingCondition(0.0, 0.0), AGED]
    real = ops.carry_lowering
    for lowering in args.lowerings:
        ops.carry_lowering = lambda platform, lanes: lowering
        try:
            for name, fn in (
                    ("call", lambda: simulate(
                        "websearch", AGED, "pr2ar2", seed=0,
                        n_requests=N_REQUESTS, engine="batched")),
                    ("grid", lambda: simulate_batch(
                        "websearch", grid, seeds=(0,),
                        n_requests=N_REQUESTS, engine="batched"))):
                fn()
                walls = []
                for _ in range(args.repeats):
                    t0 = time.perf_counter()
                    fn()
                    walls.append(time.perf_counter() - t0)
                print(json.dumps({"probe": "call", "call": name,
                                  "lowering": lowering,
                                  "median_s": statistics.median(walls),
                                  "walls_s": walls}), flush=True)
        finally:
            ops.carry_lowering = real


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lanes", type=int, nargs="+",
                    default=[8, 16, 24, 64, 128])
    ap.add_argument("--capq", type=int, nargs="*", default=[],
                    help="forced ring capacities (default: the table's own)")
    ap.add_argument("--lowerings", nargs="+", choices=LOWERINGS,
                    default=["select", "unrolled"])
    ap.add_argument("--schedulers", nargs="+", default=["fcfs", "host_prio"])
    ap.add_argument("--max-unrolled", type=int, default=64,
                    help="skip the unroll above this many lanes")
    ap.add_argument("--steps", type=int, default=4096)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--trace-steps", type=int, default=0)
    ap.add_argument("--calls", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind}),
          flush=True)
    tables = {}
    for sched in args.schedulers:
        (tables[sched],) = capture(sched)
        o, n_dies = tables[sched][:2]
        print(json.dumps({"table": sched, "lanes": o.shape[0],
                          "maxp": o.shape[1], "n_dies": n_dies,
                          "prio": tables[sched][4],
                          "steps": ops.count_steps(o),
                          "capq": ops.ring_caps(o, n_dies)[0]}), flush=True)
    if args.trace_steps:
        traced(args, tables)
    step_times(args, tables)
    if args.calls:
        calls(args)


if __name__ == "__main__":
    main()
