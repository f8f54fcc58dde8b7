"""Readings that set the limit of the correctness check: the compared
number of sound runs of the program, and of the control, over many
seeds, in one process.

    python3 bench/readings.py --workload <cell> --seeds <first> --count <k>

For each seed it makes one round of the cell's calls (each call shape
once, as a run's window does, at the cell's own size) on the chip, on a
base trace of its own (the seed is also its trace seed, so the readings
cover as many base traces as seeds, beyond the traffic's pool), draws
the sample a run would compare, and prints one JSON line with
``mismatched_fields`` of the program against the reference (the sound
reading) and of the control against the reference.  The control is the
reference itself with simulated time held in float32, the precision
below the float64 microseconds on the 2**-10 µs grid that the
configurations state.  The limit lies between the largest sound reading
and the smallest control reading.  Exits non-zero without a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, required=True,
                    help="first seed; the next --count - 1 follow")
    ap.add_argument("--count", type=int, default=12)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness

    harness.use_checkout_caches(BENCH)
    import numpy as np
    import jax

    from harness import check, driver, spec

    cell = spec.load_cell(args.workload, ROOT)
    try:
        device = driver.require_chips(jax, cell.chips)
    except driver.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    sound_max, control_min = 0, None
    program = driver.Program(cell)
    program.prewarm()
    for seed in range(args.seeds, args.seeds + args.count):
        calls = []
        for k, call in enumerate(cell.calls):
            s = driver.call_seed(seed, 1, k)
            a = time.perf_counter()
            res = program.run(call, seed, s)
            calls.append(driver.CallRecord(k, seed, s, a,
                                           time.perf_counter(),
                                           call.n_cells, res))
        sampled = check.sample_cells(calls, seed)
        sound, diffs = check.compare(cell, sampled)
        control, _ = check.compare(cell, sampled, time_dtype=np.float32)
        sound_max = max(sound_max, sound["mismatched_fields"])
        c = control["mismatched_fields"]
        control_min = c if control_min is None else min(control_min, c)
        print(json.dumps({"seed": seed, "cells": len(sampled),
                          "sound": sound["mismatched_fields"],
                          "control": c, "device": device["kind"],
                          "diffs": diffs[:3]}), flush=True)
    print(json.dumps({"workload": cell.name, "seeds": args.count,
                      "lower_reading": sound_max,
                      "upper_reading": control_min,
                      "limit": check.LIMITS["mismatched_fields"][1]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
