"""Chip benchmark of the SSD simulator (``repro.flashsim``).

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the accelerator of this machine:
sets up (imports, characterization, one warm-up call per call shape of
the cell's traffic), drives the run API in a closed loop for
``--seconds``, checks a sample of the window's cells against the plain
reference its configuration names (``bench/reference/<name>.py``), and
prints as its last line one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` a ``breakdown``,
and last ``checks`` (each compared number beside its limit).  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace of
the window's first calls.

Exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell needs, or when the simulator cannot be imported
from ``src/`` beside this directory.  JAX's persistent compilation
cache, the characterization cache and the reference's table cache live
under ``bench/.cache`` in this checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 3


def main(argv=None) -> int:
    args = _args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness

    harness.use_checkout_caches(BENCH)
    from harness import driver, spec

    try:
        cell = spec.load_cell(args.workload, ROOT)
    except spec.SpecError as e:
        return _fail(str(e))
    try:
        import repro.flashsim  # noqa: F401
    except ImportError as e:
        return _fail(f"the simulator is not importable from src/: {e}")
    import jax

    try:
        device = driver.require_chips(jax, cell.chips)
    except driver.NoChip as e:
        return _fail(str(e))
    result = driver.run_cell(cell, args.seed, args.seconds,
                             bool(args.trace), T_START, device)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (passes if "
              f"{c['pass_if']} {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
