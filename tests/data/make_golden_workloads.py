"""Regenerate tests/data/golden_workloads.json.

The golden file pins two things ``tests/test_workloads.py`` checks:

  * ``trace_sha`` — checksums of every synthetic profile's trace at
    seeds 0-4 (the workload generator's bit-parity contract);
  * ``compare_plain`` / ``compare_gc_prepass`` — the ``SimStats`` of one
    plain and one prepass-GC ``compare_mechanisms`` cell.

Run from the repo root only when simulated results legitimately change:

    PYTHONPATH=src JAX_PLATFORMS=cpu python tests/data/make_golden_workloads.py

The script prints how many pinned values moved and the largest relative
change, so the regeneration can be recorded.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import pathlib

import numpy as np

from repro.flashsim import OperatingCondition, compare_mechanisms
from repro.flashsim.workloads import (GC_PROFILES, PROFILES, generate_trace,
                                      make_workloads)

OUT = pathlib.Path(__file__).resolve().parent / "golden_workloads.json"
AGED = OperatingCondition(365.0, 1000.0)
FIELDS = ("mean_us", "p50_us", "p95_us", "p99_us", "read_mean_us",
          "n_requests", "mean_read_attempts", "die_util", "channel_util",
          "read_p99_us", "wa", "gc_invocations", "gc_page_reads",
          "gc_page_progs", "blocks_erased", "gc_suspensions",
          "write_stalls")


def trace_sha(t) -> str:
    """The checksum ``tests/test_workloads.py`` recomputes."""
    h = hashlib.sha256()
    for a in (t.arrival_us, t.is_read, t.n_pages, t.start_page):
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def pinned(grid) -> dict:
    return {m: {f: dataclasses.asdict(st)[f] for f in FIELDS}
            for m, st in grid.items()}


def moved(old, new):
    """(values that changed, largest relative change) from pin ``old``
    to pin ``new``, matched by key."""
    n, worst = 0, 0.0
    for k, a in old.items():
        b = new.get(k)
        if isinstance(a, dict) and isinstance(b, dict):
            m, w = moved(a, b)
            n, worst = n + m, max(worst, w)
        elif a != b:
            n += 1
            if isinstance(a, (int, float)) and isinstance(b, (int, float)) \
                    and a:
                worst = max(worst, abs(b - a) / abs(a))
    return n, worst


def main() -> None:
    wl = make_workloads()
    payload = {
        "trace_sha": {f"{w.name}:{s}": trace_sha(generate_trace(w, seed=s))
                      for w in PROFILES + GC_PROFILES for s in range(5)},
        "compare_plain": pinned(compare_mechanisms(
            dataclasses.replace(wl["websearch"], n_requests=400), AGED,
            mechanisms=("baseline", "pr2ar2"), seed=3)),
        "compare_gc_prepass": pinned(compare_mechanisms(
            dataclasses.replace(wl["prn"], n_requests=1200), AGED,
            mechanisms=("baseline", "pr2ar2"), seed=1, gc="prepass")),
    }
    if OUT.exists():
        n, worst = moved(json.loads(OUT.read_text()), payload)
        print(f"{n} pinned values moved; largest relative change {worst!r}")
    OUT.write_text(json.dumps(payload, indent=1) + "\n")
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
