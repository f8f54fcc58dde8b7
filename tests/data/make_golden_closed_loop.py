"""Regenerate tests/data/golden_closed_loop.json.

The golden file pins the OPEN-LOOP (``ncq_depth=None``) output of the
simulator across the scheduler x GC x faults matrix so that the
closed-loop frontend (PR 7) can assert bit-parity: with the NCQ knob
left at its default, every stat that existed before the closed-loop
code landed must be byte-identical.

Run from the repo root (only when the open-loop contract legitimately
changes, which should essentially never happen):

    PYTHONPATH=src JAX_PLATFORMS=cpu python tests/data/make_golden_closed_loop.py

It prints how many pinned values moved and the largest relative change.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

from make_golden_workloads import FIELDS, moved
from repro.flashsim import FaultConfig, OperatingCondition, simulate

OUT = pathlib.Path(__file__).resolve().parent / "golden_closed_loop.json"

N = 600
SEED = 0
COND = OperatingCondition(retention_days=365.0, pec=1000.0)

SCHEDULERS = ("fcfs", "host_prio", "host_prio_aged:8", "tokens:4,2", "preempt")
GC_MODES = ("off", "prepass", "online")
FAULTS = {
    "none": None,
    "fc": FaultConfig(
        uncorrectable_prob=0.02, mispredict_scale=4.0, escalation_attempts=2,
    ),
}


#: The pinned SimStats fields: the plain and GC block plus the fault
#: block (the closed-loop block is asserted zero by the test instead).
PINNED = FIELDS + (
    "mispredicted_reads", "rescued_reads", "parity_rebuilds",
    "rebuild_reads", "retired_blocks", "program_fails", "erase_fails",
    "unrecoverable", "recovery_p99_us",
)


def pinned(stats) -> dict:
    d = dataclasses.asdict(stats)
    return {f: d[f] for f in PINNED}


def cell_key(mech: str, sched: str, gc: str, faults: str) -> str:
    return f"{mech}|{sched}|{gc}|{faults}"


def main() -> None:
    cells = {}
    for sched in SCHEDULERS:
        for gc in GC_MODES:
            for fname, fc in FAULTS.items():
                stats = simulate(
                    "prn", COND, "pr2ar2", seed=SEED, n_requests=N,
                    scheduler=sched, gc=gc, faults=fc,
                )
                cells[cell_key("pr2ar2", sched, gc, fname)] = pinned(stats)
    # A couple of baseline-mechanism / read-heavy cells so the pin is not
    # pr2ar2-only.
    for mech in ("baseline", "sota+pr2ar2"):
        stats = simulate(
            "websearch", COND, mech, seed=SEED, n_requests=N,
            scheduler="fcfs", gc="off",
        )
        cells[cell_key(mech, "fcfs", "off", "none")] = pinned(stats)

    payload = {
        "meta": {
            "workload": "prn",
            "extra_workload": "websearch",
            "n_requests": N,
            "seed": SEED,
            "condition": {"retention_days": COND.retention_days,
                          "pec": COND.pec},
            "schedulers": list(SCHEDULERS),
            "gc_modes": list(GC_MODES),
            "fault_configs": {
                "none": None,
                "fc": {"uncorrectable_prob": 0.02, "mispredict_scale": 4.0,
                       "escalation_attempts": 2},
            },
        },
        "cells": cells,
    }
    if OUT.exists():
        n, worst = moved(json.loads(OUT.read_text()), payload)
        print(f"{n} pinned values moved; largest relative change {worst!r}")
    OUT.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    print(f"wrote {OUT} ({len(cells)} cells)")


if __name__ == "__main__":
    main()
