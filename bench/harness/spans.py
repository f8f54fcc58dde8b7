"""Per-layer numbers from the program's own spans and counters
(``repro.flashsim.obs``).

The window runs with the program's recorder off, so nothing it times
pays for spans.  After the window, the first reader of a span metric
replays the window's first round of calls (one call of each call shape
on each member of the traffic's trace pool, each with the trace seed
and call seed the window gave it) inside ``obs.recording()``, once per
run, and every span metric reads that recording.  The trace cache is
emptied before each replayed call, so the call builds its trace as the
window's call did, and each replayed cell must return the statistics
its window cell returned.  With a program that has no recorder, or when
a replayed call raises or differs, nothing is read.

The run prints the replay's per-span count, total and self milliseconds,
its counters and its calls' wall seconds as one ``spans`` line.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import time
from typing import Dict, List, Optional


@dataclasses.dataclass
class Replay:
    cells: int                        # cells of the replayed calls
    summary: Dict[str, tuple]         # name -> (count, total ms, self ms)
    counters: Dict[str, int]
    walls_s: List[float]              # host clock around each call
    core_ms: List[float]              # flashsim.core of each call

    def total_ms(self, name: str) -> float:
        return self.summary.get(name, (0, 0.0, 0.0))[1]


_REPLAYS: Dict[int, Optional[Replay]] = {}


def replay(ctx) -> Optional[Replay]:
    """The replay of ``ctx``'s first round of calls (made once)."""
    key = id(ctx)
    if key not in _REPLAYS:
        _REPLAYS[key] = _replay(ctx)
    return _REPLAYS[key]


def _first_round(ctx):
    n = len(ctx.cell.calls) * len(ctx.cell.trace_seeds)
    return ctx.calls[:n]


def _replay(ctx, log=print) -> Optional[Replay]:
    try:
        obs = importlib.import_module("repro.flashsim.obs")
    except ImportError:
        return None
    from repro.flashsim.workloads.base import clear_trace_cache

    from harness.driver import Program

    calls = _first_round(ctx)
    if not calls or any(rec.error for rec in calls):
        return None
    walls, same = [], True
    try:
        program = Program(ctx.cell)
        with obs.recording() as rec:
            for c in calls:
                clear_trace_cache()
                a = time.perf_counter()
                res = program.run(
                    ctx.cell.calls[c.index % len(ctx.cell.calls)],
                    c.trace_seed, c.seed)
                walls.append(time.perf_counter() - a)
                same = same and res == c.results
    except Exception as e:        # a reader reads nothing, never fails
        log(json.dumps({"spans": {"error": f"{type(e).__name__}: {e}"}}))
        return None
    core_ms = [0.0] * len(calls)
    for s in rec.spans:
        if s.name == "flashsim.core":
            core_ms[s.call] += s.ms
    out = Replay(cells=sum(len(c.results) for c in calls),
                 summary=rec.summary(), counters=rec.counters,
                 walls_s=walls, core_ms=core_ms)
    log(json.dumps({"spans": {
        "replayed_calls": [c.index for c in calls], "cells": out.cells,
        "identical": same, "walls_s": walls, "core_ms": core_ms,
        "by_name": out.summary, "counters": out.counters}}))
    return out if same else None


def host_ms_per_cell(ctx) -> Optional[float]:
    """Milliseconds per cell of the replayed calls that no
    ``flashsim.core`` span covers: (Σ flashsim.call − Σ flashsim.core)
    ÷ cells."""
    r = replay(ctx)
    if r is None or not r.cells or "flashsim.call" not in r.summary:
        return None
    return (r.total_ms("flashsim.call") - r.total_ms("flashsim.core")) \
        / r.cells
