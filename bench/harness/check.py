"""Whether what the timed path produced is correct: every statistic of a
sample of the window's cells against the plain reference that the
cell's configuration names (``cell.reference``), exactly.

The number compared is ``mismatched_fields``: over the sampled cells,
how many (cell, statistic) pairs differ from the reference.  Its limit
is 0: the simulator's statistics are exact on the 2**-10 µs grid, so a
sound run matches in every field.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

# The deal of a call's requests to its base trace's arrivals belongs to
# the traffic, not to the reference that judges it: the program's trace
# and every reference's are dealt by this one function.
from reference.sim import call_trace

#: Each compared number's limit, as (comparison that passes, limit).
#: ``mismatched_fields`` is an exact comparison; at least one cell must
#: have been checked.
LIMITS = {"mismatched_fields": ("<=", 0), "cells_checked": (">=", 1)}


def within(value, limit) -> bool:
    op, bound = limit
    return value <= bound if op == "<=" else value >= bound


@dataclasses.dataclass
class Sampled:
    """One window cell picked for the comparison."""

    call: int
    trace_seed: int
    mechanism: str
    condition: Tuple[float, float]
    seed: int
    stats: object          # the program's SimStats


def sample_cells(calls, seed: int) -> List[Sampled]:
    """From the completed calls, one cell of each (condition, mechanism)
    the traffic runs, each from a call drawn from ``seed``, and the cell
    with the most simulated events."""
    rng = np.random.default_rng([seed, 0x5A3])
    by_key: Dict[tuple, list] = {}
    for rec in calls:
        for (mech, cond, s), st in rec.results.items():
            by_key.setdefault((cond, mech), []).append(
                Sampled(rec.index, rec.trace_seed, mech, cond, s, st))
    out = []
    for key in sorted(by_key):
        cands = by_key[key]
        out.append(cands[int(rng.integers(len(cands)))])
    everything = [c for cands in by_key.values() for c in cands]
    if everything:
        longest = max(everything,
                      key=lambda c: (c.stats.fast_path_events, -c.call))
        if all((c.call, c.mechanism, c.condition) !=
               (longest.call, longest.mechanism, longest.condition)
               for c in out):
            out.append(longest)
    return out


def mismatches(program_stats, reference: dict,
               fields) -> Dict[str, tuple]:
    """Statistics, of ``fields``, on which the program and the reference
    differ."""
    out = {}
    for f in fields:
        got = getattr(program_stats, f, None)
        if got != reference[f]:
            out[f] = (got, reference[f])
    return out


def compare(cell, sampled: List[Sampled], time_dtype=float):
    """Run the reference on each sampled cell (its own base trace from
    the call's trace seed, dealt in the order the call's seed draws);
    return the compared numbers and the per-cell differences.
    ``time_dtype`` other than float runs the reference in a narrower
    precision (the control) and compares that with the reference
    instead."""
    refmod = cell.reference
    bases = {}
    diffs = []
    for c in sampled:
        if c.trace_seed not in bases:
            bases[c.trace_seed] = refmod.generate_trace(
                cell.workload, cell.n_requests, c.trace_seed)
        trace = call_trace(bases[c.trace_seed], c.seed)
        ref = refmod.simulate(cell.drive, trace, c.condition, c.mechanism,
                              c.seed)
        if time_dtype is not float:
            got = refmod.simulate(cell.drive, trace, c.condition,
                                  c.mechanism, c.seed,
                                  time_dtype=time_dtype)
            bad = {f: (got[f], ref[f]) for f in refmod.STAT_FIELDS
                   if got[f] != ref[f]}
        else:
            bad = mismatches(c.stats, ref, refmod.STAT_FIELDS)
        if bad:
            diffs.append({"call": c.call, "trace_seed": c.trace_seed,
                          "mechanism": c.mechanism,
                          "condition": list(c.condition), "seed": c.seed,
                          "fields": {k: [repr(a), repr(b)]
                                     for k, (a, b) in bad.items()}})
    numbers = {
        "mismatched_fields": sum(len(d["fields"]) for d in diffs),
        "cells_checked": len(sampled),
    }
    return numbers, diffs


def verdict(numbers: dict) -> bool:
    return all(within(numbers[k], lim) for k, lim in LIMITS.items())
