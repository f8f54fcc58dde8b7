"""The plain reference agrees with the program, field for field, on the
CPU at small sizes — and its control, the same computation with
simulated time in float32, does not.  The reference draws its own base
trace with its own generator; the program draws the same base trace
with the program's, so the generators are compared too."""

import dataclasses

import numpy as np
import pytest

from harness import check, driver, spec
from reference import sim as refsim


def _cell(name, n):
    cell = spec.load_cell(name)
    return dataclasses.replace(cell, config=dict(cell.config, n_requests=n))


def _program_stats(cell, trace_seed, cond, mech, seed):
    program = driver.Program(cell)
    call = spec.Call((cond,), (mech,))
    return program.fs.simulate(program.source(trace_seed),
                               program.conditions(call)[0], mech, seed=seed,
                               cfg=program.cfg, engine="array")


def _ref_stats(cell, trace_seed, cond, mech, seed):
    base = refsim.generate_trace(cell.workload, cell.n_requests, trace_seed)
    trace = refsim.call_trace(base, seed)
    return refsim.simulate(cell.drive, trace, cond, mech, seed)


CASES = [
    ("websearch.grid", 1500, 2 ** 33 + 12345, (0.0, 0.0), "baseline"),
    ("websearch.grid", 1500, 0, (365.0, 1000.0), "sota+pr2ar2"),
    ("websearch.grid", 1500, 1, (365.0, 1000.0), "ar2"),
    ("websearch.grid", 1500, 2 ** 31 + 7, (0.0, 0.0), "pr2"),
    ("websearch.call", 1500, 17, (365.0, 1000.0), "pr2ar2"),
]


@pytest.mark.parametrize("name,n,trace_seed,cond,mech", CASES)
def test_reference_matches_program(name, n, trace_seed, cond, mech):
    cell = _cell(name, n)
    seed = 2 ** 40 + 3
    ref = _ref_stats(cell, trace_seed, cond, mech, seed)
    st = _program_stats(cell, trace_seed, cond, mech, seed)
    assert check.mismatches(st, ref) == {}


@pytest.mark.parametrize("name,n,trace_seed,cond,mech", CASES[1:4])
def test_control_fails(name, n, trace_seed, cond, mech):
    cell = _cell(name, n)
    seed = 2 ** 33 + 777
    st = _program_stats(cell, trace_seed, cond, mech, seed)
    sampled = [check.Sampled(0, trace_seed, mech, cond, seed, st)]
    sound, _ = check.compare(cell, sampled)
    control, diffs = check.compare(cell, sampled, time_dtype=np.float32)
    assert check.within(sound["mismatched_fields"],
                        check.LIMITS["mismatched_fields"])
    assert not check.within(control["mismatched_fields"],
                            check.LIMITS["mismatched_fields"])
    assert "mean_us" in diffs[0]["fields"]
