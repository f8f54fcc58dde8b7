"""The plain reference agrees with the program, field for field, on the
CPU at small sizes — and its control, the same computation with
simulated time in float32, does not.  The reference draws its own base
trace with its own generator; the program draws the same base trace
with the program's, so the generators are compared too.  The check
runs the reference that the cell's configuration names."""

import dataclasses
import types

import numpy as np
import pytest

from conftest import gc_config, gc_root
from harness import check, driver, spec


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
    ref = cell.reference
    base = ref.generate_trace(cell.workload, cell.n_requests, trace_seed)
    trace = check.call_trace(base, seed)
    return ref.simulate(cell.drive, trace, cond, mech, seed)


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
    assert check.mismatches(st, ref, cell.reference.STAT_FIELDS) == {}


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


def test_compare_runs_the_named_reference(stub_reference, tmp_path):
    root, b = gc_root(tmp_path, gc_config(stub_reference))
    cell = spec.load_cell("gc.grid", root=root, bench=b)
    cell = dataclasses.replace(cell, config=dict(cell.config, n_requests=40))
    calls = cell.reference.CALLS
    calls.clear()
    stats = types.SimpleNamespace(mean_us=1.0, n_requests=40,
                                  gc_invocations=2, p99_us=-1.0)
    sampled = [check.Sampled(0, 5, "pr2", (365.0, 1000.0), 77, stats)]
    numbers, diffs = check.compare(cell, sampled)
    assert calls == [("host_prio_aged:8", 40, (365.0, 1000.0), "pr2", 77,
                      float)]
    assert numbers == {"mismatched_fields": 1, "cells_checked": 1}
    assert diffs[0]["fields"] == {"gc_invocations": ["2", "1"]}
    numbers, _ = check.compare(cell, sampled, time_dtype=np.float32)
    assert [c[-1] for c in calls[1:]] == [float, np.float32]
    assert numbers["mismatched_fields"] == 0


def _fields(obj):
    return {f.name: getattr(obj, f.name) for f in dataclasses.fields(obj)}


def test_program_config_as_named_before():
    """websearch-8x8's drive and workload reach the program as they did
    when the harness named each workload key."""
    import repro.flashsim as fs
    from repro.core.timing import TimingParams
    from repro.flashsim.config import GCConfig, SSDConfig

    cell = spec.load_cell("websearch.call")
    program = driver.Program(cell)
    d, w = cell.drive, cell.workload
    plain = {k: v for k, v in d.items() if k not in ("timing", "gc")}
    cfg = SSDConfig(timing=TimingParams(**d["timing"]),
                    gc=GCConfig(**d["gc"]), **plain)
    workload = fs.Workload(
        w["name"], read_ratio=w["read_ratio"], iops=w["iops"],
        burstiness=w["burstiness"], mean_pages=w["mean_pages"],
        n_requests=cell.n_requests, span_pages=w["span_pages"])
    assert _fields(program.cfg) == _fields(cfg)
    assert _fields(program.workload) == _fields(workload)


def test_program_takes_a_gc_drive(stub_reference, tmp_path):
    cfg = gc_config(stub_reference, ecc_engines_per_channel=2)
    root, b = gc_root(tmp_path, cfg)
    program = driver.Program(spec.load_cell("gc.grid", root=root, bench=b))
    assert program.cfg.scheduler == "host_prio_aged:8"
    assert program.cfg.ecc_engines_per_channel == 2
    assert (program.cfg.gc.enabled, program.cfg.gc.mode,
            program.cfg.gc.pages_per_block) == (True, "prepass", 16)
    assert program.workload.span_pages == cfg["workload"]["span_pages"]
