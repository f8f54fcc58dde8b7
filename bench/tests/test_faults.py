"""A whole run, minus the look for a chip, with the timed path broken
underneath: ``correct`` must come out false for each fault a cell can
have, and true when nothing is broken.

The faults: a lockstep step that returns its state unchanged (the core
hands back its initial state); half of the work left out (each call
simulates the first half of its trace, the statistics taken over it);
an answer altered where it is produced (one statistic moved by one
tick).  The exchange between chips has no counterpart: every cell runs
on one chip.
"""

import dataclasses
import json
import time

import numpy as np
import pytest

from harness import driver, spec

SIZES = {"websearch.grid": 200, "websearch.call": 200}


def _cell(name):
    cell = spec.load_cell(name)
    cfg = dict(cell.config, n_requests=SIZES[name])
    return dataclasses.replace(cell, config=cfg)


def _run(name, seconds=0.2, log=lambda *_: None):
    import jax

    return driver.run_cell(_cell(name), 2 ** 31 + 99, seconds, False,
                           time.perf_counter(), driver.device_info(jax),
                           log=log)


def _unchanged_state(monkeypatch):
    from repro.kernels.fcfs_core import ops

    def initial_state(table, steps, timing, *, n_dies, capsteps, **_):
        L, maxp = table.shape[0], table.shape[1]
        log = np.concatenate([np.zeros((capsteps, L), np.int64),
                              np.full((capsteps, L), maxp, np.int64)], 1)
        return (log, np.zeros((L, n_dies, 2), np.int64),
                np.zeros((L, 4), np.int64))

    monkeypatch.setattr(ops, "_core_jit", initial_state)


def _half_the_work(monkeypatch):
    from repro.flashsim import ssd
    from repro.flashsim.workloads import RequestTrace

    resolve = ssd.resolve_trace

    def first_half(*a, **kw):
        t = resolve(*a, **kw)
        h = len(t) // 2
        return RequestTrace(t.arrival_us[:h], t.is_read[:h], t.n_pages[:h],
                            t.start_page[:h])

    monkeypatch.setattr(ssd, "resolve_trace", first_half)


def _altered_answer(monkeypatch):
    from repro.flashsim import ssd

    finalize = ssd.SSDSim._finalize

    def one_tick_off(self, prep, res):
        st = finalize(self, prep, res)
        st.p99_us += 2.0 ** -10
        return st

    monkeypatch.setattr(ssd.SSDSim, "_finalize", one_tick_off)


FAULTS = {"unchanged_state": _unchanged_state,
          "half_the_work": _half_the_work,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("name", sorted(SIZES))
def test_sound_run_is_correct(name):
    res = _run(name)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["mismatched_fields"]["value"] == 0
    assert set(res["metrics"]) == {m["name"]
                                   for m in _cell(name).end_to_end}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", sorted(SIZES))
def test_fault_is_caught(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = _run(name)
    assert res["correct"] is False
    assert res["checks"]["mismatched_fields"]["value"] > 0 or \
        res["failed"] > 0


def test_window_takes_the_pool_in_turn():
    lines = []
    res = _run("websearch.grid", seconds=2.0, log=lines.append)
    assert res["correct"] is True
    detail = next(json.loads(x) for x in lines if '"detail"' in x)
    cell = _cell("websearch.grid")
    pool = cell.trace_seeds
    assert len(pool) > 1
    rounds = [c["trace_seed"] for c in detail["calls"][::len(cell.calls)]]
    assert len(rounds) >= 3
    first = pool.index(rounds[0])
    assert rounds == [pool[(first + r) % len(pool)]
                      for r in range(len(rounds))]
