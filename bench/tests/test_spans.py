"""The span metrics (``harness.spans`` and its readers) on the CPU at a
small size: a window of real calls, then the replay of its first round
with the program's recorder on."""

import dataclasses
import sys

import pytest

from harness import driver, spans, spec

READERS = ("host_ms_per_cell.call", "host_ms_per_cell.grid",
           "core_wall_ms_per_cell.grid", "useful_lane_step_pct.grid")


def _window(name, n, calls):
    """A Context over ``calls`` calls of cell ``name`` at ``n`` requests
    per cell, made as the window makes them."""
    cell = spec.load_cell(name)
    cell = dataclasses.replace(cell, config=dict(cell.config, n_requests=n))
    program = driver.Program(cell)
    pool, shapes = cell.trace_seeds, len(cell.calls)
    recs = []
    for i in range(calls):
        ts = pool[(i // shapes) % len(pool)]
        s = driver.call_seed(2 ** 40 + 9, 1, i)
        res = program.run(cell.calls[i % shapes], ts, s)
        recs.append(driver.CallRecord(i, ts, s, 0.0, 1.0,
                                      cell.calls[i % shapes].n_cells, res))
    return driver.Context(cell=cell, calls=recs, window=(0.0, 1.0),
                          setup={}, counters={}, trace=None, traced_calls=[])


@pytest.fixture(scope="module")
def call_ctx():
    return _window("websearch.call", 200, 5)


@pytest.fixture(scope="module")
def grid_ctx():
    return _window("websearch.grid", 200, 3)


def _read(name, ctx):
    return driver._load_reader(name)(ctx)


def test_call_cell_replays_its_first_round(call_ctx, capsys):
    r = spans.replay(call_ctx)
    line = capsys.readouterr().out
    assert '"spans"' in line and '"identical": true' in line
    # One call of each of the four call shapes on the one pool member.
    assert len(r.walls_s) == 4 and r.cells == 4
    assert r.counters["dispatches"] == 4
    assert r.summary["flashsim.call"][0] == 4
    assert all(c > 0.0 for c in r.core_ms)
    # The spans account for the calls the host clock timed.
    assert r.total_ms("flashsim.call") <= 1e3 * sum(r.walls_s)
    assert spans.replay(call_ctx) is r            # made once per run
    want = (r.total_ms("flashsim.call") - r.total_ms("flashsim.core")) / 4
    assert _read("host_ms_per_cell.call", call_ctx) == want > 0.0


def test_grid_readers(grid_ctx):
    r = spans.replay(grid_ctx)
    # One call on each of the two pool members, 12 cells each.
    assert len(r.walls_s) == 2 and r.cells == 24
    host = _read("host_ms_per_cell.grid", grid_ctx)
    core = _read("core_wall_ms_per_cell.grid", grid_ctx)
    useful = _read("useful_lane_step_pct.grid", grid_ctx)
    assert host > 0.0 and core > 0.0
    assert (host + core) * 24 == pytest.approx(
        r.total_ms("flashsim.call"))
    assert core * 24 == pytest.approx(sum(r.core_ms))
    assert 0.0 < useful <= 100.0
    c = r.counters
    assert useful == 100.0 * c["lane_steps_useful"] / c["lane_steps_run"]


def test_nothing_without_the_recorder(grid_ctx, monkeypatch):
    ctx = dataclasses.replace(grid_ctx)           # a run of its own
    monkeypatch.setitem(sys.modules, "repro.flashsim.obs", None)
    assert all(_read(n, ctx) is None for n in READERS)


def test_nothing_when_a_replayed_cell_differs(call_ctx):
    first = call_ctx.calls[0]
    (key, st), = first.results.items()
    other = dataclasses.replace(st, mean_us=st.mean_us + 1.0)
    calls = [dataclasses.replace(first, results={key: other})] + \
        call_ctx.calls[1:]
    ctx = dataclasses.replace(call_ctx, calls=calls)
    assert _read("host_ms_per_cell.call", ctx) is None


def test_nothing_when_the_replay_raises(call_ctx, monkeypatch, capsys):
    def boom(*_):
        raise RuntimeError("lost the device")
    monkeypatch.setattr(driver.Program, "run", boom)
    ctx = dataclasses.replace(call_ctx)           # a run of its own
    assert all(_read(n, ctx) is None for n in READERS)
    assert "RuntimeError: lost the device" in capsys.readouterr().out


def test_nothing_when_a_window_call_failed(call_ctx):
    calls = [dataclasses.replace(call_ctx.calls[0], results={},
                                 error="RuntimeError: x")] + \
        call_ctx.calls[1:]
    ctx = dataclasses.replace(call_ctx, calls=calls)
    assert _read("host_ms_per_cell.call", ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_each_reader_belongs_to_its_cells(name):
    cell = "websearch." + name.rsplit(".", 1)[1]
    assert name in [m["name"] for m in spec.load_cell(cell).per_layer]
