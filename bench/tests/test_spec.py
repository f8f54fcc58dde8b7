"""Configuration, traffic and metric files are found by name and
validated; BENCHMARK.json keeps to the shape the harness reads."""

import copy
import json
import os
import re

import pytest

from conftest import gc_config, gc_root
from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench=bench)
        assert cell.calls and cell.n_requests > 0
        assert cell.api in spec.APIS
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_names_units_and_files(bench):
    names = [c["name"] for c in bench["configs"]] + \
        [w["name"] for w in bench["workloads"]] + \
        [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for c in bench["configs"]:
        assert c["file"].startswith("bench/")
        assert os.path.isfile(os.path.join(spec.ROOT_DIR, c["file"]))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.isfile(spec.reader_path(m["name"])), m["name"]


def test_per_layer_moves_a_reported_metric(bench):
    for w in bench["workloads"]:
        e2e, layer = spec.metrics_of(bench, w["name"])
        reported = {m["name"] for m in e2e}
        for m in layer:
            assert m["moves"] in reported, (w["name"], m["name"])


def test_metrics_follow_their_workloads(bench):
    e2e, layer = spec.metrics_of(bench, "websearch.call")
    assert {m["name"] for m in e2e} == {"call_p50_s", "setup_s"}
    assert "setup_char_s" in {m["name"] for m in layer}
    assert "core_ms_per_cell.grid" not in {m["name"] for m in layer}


def test_missing_cell_and_files_are_refused(bench, tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_cell("no.such.cell", bench=bench)
    b = copy.deepcopy(bench)
    b["workloads"][0]["traffic"] = "no-such-traffic"
    with pytest.raises(spec.SpecError, match="missing file"):
        spec.load_cell(b["workloads"][0]["name"], bench=b)


def _config(name):
    with open(os.path.join(spec.BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_config_validation():
    good = _config("websearch-8x8")
    spec.validate_config("websearch-8x8", good)
    for key, value in (("scheduler", "host_prio_aged:8"),
                       ("gc", {"enabled": True, "mode": "prepass"})):
        bad = copy.deepcopy(good)
        bad["drive"][key] = value
        with pytest.raises(spec.SpecError, match="FIFO"):
            spec.validate_config("websearch-8x8", bad)
    bad = copy.deepcopy(good)
    bad["drive"]["bogus"] = 1
    with pytest.raises(spec.SpecError, match="unknown keys"):
        spec.validate_config("websearch-8x8", bad)
    bad = copy.deepcopy(good)
    del bad["workload"]["iops"]
    with pytest.raises(spec.SpecError, match="missing keys"):
        spec.validate_config("websearch-8x8", bad)
    with pytest.raises(spec.SpecError, match="names"):
        spec.validate_config("other-8x8", good)


def test_traffic_validation():
    ok = {"api": "simulate", "trace_seeds": [0, 1], "calls": [
        {"conditions": [[365, 1000]], "mechanisms": ["pr2"]}]}
    (call,) = spec.parse_traffic("t", ok)
    assert call.conditions == ((365.0, 1000.0),) and call.n_cells == 1
    two = dict(ok, calls=[
        {"conditions": [[0, 0], [365, 1000]], "mechanisms": ["pr2"]}])
    with pytest.raises(spec.SpecError, match="one condition"):
        spec.parse_traffic("t", two)
    with pytest.raises(spec.SpecError, match="api"):
        spec.parse_traffic("t", {"api": "compare", "calls": ok["calls"]})
    with pytest.raises(spec.SpecError, match="no calls"):
        spec.parse_traffic("t", {"api": "simulate", "calls": []})
    for seeds in (None, [], [-1], [3, 3], [1.5]):
        with pytest.raises(spec.SpecError, match="trace_seeds"):
            spec.parse_traffic("t", dict(ok, trace_seeds=seeds))


def test_named_reference_loads(stub_reference, tmp_path):
    root, b = gc_root(tmp_path, gc_config(stub_reference))
    cell = spec.load_cell("gc.grid", root=root, bench=b)
    assert cell.reference.__name__ == "reference.prio_gc"
    assert cell.drive["scheduler"] == "host_prio_aged:8"
    assert cell.drive["gc"]["enabled"] is True
    assert spec.load_cell("websearch.grid").reference.__name__ == \
        "reference.sim"


@pytest.mark.parametrize("reference", [None, "sim"])
def test_gc_drive_refused_by_sim(reference, tmp_path):
    root, b = gc_root(tmp_path, gc_config(reference))
    with pytest.raises(spec.SpecError, match="FIFO"):
        spec.load_cell("gc.grid", root=root, bench=b)


@pytest.mark.parametrize("name", [
    "no_such_reference", "../reference/sim", "reference/sim", "..", "./sim",
    "/sim", "Sim", "sim\n", "", 3, "nand", "__init__"])
def test_reference_name_refused(name, stub_reference):
    with pytest.raises(spec.SpecError, match="no reference|lacks"):
        spec.validate_config("gc-stub", gc_config(name))


@pytest.mark.parametrize("where,key", [
    ("drive", "bogus"), ("workload", "bogus"), ("gc", "bogus"),
    ("drive", "ncq_depth")])
def test_undeclared_keys_refused(where, key, stub_reference):
    cfg = gc_config(stub_reference, ecc_engines_per_channel=2)
    assert spec.validate_config("gc-stub", cfg).__name__ == \
        "reference.prio_gc"
    part = cfg["drive"]["gc"] if where == "gc" else cfg[where]
    part[key] = 1
    with pytest.raises(spec.SpecError, match="unknown keys"):
        spec.validate_config("gc-stub", cfg)


def test_gc_keys_are_gcconfig_fields():
    """spec names GCConfig's fields without importing the program; the
    two lists stay one."""
    import dataclasses

    from repro.flashsim.config import GCConfig

    assert spec._GC_KEYS == {f.name for f in dataclasses.fields(GCConfig)}
