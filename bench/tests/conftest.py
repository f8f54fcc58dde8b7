"""CPU rehearsal tests of the benchmark harness.

Run from the repository root:  python -m pytest bench/tests
They use JAX's CPU backend and never look for a chip.
"""

import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def pytest_configure(config):
    # The reference keeps its characterization tables out of the
    # checkout's own cache while tests run.
    import tempfile

    os.environ.setdefault("BENCH_REFERENCE_CACHE_DIR",
                          tempfile.mkdtemp(prefix="bench-ref-tables-"))


STUB_DIR = os.path.join(BENCH, "tests", "stub_reference")


@pytest.fixture
def stub_reference(monkeypatch):
    """The test-only reference ``prio_gc`` (``tests/stub_reference``),
    found as ``bench/reference/prio_gc.py`` would be, for one test."""
    import reference

    monkeypatch.setattr(reference, "__path__",
                        list(reference.__path__) + [STUB_DIR])
    yield "prio_gc"
    sys.modules.pop("reference.prio_gc", None)


def gc_config(reference=None, **drive):
    """The websearch-8x8 configuration on a drive with prepass GC and
    aged host-priority rings, naming ``reference``."""
    with open(os.path.join(BENCH, "configs", "websearch-8x8.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "gc-stub"
    if reference is not None:
        cfg["reference"] = reference
    cfg["drive"].update(scheduler="host_prio_aged:8",
                        gc={"enabled": True, "mode": "prepass",
                            "pages_per_block": 16}, **drive)
    return cfg


def gc_root(tmp_path, cfg):
    """A checkout root holding ``cfg`` as the configuration of one new
    cell, ``gc.grid``, beside the benchmark's own traffic and metric
    files; returns (root, BENCHMARK.json's content)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for d in ("workloads", "metrics"):
        shutil.copytree(os.path.join(BENCH, d), tmp_path / "bench" / d)
    (tmp_path / "bench" / "configs").mkdir()
    path = "bench/configs/gc-stub.json"
    (tmp_path / path).write_text(json.dumps(cfg))
    bench["configs"].append({"name": "gc-stub", "source": "test",
                             "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "gc.grid", "config": "gc-stub",
                               "traffic": "sweep.6x2", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "websearch.grid" in m.get("workloads", ()):
            m["workloads"].append("gc.grid")
    return str(tmp_path), bench
