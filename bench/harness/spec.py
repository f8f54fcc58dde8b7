"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

A cell (an entry of ``workloads``) names a configuration, whose file
holds the drive and the host workload, and a traffic mix, whose file
``bench/workloads/<traffic>.json`` holds the calls one client makes.
Metrics are computed by readers ``bench/metrics/<metric name>.py``, and
the configuration names the reference that checks it,
``bench/reference/<name>.py`` (the contract is in ``reference``'s
docstring).  Everything is found by name; nothing here knows a
particular cell.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import re
import types
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT_DIR = os.path.dirname(BENCH_DIR)

# The core every configuration states; a reference may model further
# drive and workload keys (its DRIVE_KEYS and WORKLOAD_KEYS).
_DRIVE_KEYS = {"n_channels", "dies_per_channel", "page_kib",
               "host_overhead_us", "timing", "scheduler", "gc"}
_TIMING_KEYS = {"tr_us", "tdma_us", "tecc_us", "tprog_us"}
_WORKLOAD_KEYS = {"name", "read_ratio", "iops", "burstiness", "mean_pages",
                  "span_pages"}
# The fields of the program's GCConfig (repro.flashsim.config), named
# here so that checking a configuration imports nothing of the program.
_GC_KEYS = {"enabled", "mode", "watermark_blocks", "op_ratio",
            "pages_per_block", "blocks_per_die", "gc_threshold_blocks",
            "t_erase_us", "pec_per_erase"}
_DEFAULT_REFERENCE = "sim"
_REFERENCE_NAME = re.compile(r"[a-z_][a-z0-9_]*")
_REFERENCE_API = ("STAT_FIELDS", "generate_trace", "simulate",
                  "DRIVE_KEYS", "WORKLOAD_KEYS", "accepts")
APIS = ("simulate_batch", "simulate")


class SpecError(ValueError):
    """A benchmark file is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Call:
    """One call shape of a traffic mix: its conditions ((retention days,
    P/E) pairs) and mechanisms."""

    conditions: tuple
    mechanisms: tuple

    @property
    def n_cells(self) -> int:
        return len(self.conditions) * len(self.mechanisms)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One benchmark cell, resolved from its files."""

    name: str
    chips: int
    config: dict          # the configuration file
    reference: types.ModuleType   # the reference the configuration names
    traffic: dict         # the traffic file
    calls: tuple          # Call shapes, cycled through by the client
    end_to_end: tuple     # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple

    @property
    def drive(self) -> dict:
        return self.config["drive"]

    @property
    def workload(self) -> dict:
        return self.config["workload"]

    @property
    def n_requests(self) -> int:
        return int(self.config["n_requests"])

    @property
    def api(self) -> str:
        return self.traffic["api"]

    @property
    def trace_seeds(self) -> tuple:
        return tuple(self.traffic["trace_seeds"])


def _load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT_DIR)}")
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT_DIR) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _check_keys(what: str, d: dict, allowed: set, required: set) -> None:
    if not isinstance(d, dict):
        raise SpecError(f"{what} must be an object")
    extra = set(d) - allowed
    missing = required - set(d)
    if extra or missing:
        raise SpecError(f"{what}: unknown keys {sorted(extra)}, "
                        f"missing keys {sorted(missing)}")


def load_reference(name) -> types.ModuleType:
    """The reference module ``bench/reference/<name>.py``, which keeps to
    the contract in ``reference``'s docstring."""
    import reference

    if not isinstance(name, str) or not _REFERENCE_NAME.fullmatch(name) or \
            not any(os.path.isfile(os.path.join(d, f"{name}.py"))
                    for d in reference.__path__):
        raise SpecError(f"no reference {name!r} in bench/reference/")
    mod = importlib.import_module(f"reference.{name}")
    missing = [a for a in _REFERENCE_API if not hasattr(mod, a)]
    if missing:
        raise SpecError(f"reference {name!r} lacks {missing}")
    return mod


def validate_config(name: str, cfg: dict) -> types.ModuleType:
    """A configuration file states a drive and a host workload, and may
    name the reference that checks it (``reference``, default ``sim``).
    Returns that reference's module."""
    if cfg.get("name") != name:
        raise SpecError(f"configuration file of {name!r} names "
                        f"{cfg.get('name')!r}")
    for key in ("source", "drive", "workload", "n_requests", "guarantees"):
        if key not in cfg:
            raise SpecError(f"configuration {name!r} lacks {key!r}")
    ref = load_reference(cfg.get("reference", _DEFAULT_REFERENCE))
    drive = cfg["drive"]
    _check_keys(f"{name}.drive", drive, _DRIVE_KEYS | set(ref.DRIVE_KEYS),
                _DRIVE_KEYS)
    _check_keys(f"{name}.drive.timing", drive["timing"], _TIMING_KEYS,
                _TIMING_KEYS)
    if set(drive["timing"]["tr_us"]) != {"lsb", "csb", "msb"}:
        raise SpecError(f"{name}: tr_us needs lsb, csb and msb")
    _check_keys(f"{name}.drive.gc", drive["gc"], _GC_KEYS, set())
    _check_keys(f"{name}.workload", cfg["workload"],
                _WORKLOAD_KEYS | set(ref.WORKLOAD_KEYS), _WORKLOAD_KEYS)
    reason = ref.accepts(drive, cfg["workload"])
    if reason:
        raise SpecError(f"{name}: {reason}")
    if int(cfg["n_requests"]) < 1:
        raise SpecError(f"{name}: n_requests must be >= 1")
    return ref


def parse_traffic(name: str, traffic: dict) -> tuple:
    """The call shapes of a traffic file, validated."""
    if traffic.get("api") not in APIS:
        raise SpecError(f"traffic {name!r}: api must be one of {APIS}")
    raw = traffic.get("calls")
    if not raw:
        raise SpecError(f"traffic {name!r} has no calls")
    calls = []
    for c in raw:
        _check_keys(f"traffic {name!r} call", c, {"conditions", "mechanisms"},
                    {"conditions", "mechanisms"})
        conds = tuple((float(r), float(p)) for r, p in c["conditions"])
        call = Call(conds, tuple(c["mechanisms"]))
        if not conds or not call.mechanisms:
            raise SpecError(f"traffic {name!r}: a call with no cells")
        if traffic["api"] == "simulate" and call.n_cells != 1:
            raise SpecError(f"traffic {name!r}: a simulate call runs one "
                            f"condition under one mechanism")
        calls.append(call)
    seeds = traffic.get("trace_seeds")
    if not seeds or not isinstance(seeds, list) or \
            not all(isinstance(s, int) and s >= 0 for s in seeds) or \
            len(set(seeds)) != len(seeds):
        raise SpecError(f"traffic {name!r}: trace_seeds must be a list of "
                        f"distinct whole numbers >= 0")
    if float(traffic.get("trace_seconds", 1.0)) <= 0.0:
        raise SpecError(f"traffic {name!r}: trace_seconds must be > 0")
    return tuple(calls)


def metrics_of(bench: dict, cell_name: str):
    """(end_to_end, per_layer) metric entries the cell reports: an
    end-to-end metric without ``workloads`` belongs to every cell; a
    per-layer metric without it belongs to every cell that reports the
    end-to-end metric it moves."""
    e2e = tuple(m for m in bench["end_to_end"]
                if "workloads" not in m or cell_name in m["workloads"])
    names = {m["name"] for m in e2e}
    layer = tuple(m for m in bench["per_layer"]
                  if (cell_name in m["workloads"] if "workloads" in m
                      else m["moves"] in names))
    return e2e, layer


def reader_path(metric_name: str, root: str = ROOT_DIR) -> str:
    return os.path.join(root, "bench", "metrics", f"{metric_name}.py")


def load_cell(name: str, root: str = ROOT_DIR,
              bench: Optional[dict] = None) -> Cell:
    """Resolve the cell ``name`` from ``BENCHMARK.json`` and its files."""
    bench = bench if bench is not None else load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    conf = next((c for c in bench["configs"]
                 if c["name"] == entry["config"]), None)
    if conf is None:
        raise SpecError(f"workload {name!r} names unknown configuration "
                        f"{entry['config']!r}")
    cfg = _load_json(os.path.join(root, conf["file"]))
    ref = validate_config(conf["name"], cfg)
    tpath = os.path.join(root, "bench", "workloads",
                         f"{entry['traffic']}.json")
    traffic = _load_json(tpath)
    calls = parse_traffic(entry["traffic"], traffic)
    e2e, layer = metrics_of(bench, name)
    for m in e2e + layer:
        if not os.path.isfile(reader_path(m["name"], root)):
            raise SpecError(f"metric {m['name']!r} has no reader "
                            f"bench/metrics/{m['name']}.py")
    return Cell(name=name, chips=int(entry["chips"]), config=cfg,
                reference=ref, traffic=traffic,
                calls=calls, end_to_end=e2e, per_layer=layer)
