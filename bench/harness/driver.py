"""One benchmark run of one cell: set-up, the measured window, the
correctness check, and the metrics.

The window is one client in a closed loop: each call to the run API is
issued when the previous one has returned, until ``seconds`` have
passed; the call running then finishes, and the window ends with it, so
every end-to-end metric covers all the work and all the time of the
window.

A traffic names a small pool of trace seeds.  Call ``i`` of the window
takes the pool's members in turn, one per round of the traffic's call
shapes, starting at a member drawn from the run's ``--seed``.  Inside
the call, the run API builds its trace through the program's
``TraceSource`` interface: the program's own generator draws the base
trace from the member's seed, and the harness deals its requests to its
arrivals in an order drawn from the call's seed; the call's attempt
draws come from the call's seed too.  So every run holds the same set
of traces, in another order and with other draws, trace generation runs
inside every call, and set-up, which makes one call of every call shape
on every member of the pool, has compiled every kernel shape the window
runs.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import tempfile
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from harness import check, spec, xplane


class NoChip(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell needs."""


def require_chips(jax, chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX sees no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return device_info(jax)


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileCounter:
    """XLA compiles (including loads from the persistent cache) and
    their seconds, from ``jax.monitoring``."""

    def __init__(self, jax):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.compiles, self.seconds, self.cache_hits)


def call_seed(seed: int, phase: int, index: int) -> int:
    """Trace seed of call ``index`` of a phase (0 warm-up, 1 window):
    62 bits from the run's seed."""
    ss = np.random.SeedSequence([seed, phase, index])
    return int(ss.generate_state(1, np.uint64)[0] >> np.uint64(2))


def first_member(seed: int, pool: int) -> int:
    """The pool member a run's first call takes."""
    return call_seed(seed, 2, 0) % pool


@dataclasses.dataclass
class CallRecord:
    index: int
    trace_seed: int
    seed: int
    t0: float
    t1: float
    n_cells: int                 # cells the call asked for
    results: Dict[tuple, object]
    error: str = ""


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    cell: object
    calls: List[CallRecord]
    window: tuple                # (start, end), host clock seconds
    setup: dict                  # setup_s, char_s, compile_s
    counters: dict               # program counters over the window
    trace: Optional[object]      # xplane.Reduced of the traced calls
    traced_calls: List[CallRecord]


class Program:
    """The system under test, driven through its public run APIs."""

    def __init__(self, cell):
        import repro.flashsim as fs
        from repro.core.timing import TimingParams
        from repro.flashsim.config import GCConfig, SSDConfig
        from repro.kernels.fcfs_core import ops as kops

        self.fs, self.kops, self.cell = fs, kops, cell
        d = cell.drive
        plain = {k: v for k, v in d.items() if k not in ("timing", "gc")}
        self.cfg = SSDConfig(timing=TimingParams(**d["timing"]),
                             gc=GCConfig(**d["gc"]), **plain)
        w = cell.workload
        self.workload = fs.Workload(
            w["name"], n_requests=cell.n_requests,
            **{k: v for k, v in w.items() if k != "name"})
        self.source_type = _call_source(fs)

    def source(self, trace_seed: int):
        """A call's trace source: the run API builds the call's trace
        from it with the call's seed."""
        return self.source_type(self.workload, trace_seed)

    def conditions(self, call):
        return tuple(self.fs.OperatingCondition(r, p)
                     for r, p in call.conditions)

    def prewarm(self) -> int:
        from repro.flashsim.runtime import Cell, prewarm_characterization

        conds = sorted({c for call in self.cell.calls
                        for c in call.conditions})
        mechs = sorted({m for call in self.cell.calls
                        for m in call.mechanisms})
        return prewarm_characterization([Cell(
            "batch", self.workload,
            tuple(self.fs.OperatingCondition(r, p) for r, p in conds),
            tuple(mechs), 0, cfg=self.cfg)])

    def run(self, call, trace_seed: int, seed: int) -> Dict[tuple, object]:
        """One call; results keyed (mechanism, (retention, P/E), seed)."""
        conds = self.conditions(call)
        src = self.source(trace_seed)
        if self.cell.api == "simulate_batch":
            out = self.fs.simulate_batch(
                src, conds, call.mechanisms, seeds=(seed,),
                cfg=self.cfg, engine="batched")
            return {(m, (c.retention_days, c.pec), s): st
                    for (m, c, s), st in out.items()}
        st = self.fs.simulate(src, conds[0], call.mechanisms[0],
                              seed=seed, cfg=self.cfg, engine="batched")
        c = conds[0]
        return {(call.mechanisms[0], (c.retention_days, c.pec), seed): st}

    @property
    def dispatches(self) -> int:
        return self.kops.KERNEL_DISPATCHES


def _call_source(fs):
    """A trace source (the program's ``TraceSource`` interface): the
    program's generator draws the base trace from the pool member's
    seed, dealt in an order drawn from the call's seed by the harness's
    one deal, ``check.call_trace``, whatever reference checks it."""

    class CallTrace(fs.TraceSource):
        def __init__(self, workload, trace_seed):
            self.workload, self.trace_seed = workload, trace_seed

        def _build(self, seed):
            t = fs.generate_trace(self.workload, seed=self.trace_seed)
            arrival, is_read, n_pages, start = check.call_trace(
                (t.arrival_us, t.is_read, t.n_pages, t.start_page), seed)
            return fs.RequestTrace(arrival, is_read, n_pages, start)

        def cache_key(self, seed):
            return ("bench-call", self.workload.name, self.trace_seed, seed)

    return CallTrace


class Tracer:
    """The profiler over the start of the window: the first call whole,
    or, where the traffic gives ``trace_seconds``, that many seconds
    from the first call's start (a profile holds one event per device op
    per lockstep step, so a whole long call would not fit a run).  The
    window issues no call while the profiler is stopping: collecting the
    events is slow, and slower still beside a running call.  A
    ``bench.anchor`` mark ties the host clock to the trace's."""

    OPTIONS = {"python_tracer_level": 0, "enable_hlo_proto": False}

    def __init__(self, jax, cell):
        self.jax, self.cell = jax, cell
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.slice_s = cell.traffic.get("trace_seconds")
        self.running = False
        self.timer = None

    def start(self):
        opts = self.jax.profiler.ProfileOptions()
        for k, v in self.OPTIONS.items():
            setattr(opts, k, v)
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.running = True
        self.anchor = time.perf_counter()
        with self.jax.profiler.TraceAnnotation("bench.anchor"):
            pass
        if self.slice_s is not None:
            self.timer = threading.Timer(float(self.slice_s), self.stop)
            self.timer.start()

    def stop(self):
        if self.running:
            self.jax.profiler.stop_trace()
            self.running = False

    def after_call(self):
        if self.slice_s is None:
            self.stop()
        else:
            self.timer.join()

    def whole_calls(self, calls):
        """Calls the trace holds from start to end."""
        return calls[:1] if self.slice_s is None else []

    def reduce(self, calls) -> xplane.Reduced:
        if self.timer is not None:
            self.timer.join()
        found = [os.path.join(r, f) for r, _, fs in os.walk(self.dir)
                 for f in fs if f.endswith(".xplane.pb")]
        devices, marks = xplane.read(found[0])
        shutil.rmtree(self.dir, ignore_errors=True)
        at = marks["bench.anchor"][0][0] - self.anchor

        if self.slice_s is None:
            lo, hi = calls[0].t0, calls[0].t1
        else:
            lo, hi = self.anchor, self.anchor + float(self.slice_s)
        spans = [(c.t0 + at, c.t1 + at,
                  f"inside call {c.index} ({self.cell.api})") for c in calls]
        return xplane.Reduced(window=(lo + at, hi + at), devices=devices,
                              spans=spans)


def _on_core(st) -> bool:
    return st.engine_selected == "batched" and st.fast_path_events > 0


def failed_cells(rec: CallRecord, call) -> int:
    """Cells of a call that did not come back from the lockstep core."""
    if rec.error:
        return call.n_cells
    missing = call.n_cells - len(rec.results)
    return missing + sum(not _on_core(st) for st in rec.results.values())


def _load_reader(name: str):
    path = spec.reader_path(name)
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries, ctx: Context) -> dict:
    """Each metric its reader finds something to read, with its unit."""
    out = {}
    for m in entries:
        v = _load_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def _peak_bytes(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float, device: dict, log=print) -> dict:
    """Set up, run the window, check, and return the result object."""
    import jax

    counter = CompileCounter(jax)
    program = Program(cell)
    t0 = time.perf_counter()
    program.prewarm()
    char_s = time.perf_counter() - t0
    warm_errors = 0
    pool, shapes = cell.trace_seeds, len(cell.calls)
    for j, trace_seed in enumerate(pool):
        for k, call in enumerate(cell.calls):
            try:
                program.run(call, trace_seed,
                            call_seed(seed, 0, j * shapes + k))
            except Exception as e:   # the window's calls will fail too
                warm_errors += 1
                log(json.dumps({"warmup_error":
                                f"{type(e).__name__}: {e}"}))
    setup_s = time.perf_counter() - t_start
    c_setup = counter.snapshot()

    tracer = Tracer(jax, cell) if trace else None
    calls: List[CallRecord] = []
    first = first_member(seed, len(pool))
    d0 = program.dispatches
    w0 = time.perf_counter()
    i = 0
    while True:
        call = cell.calls[i % shapes]
        ts = pool[(first + i // shapes) % len(pool)]
        s = call_seed(seed, 1, i)
        if i == 0 and tracer:
            tracer.start()
        a = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"bench.call {i}"):
                res = program.run(call, ts, s)
            err = ""
        except Exception as e:       # counted as failed cells
            res, err = {}, f"{type(e).__name__}: {e}"
        b = time.perf_counter()
        calls.append(CallRecord(i, ts, s, a, b, call.n_cells, res, err))
        i += 1
        if tracer:
            tracer.after_call()
        if b - w0 >= seconds and not (tracer and tracer.running):
            break
    w1 = calls[-1].t1
    counters = {"kernel_dispatches": program.dispatches - d0}
    c_window = counter.snapshot()
    memory_peak = _peak_bytes(jax)
    reduced = tracer.reduce(calls) if tracer else None

    attempted = sum(c.n_cells for c in calls)
    failed = sum(failed_cells(rec, cell.calls[rec.index % len(cell.calls)])
                 for rec in calls)
    del program
    sampled = check.sample_cells(calls, seed)
    numbers, diffs = check.compare(cell, sampled)
    correct = failed == 0 and warm_errors == 0 and check.verdict(numbers)

    ctx = Context(cell=cell, calls=calls, window=(w0, w1),
                  setup={"setup_s": setup_s, "char_s": char_s,
                         "compile_s": c_setup[1]},
                  counters=counters, trace=reduced,
                  traced_calls=tracer.whole_calls(calls) if tracer else [])
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end,
                           ctx)
    dev = dict(device, memory_peak_bytes=memory_peak)
    if reduced is not None:
        dev.update(busy_s=reduced.busy_s(), window_s=reduced.window_s)

    means = {}
    for rec in calls:
        for (m, _, _), st in rec.results.items():
            means.setdefault(m, []).append(st.mean_us)
    log(json.dumps({
        "detail": cell.name, "seed": seed, "seconds": seconds,
        "trace": trace, "calls": [
            {"i": r.index, "trace_seed": r.trace_seed, "seed": r.seed,
             "s": r.t1 - r.t0,
             "cells": len(r.results), "error": r.error} for r in calls],
        "setup": {"setup_s": setup_s, "char_s": char_s,
                  "compiles": c_setup[0], "compile_s": c_setup[1],
                  "cache_hits": c_setup[2]},
        "window_compiles": c_window[0] - c_setup[0],
        "window_compile_s": c_window[1] - c_setup[1],
        "kernel_dispatches": counters["kernel_dispatches"],
        "mean_us_by_mechanism": {m: float(np.mean(v))
                                 for m, v in means.items()},
        "mismatches": diffs[:20],
    }))
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if reduced is not None:
        log(json.dumps({"trace": {"window_s": reduced.window_s,
                                  "busy_s": reduced.busy_s(),
                                  "core_s": reduced.core_s(),
                                  "traced_calls": len(ctx.traced_calls)}}))
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in reduced.idle_gaps(10)],
        }
    compared = dict(numbers, failed_cells=failed, warmup_errors=warm_errors)
    limits = dict(check.LIMITS, failed_cells=("<=", 0),
                  warmup_errors=("<=", 0))
    result["checks"] = {k: {"value": v, "pass_if": limits[k][0],
                            "limit": limits[k][1]}
                        for k, v in compared.items()}
    return result
