"""Parallel sweep runtime: grid cells scheduled across a process pool.

This is the middle layer of the sharded simulation runtime (ISSUE 5):

  * :mod:`repro.flashsim.engine` — *intra-run* decomposition: one event
    loop per channel behind ``shard=True``, bit-identical to the
    monolithic loop;
  * **this module** — *inter-cell* parallelism: a sweep's
    (mechanism x condition x seed x trace) grid cells are scheduled
    across a process pool with deterministic assembly, so a
    ``workers=4`` sweep returns exactly what ``workers=1`` returns —
    byte-identical once serialized (:func:`sweep_to_json`) — only
    faster;
  * :mod:`repro.flashsim.ssd` — the run APIs' ``workers=`` / ``shard=``
    knobs, which delegate here.

Scheduling unit
---------------
A :class:`Cell` is one schedulable unit.  ``kind="batch"`` cells are the
sweet spot: one *seed group* of a ``simulate_batch`` grid, which keeps
the single-seed trace generation, page-op expansion, and FTL pre-pass
shared across that group's (mechanism x condition) cells inside one
worker — the same sharing ``simulate_batch`` does inline.  ``simulate``
and ``compare`` cells wrap the corresponding run APIs for benchmark
harnesses that sweep per-seed cells directly.

Cache reuse across workers
--------------------------
Workers are forked (the ``fork`` start method; platforms without it
run inline): a forked worker inherits the parent's process-wide caches
copy-on-write — the content-hash trace cache
(:func:`repro.flashsim.workloads.cached_trace`) and the in-process
characterization memos — so :func:`run_cells` pre-warms every
(condition, mechanism) characterization table in the parent *before*
creating the pool and no worker ever enters JAX.  Cells that may run
the lockstep core (``engine="batched"``/``"auto"`` inside the batched
matrix) never go to a worker: the accelerator belongs to one process,
so they run in the caller's.  Force inline execution (no pool, e.g. in
sandboxes without working semaphores) with ``REPRO_SWEEP_INLINE=1``.

Determinism
-----------
Cell *results* never depend on the worker count — each cell runs the
identical code path a ``workers=1`` run executes — and cell *ordering*
is fixed by the caller's input order (:func:`run_cells` returns results
positionally; :func:`run_sweep` assembles its dict in canonical
seed -> condition -> mechanism order).  :func:`sweep_to_json` is the
canonical serialization used by the determinism tests and the CI
bench-smoke lane: byte-identical output for any ``workers``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import multiprocessing
import os
import platform
import threading
import time
from concurrent.futures import (
    FIRST_COMPLETED,
    BrokenExecutor,
    ProcessPoolExecutor,
    wait,
)
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from repro.flashsim.config import (
    DEFAULT_SSD,
    FaultConfig,
    OperatingCondition,
    SSDConfig,
)

__all__ = [
    "Cell",
    "host_fingerprint",
    "prewarm_characterization",
    "run_cells",
    "run_compare",
    "run_sweep",
    "sweep_cell_key",
    "sweep_to_json",
]


@dataclasses.dataclass(frozen=True)
class Cell:
    """One schedulable unit of a sweep.

    ``kind`` selects the run API the worker executes:

      * ``"simulate"`` — one (mechanism, condition, seed) run; returns
        a :class:`repro.flashsim.ssd.SimStats`;
      * ``"compare"`` — all ``mechanisms`` over one shared trace
        (:func:`repro.flashsim.ssd.compare_mechanisms`); returns
        ``{mechanism: SimStats}``;
      * ``"batch"`` — one full single-seed ``simulate_batch`` group
        (shares trace/expansion/FTL pre-pass across
        mechanisms x conditions); returns the batch dict.

    Cells must be picklable: ``workload`` is a
    :class:`~repro.flashsim.workloads.Workload`, a registry spec string,
    or a picklable :class:`~repro.flashsim.workloads.TraceSource`.
    """

    kind: str
    workload: object
    conditions: Tuple[OperatingCondition, ...]
    mechanisms: Tuple[str, ...]
    seed: int
    cfg: SSDConfig = DEFAULT_SSD
    n_requests: Optional[int] = None
    #: ``None`` defers to ``cfg.engine`` (itself ``"array"`` by default).
    engine: Optional[str] = None
    scheduler: Optional[str] = None
    gc: Optional[str] = None
    shard: bool = False
    faults: Optional[FaultConfig] = None
    ncq_depth: Optional[int] = None
    host_cache: object = None
    #: Fused-sweep dispatch policy (``None`` defers to ``cfg.fuse``).
    #: ``"batch"``/``"compare"`` cells fuse their inner grid inside
    #: ``simulate_batch``/``compare_mechanisms``; eligible
    #: ``"simulate"`` cells sharing a trace and config are additionally
    #: fused *across cells* by :func:`run_cells` (same results either
    #: way — the fused path is bit-identical).
    fuse: Optional[bool] = None

    def __post_init__(self):
        if self.kind not in ("simulate", "compare", "batch"):
            raise ValueError(
                f"Cell.kind must be 'simulate', 'compare' or 'batch', "
                f"got {self.kind!r}"
            )
        if self.kind == "simulate" and len(self.mechanisms) != 1:
            raise ValueError(
                "a 'simulate' cell takes exactly one mechanism, got "
                f"{self.mechanisms!r}"
            )
        if self.kind != "batch" and len(self.conditions) != 1:
            raise ValueError(
                f"a {self.kind!r} cell takes exactly one condition, got "
                f"{len(self.conditions)}"
            )


def _run_cell(cell: Cell):
    """Execute one cell (in a worker or inline) — pure in its argument."""
    from repro.flashsim.ssd import (
        compare_mechanisms,
        simulate,
        simulate_batch,
    )

    if cell.kind == "simulate":
        return simulate(
            cell.workload, cell.conditions[0], cell.mechanisms[0],
            seed=cell.seed, cfg=cell.cfg, n_requests=cell.n_requests,
            engine=cell.engine, scheduler=cell.scheduler, gc=cell.gc,
            shard=cell.shard, faults=cell.faults,
            ncq_depth=cell.ncq_depth, host_cache=cell.host_cache,
        )
    if cell.kind == "compare":
        return compare_mechanisms(
            cell.workload, cell.conditions[0], mechanisms=cell.mechanisms,
            seed=cell.seed, cfg=cell.cfg, n_requests=cell.n_requests,
            engine=cell.engine, scheduler=cell.scheduler, gc=cell.gc,
            shard=cell.shard, faults=cell.faults,
            ncq_depth=cell.ncq_depth, host_cache=cell.host_cache,
            fuse=cell.fuse,
        )
    return simulate_batch(
        cell.workload, cell.conditions, mechanisms=cell.mechanisms,
        seeds=(cell.seed,), cfg=cell.cfg, n_requests=cell.n_requests,
        engine=cell.engine, scheduler=cell.scheduler, gc=cell.gc,
        shard=cell.shard, faults=cell.faults,
        ncq_depth=cell.ncq_depth, host_cache=cell.host_cache,
        fuse=cell.fuse,
    )


def _fusable_cfg(cell: Cell):
    """Knob-overlaid config when a ``"simulate"`` cell is eligible for
    cross-cell fusion, else ``None``.

    Eligibility mirrors the inline sweeps: the cell's engine must be
    ``"batched"``/``"auto"``, fusion enabled (``cell.fuse``, defaulting
    to ``cfg.fuse``), and the overlaid config must resolve inside the
    batched matrix (ring-lowerable scheduler, gc off/prepass, no
    faults, open loop).  Ineligible cells run :func:`_run_cell` alone —
    ``"auto"`` fallbacks record their reason on ``SimStats`` exactly as
    without fusion, and explicit-``"batched"`` misconfigurations raise
    the same :class:`BatchedUnsupported` they always did.
    """
    if cell.kind != "simulate":
        return None
    engine = cell.engine if cell.engine is not None else cell.cfg.engine
    from repro.flashsim.ssd import _fuse_resolved, _with_knobs

    cfg = _with_knobs(cell.cfg, cell.scheduler, cell.gc, cell.faults,
                      cell.ncq_depth, cell.host_cache)
    return cfg if _fuse_resolved(cfg, engine, cell.fuse) else None


def _fusion_groups(items: Sequence[Tuple[int, Cell]]):
    """Partition (index, cell) pairs into host-prep groups and leftovers.

    A group is a maximal set of eligible ``"simulate"`` cells sharing
    the *resolved trace object* (cached and frozen, so equal
    (workload, seed, n_requests) cells resolve to one identity) and the
    knob-overlaid config (compared by ``repr`` — configs carry an
    unhashable timing dict); the shared trace/expansion/schedule are
    then computed once per group.  The
    grouping only decides host-side sharing — the kernel dispatch fuses
    *across* groups (:func:`_run_items_fused` hands every prepared cell
    to one engine call, which chunks by static kernel shape and step
    homogeneity), so a lone cell of one trace still stacks with cells
    of another.  Returns ``(groups, singles)`` where each group is
    ``(trace, cfg, [(index, cell), ...])``.
    """
    from repro.flashsim.ssd import resolve_trace

    buckets: Dict[Tuple[str, str], list] = {}
    singles: List[Tuple[int, Cell]] = []
    for i, cell in items:
        cfg = _fusable_cfg(cell)
        if cfg is None:
            singles.append((i, cell))
            continue
        trace = resolve_trace(cell.workload, seed=cell.seed,
                              n_requests=cell.n_requests)
        # Trace identity, not content hash: resolved traces are cached
        # frozen objects, so equal (workload, seed, n) cells share one.
        # Grouping only decides host-prep sharing — results are
        # grouping-invariant (the cell-axis law), so a cache miss can
        # only cost sharing, never correctness.
        key = (id(trace), repr(cfg))
        buckets.setdefault(key, []).append((i, cell, cfg, trace))
    groups = []
    for members in buckets.values():
        _, _, cfg, trace = members[0]
        groups.append((trace, cfg, [(i, c) for i, c, _, _ in members]))
    return groups, singles


def _run_items_fused(items: Sequence[Tuple[int, Cell]]) -> Dict[int, object]:
    """Results for the fused-eligible subset of ``items`` (cross-cell
    fusion); cells not covered by the returned dict run per-cell.

    Host prep is shared per trace/config group, then every prepared
    cell goes through ONE fused engine call — cells of different
    workloads and seeds stack along the kernel's cell axis whenever
    their static shapes and step bounds line up.  A lone eligible cell
    runs per-cell (nothing to amortize).  A batch that turns out
    unsupported at dispatch time (a guard the pre-filter should make
    unreachable) falls back to per-cell runs by simply not contributing
    results — never a silent wrong answer.
    """
    from repro.flashsim.engine_batched import BatchedUnsupported
    from repro.flashsim.ssd import (_make_sim, _run_prepared_fused,
                                    _shared_views)

    groups, _ = _fusion_groups(items)
    if sum(len(members) for _, _, members in groups) < 2:
        return {}
    prepped: List[Tuple[int, object, object]] = []
    for trace, cfg, members in groups:
        expansion, schedule = _shared_views(trace, cfg)
        for i, cell in members:
            engine = (cell.engine if cell.engine is not None
                      else cell.cfg.engine)
            sim = _make_sim(cfg, cell.conditions[0], cell.mechanisms[0],
                            cell.seed + 7, engine)
            prepped.append((i, sim, sim._prepare(
                trace, expansion=expansion, schedule=schedule)))
    try:
        stats = _run_prepared_fused([(s, p) for _, s, p in prepped])
    except BatchedUnsupported:
        return {}
    return {i: st for (i, _, _), st in zip(prepped, stats)}


def prewarm_characterization(cells: Iterable[Cell]) -> int:
    """Build every (condition, mechanism) table the cells will touch.

    Called in the parent before the pool is created so forked workers
    inherit warm in-process memos (and never call into JAX themselves).
    Returns the number of distinct tables touched.
    """
    from repro.core.retry import RetryPolicy
    from repro.flashsim.ssd import SSDSim

    seen = set()
    for cell in cells:
        for cond in cell.conditions:
            for mech in cell.mechanisms:
                key = (cond, mech)
                if key in seen:
                    continue
                seen.add(key)
                SSDSim(cell.cfg, cond, RetryPolicy(mech))
    return len(seen)


def _on_device(cell: Cell) -> bool:
    """Whether ``cell`` may run the lockstep core on the accelerator.

    True when its engine is ``"batched"`` or ``"auto"`` and its
    knob-overlaid config resolves inside the batched matrix — the same
    :func:`~repro.flashsim.engine_batched.resolve_engine` call run()
    makes.  The accelerator belongs to one process, so such cells run in
    the process that holds it, never in a pool worker.
    """
    from repro.flashsim.engine_batched import resolve_engine
    from repro.flashsim.ssd import _with_knobs

    engine = cell.engine if cell.engine is not None else cell.cfg.engine
    if engine not in ("batched", "auto"):
        return False
    cfg = _with_knobs(cell.cfg, cell.scheduler, cell.gc, cell.faults,
                      cell.ncq_depth, cell.host_cache)
    return resolve_engine(cfg)[0] == "batched"


def _fork_context():
    """The ``fork`` start method, or ``None`` where the platform lacks it.

    Pool workers run interpreter (array-engine) cells only and never
    enter JAX: they read the characterization memos the parent warmed
    (:func:`prewarm_characterization`) through copy-on-write memory.  A
    ``spawn`` worker would start cold and characterize in JAX itself,
    so there is no spawn pool.
    """
    if "fork" not in multiprocessing.get_all_start_methods():
        return None
    return multiprocessing.get_context("fork")


def _inline_forced() -> bool:
    return os.environ.get("REPRO_SWEEP_INLINE", "0") == "1"


# -- checkpoint journal ----------------------------------------------------


def _encode_result(r):
    """Cell result -> JSON-safe journal record (floats repr-round-trip)."""
    from repro.flashsim.ssd import SimStats

    if isinstance(r, SimStats):
        return {"t": "stats", "v": dataclasses.asdict(r)}
    if isinstance(r, dict):
        if all(isinstance(k, str) for k in r):       # compare: {mech: stats}
            return {"t": "mechs",
                    "v": {m: dataclasses.asdict(s) for m, s in r.items()}}
        return {"t": "cells",                        # batch: {(m, cond, s): stats}
                "v": [[m, cond.retention_days, cond.pec, s,
                       dataclasses.asdict(st)]
                      for (m, cond, s), st in r.items()]}
    raise TypeError(f"cell result of type {type(r).__name__} cannot be "
                    f"journaled")


def _stats_from_journal(d):
    """Rebuild a SimStats from a journal record, tolerating schema drift.

    SimStats grows additive zero-default fields over time (GC, fault and
    closed-loop blocks landed in separate PRs).  A journal written by an
    older build lacks the new keys (defaults fill them in), and one
    written by a *newer* build may carry keys this build doesn't know —
    drop those rather than crash, so resume never breaks on additive
    stats.
    """
    from repro.flashsim.ssd import SimStats

    known = {f.name for f in dataclasses.fields(SimStats)}
    return SimStats(**{k: v for k, v in d.items() if k in known})


def _decode_result(e):
    t, v = e["t"], e["v"]
    if t == "stats":
        return _stats_from_journal(v)
    if t == "mechs":
        return {m: _stats_from_journal(d) for m, d in v.items()}
    return {
        (m, OperatingCondition(ret, pec), s): _stats_from_journal(d)
        for m, ret, pec, s, d in v
    }


class _Journal:
    """Append-only JSONL checkpoint of completed cells.

    Line 0 is a header carrying the *run key* — a hash over the cell
    list's reprs — so a journal can only ever resume the exact sweep
    that wrote it; any other cell list starts the file over.  Each
    subsequent line records one completed cell ``{"i": index, "r":
    encoded result}``, flushed as it lands, so a run killed mid-sweep
    (even SIGKILL — the write syscall has happened) loses at most the
    in-flight cells.  JSON floats round-trip exactly through ``repr``,
    so a resumed sweep's assembled results — and its
    :func:`sweep_to_json` — are byte-identical to an uninterrupted run.
    A torn trailing line (killed mid-append) is ignored.
    """

    def __init__(self, path, cells: Sequence[Cell]):
        self.path = os.fspath(path)
        self.key = hashlib.sha256(
            "\n".join(repr(c) for c in cells).encode()
        ).hexdigest()
        self.done: Dict[int, object] = {}
        try:
            with open(self.path) as f:
                lines = f.read().splitlines()
        except OSError:
            lines = []
        resumable = False
        if lines:
            try:
                resumable = json.loads(lines[0]).get("run") == self.key
            except ValueError:
                resumable = False
        if resumable:
            for ln in lines[1:]:
                try:
                    ent = json.loads(ln)
                    self.done[int(ent["i"])] = _decode_result(ent["r"])
                except (ValueError, KeyError, TypeError):
                    break                      # torn tail: drop it
            self._f = open(self.path, "a")
        else:
            self._f = open(self.path, "w")
            self._f.write(json.dumps({"run": self.key}) + "\n")
            self._f.flush()

    def record(self, i: int, result) -> None:
        self._f.write(
            json.dumps({"i": i, "r": _encode_result(result)}) + "\n"
        )
        self._f.flush()


# Oversubscription factor for chunked submission: pending cells are
# grouped into ~workers * _CHUNK_OVERSUB tasks, so one pickled round
# trip carries several small cells (per-task IPC was costing more than
# the cells themselves: BENCH_sim recorded speedup 0.92 at workers=4)
# while still leaving enough tasks per worker for load balancing.
_CHUNK_OVERSUB = 4


def _chunk_pending(pending: Dict[int, Cell],
                   workers: int) -> List[List[Tuple[int, Cell]]]:
    items = sorted(pending.items())
    n_tasks = workers * _CHUNK_OVERSUB
    size = max(1, -(-len(items) // n_tasks))
    return [items[k:k + size] for k in range(0, len(items), size)]


def _run_cell_chunk(items: List[Tuple[int, Cell]]):
    """Worker entry: run a chunk of (index, cell) pairs in order.

    Fusable ``"simulate"`` cells that landed in the same chunk run as
    fused kernel dispatches (:func:`_run_items_fused`); the rest — and
    any fused group that falls back — run per-cell.  Bit-identical
    either way, so chunking policy never changes results.
    """
    fused = _run_items_fused(items)
    return [(i, fused[i] if i in fused else _run_cell(c))
            for i, c in items]


def _finish_inline(results: List, pending: Dict[int, Cell],
                   jr: Optional[_Journal]) -> List:
    """Run the leftover cells inline (in index order), journaling each.

    Like the chunked worker path, fusable ``"simulate"`` cells run as
    fused dispatches first; journal records are still written in index
    order, so resume semantics are unchanged.
    """
    fused = _run_items_fused(sorted(pending.items()))
    for i in sorted(pending):
        r = fused[i] if i in fused else _run_cell(pending[i])
        results[i] = r
        if jr is not None:
            jr.record(i, r)
    return results


def run_cells(cells: Sequence[Cell], workers: int = 1,
              prewarm: bool = True, journal=None,
              cell_timeout: Optional[float] = None,
              max_retries: int = 2, backoff_s: float = 0.1) -> List:
    """Execute ``cells``; results are returned in input order.

    ``workers <= 1`` runs inline (no pool, no pickling — the exact
    ``workers=1`` code path).  Cells that may run the lockstep core
    always run inline, in this process (:func:`_on_device`).  Larger
    counts fan the remaining interpreter cells out over a fork pool in
    *chunks* of several cells per task (amortizing the
    per-task pickle/IPC overhead that made small-cell sweeps slower
    than inline); results are still assembled positionally, so the
    output is independent of completion order, worker count, and
    chunking.

    Self-healing: pool-*infrastructure* failures never cost completed
    work.  Results are harvested per-cell as futures finish, so when
    workers die (``BrokenExecutor`` — fork breakage, an OOM-killed or
    SIGKILLed child) only the genuinely unfinished cells are retried —
    on a fresh pool, up to ``max_retries`` times with exponential
    backoff (``backoff_s * 2**attempt``), then inline as the last
    resort.  ``cell_timeout`` (seconds) bounds the wait for *progress*:
    if no cell completes within it, the pool is declared stalled and
    abandoned (a hung worker cannot hang the sweep) and the remainder
    is retried the same way (progress is observed per completed
    *chunk*).  An exception raised *by a cell itself*
    propagates unchanged — it would fail inline too, so retrying would
    only duplicate the work.

    ``journal`` (a path) checkpoints every completed cell to an
    append-only JSONL file keyed by the cell list: a killed sweep
    re-run with the same cells and journal skips the recorded cells and
    returns byte-identical results (:class:`_Journal`).
    """
    cells = list(cells)
    jr = _Journal(journal, cells) if journal is not None else None
    results: List = [None] * len(cells)
    pending: Dict[int, Cell] = {}
    for i, c in enumerate(cells):
        if jr is not None and i in jr.done:
            results[i] = jr.done[i]
        else:
            pending[i] = c
    if not pending:
        return results
    workers = min(int(workers), len(pending))
    ctx = _fork_context()
    if workers <= 1 or _inline_forced() or ctx is None:
        return _finish_inline(results, pending, jr)
    # Cells that may run the lockstep core stay in this process (the
    # accelerator belongs to one process); only interpreter cells fan
    # out, over a fork pool whose workers never enter JAX.
    device = {i: c for i, c in pending.items() if _on_device(c)}
    if device:
        _finish_inline(results, device, jr)
        pending = {i: c for i, c in pending.items() if i not in device}
    workers = min(workers, len(pending))
    if workers <= 1:
        return _finish_inline(results, pending, jr)
    if prewarm:
        prewarm_characterization(pending.values())
    attempt = 0
    while True:
        try:
            pool = ProcessPoolExecutor(
                max_workers=min(workers, len(pending)),
                mp_context=ctx,
            )
        except (OSError, PermissionError):
            # Sandboxed semaphores / fork unavailable: no pool at all.
            break
        stalled = False
        try:
            # Chunked submission: one task carries several cells, so
            # the pickle/IPC round trip is amortized (results are still
            # placed positionally — output is identical for any worker
            # count or chunking).
            futures = {pool.submit(_run_cell_chunk, ch): [i for i, _ in ch]
                       for ch in _chunk_pending(pending, workers)}
            not_done = set(futures)
            while not_done:
                done, not_done = wait(not_done, timeout=cell_timeout,
                                      return_when=FIRST_COMPLETED)
                if not done:
                    stalled = True        # no progress within cell_timeout
                    break
                for fut in done:
                    try:
                        chunk_results = fut.result()
                    except BrokenExecutor:
                        # This future's worker died; siblings that DID
                        # complete still carry their results — keep
                        # harvesting, never discard finished work.
                        stalled = True
                        continue
                    for i, r in chunk_results:
                        results[i] = r
                        del pending[i]
                        if jr is not None:
                            jr.record(i, r)
        except BrokenExecutor:
            stalled = True
        except BaseException:
            pool.shutdown(wait=False, cancel_futures=True)
            raise
        # A stalled pool may hold a hung worker: abandon it without
        # waiting (its processes drain in the background).
        pool.shutdown(wait=not stalled, cancel_futures=True)
        if not pending:
            return results
        attempt += 1
        if attempt > max_retries:
            break
        time.sleep(backoff_s * (2 ** (attempt - 1)))
    return _finish_inline(results, pending, jr)


def run_sweep(
    workload,
    conditions: Iterable[OperatingCondition],
    mechanisms: Sequence[str],
    seeds: Sequence[int],
    cfg: SSDConfig = DEFAULT_SSD,
    n_requests: Optional[int] = None,
    engine: str = "array",
    scheduler: Optional[str] = None,
    gc: Optional[str] = None,
    shard: bool = False,
    workers: int = 1,
    faults: Optional[FaultConfig] = None,
    journal=None,
    ncq_depth: Optional[int] = None,
    host_cache=None,
    fuse: Optional[bool] = None,
) -> Dict[Tuple[str, OperatingCondition, int], "object"]:
    """``simulate_batch`` semantics with seed groups fanned over workers.

    One :class:`Cell` per seed keeps each group's trace / expansion /
    FTL pre-pass shared inside its worker, exactly like the inline
    sweep.  The result dict is assembled in the canonical
    seed -> condition -> mechanism order regardless of worker count, so
    iteration order — and :func:`sweep_to_json` output — is byte-stable.
    ``journal=`` names a checkpoint file: completed seed groups are
    recorded as they finish and a killed sweep re-run with the same
    arguments resumes from it byte-identically (:func:`run_cells`).
    ``fuse=`` overrides ``cfg.fuse`` per cell: each seed group's
    eligible (condition x mechanism) grid runs as fused kernel
    dispatches inside its worker, bit-identical either way.
    """
    conditions = tuple(conditions)
    mechanisms = tuple(mechanisms)
    seeds = tuple(seeds)
    cells = [
        Cell("batch", workload, conditions, mechanisms, s, cfg, n_requests,
             engine, scheduler, gc, shard, faults=faults,
             ncq_depth=ncq_depth, host_cache=host_cache, fuse=fuse)
        for s in seeds
    ]
    groups = run_cells(cells, workers=workers, journal=journal)
    out: Dict[Tuple[str, OperatingCondition, int], object] = {}
    for s, group in zip(seeds, groups):
        for cond in conditions:
            for mech in mechanisms:
                out[(mech, cond, s)] = group[(mech, cond, s)]
    return out


# -- compare_mechanisms fan-out -------------------------------------------
#
# Mechanisms of one compare share the trace, the expansion, and (prepass
# GC) the FTL schedule.  Shipping those to workers by pickle would cost
# more than it saves, so the parallel path relies on fork inheritance:
# the parent materializes the shared views in _COMPARE_PAYLOAD, forks the
# pool, and each task reads them back copy-on-write.  Without fork the
# call simply runs inline — correctness never depends on the pool.
# _COMPARE_LOCK serializes the payload's lifetime so concurrent
# compare_mechanisms(..., workers>1) calls from different threads cannot
# fork a pool against each other's views.

_COMPARE_PAYLOAD = None
_COMPARE_LOCK = threading.Lock()


def _run_compare_mech(mechanism: str):
    from repro.flashsim.ssd import _make_sim

    trace, expansion, schedule, cfg, condition, seed, shard, engine = \
        _COMPARE_PAYLOAD
    sim = _make_sim(cfg, condition, mechanism, seed + 7, engine)
    return sim.run(trace, expansion=expansion, schedule=schedule,
                   shard=shard)


def run_compare(
    workload,
    condition: OperatingCondition,
    mechanisms: Sequence[str],
    seed: int,
    cfg: SSDConfig,
    n_requests: Optional[int],
    scheduler: Optional[str],
    gc: Optional[str],
    shard: bool,
    workers: int,
    engine: str = "array",
    fuse: Optional[bool] = None,
) -> Dict[str, "object"]:
    """Parallel ``compare_mechanisms``: one worker per mechanism.

    Requires the ``fork`` start method (shared views are inherited, not
    pickled); otherwise — or on pool failure — falls back to the inline
    run API.  Results match ``compare_mechanisms(..., workers=1)``
    exactly, in the caller's mechanism order.  Only interpreter
    (``array``) compares fork: a compare that may run the lockstep core
    (:func:`_on_device`) runs inline, in the process that holds the
    accelerator, fused or not.
    """
    global _COMPARE_PAYLOAD
    from repro.flashsim import ssd

    mechanisms = tuple(mechanisms)
    ctx = _fork_context()
    on_device = _on_device(Cell("compare", workload, (condition,),
                                mechanisms, seed, cfg, engine=engine,
                                scheduler=scheduler, gc=gc))
    if (on_device or workers <= 1 or len(mechanisms) <= 1
            or _inline_forced() or ctx is None):
        return ssd.compare_mechanisms(
            workload, condition, mechanisms=mechanisms, seed=seed, cfg=cfg,
            n_requests=n_requests, engine=engine, scheduler=scheduler,
            gc=gc, shard=shard, fuse=fuse,
        )
    cfg = ssd._with_knobs(cfg, scheduler, gc)
    trace = ssd.resolve_trace(workload, seed=seed, n_requests=n_requests)
    expansion, schedule = ssd._shared_views(trace, cfg)
    # Materialize the lazy list views now so forked children share them.
    expansion.admission_lists
    if schedule is not None:
        schedule.admission_lists
    prewarm_characterization(
        [Cell("compare", workload, (condition,), mechanisms, seed, cfg)]
    )
    with _COMPARE_LOCK:
        _COMPARE_PAYLOAD = (trace, expansion, schedule, cfg, condition,
                            seed, shard, engine)
        try:
            try:
                pool = ProcessPoolExecutor(
                    max_workers=min(workers, len(mechanisms)),
                    mp_context=ctx,
                )
            except (OSError, PermissionError):
                pool = None
            if pool is None:
                stats = [_run_compare_mech(m) for m in mechanisms]
            else:
                try:
                    with pool:
                        futures = [pool.submit(_run_compare_mech, m)
                                   for m in mechanisms]
                        stats = [f.result() for f in futures]
                except BrokenExecutor:
                    stats = [_run_compare_mech(m) for m in mechanisms]
        finally:
            _COMPARE_PAYLOAD = None
    return dict(zip(mechanisms, stats))


# -- canonical serialization ----------------------------------------------


def sweep_cell_key(mechanism: str, condition: OperatingCondition,
                   seed: int) -> str:
    """Collision-free string key for one sweep cell (JSON dict key).

    Condition floats are rendered with ``repr`` (exact round-trip), so
    two distinct conditions can never collapse to one key.
    """
    return (f"{mechanism}|ret{condition.retention_days!r}"
            f"|pec{condition.pec!r}|seed{seed}")


def _stats_payload(stats) -> Dict[str, object]:
    """SimStats -> JSON dict of *compared* fields only.

    ``compare=False`` fields (engine_selected, fast_path_events,
    fused_cells, ...) describe how a result was computed, not what it
    is — including them would make the serialization depend on engine
    and fusion decisions that are defined to be outcome-neutral.
    """
    d = dataclasses.asdict(stats)
    return {f.name: d[f.name] for f in dataclasses.fields(stats)
            if f.compare}


def sweep_to_json(results: Dict) -> str:
    """Canonical, byte-stable serialization of a sweep result dict.

    Keys sort lexicographically and floats serialize via ``repr`` (exact
    round-trip), so two sweeps are byte-identical iff every cell's
    SimStats match exactly — the contract the worker-count determinism
    tests and the CI bench-smoke lane assert.  Observability fields
    (``compare=False`` on :class:`~repro.flashsim.ssd.SimStats`) are
    excluded, so the bytes are invariant across engine selection,
    worker count, and fusion decisions.
    """
    payload = {
        sweep_cell_key(m, cond, s): _stats_payload(stats)
        for (m, cond, s), stats in results.items()
    }
    return json.dumps(payload, sort_keys=True, indent=1) + "\n"


# -- host fingerprint ------------------------------------------------------


def host_fingerprint() -> Dict[str, object]:
    """CPU model, core count, and interpreter/library versions.

    Recorded alongside every absolute timing in ``BENCH_sim.json`` so a
    number measured on one machine class can no longer masquerade as a
    regression when re-measured on another (the PR 4 incident: a slower
    session machine read as a ~35% engine slowdown).
    """
    cpu_model = None
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    cpu_model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return {
        "cpu_model": cpu_model or platform.processor() or None,
        "cpu_count": os.cpu_count(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "numpy": np.__version__,
    }
