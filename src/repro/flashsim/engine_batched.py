"""Batched shard core: lockstep-vectorized event loops over all channels.

``run_event_core_batched`` is a drop-in replacement for
:func:`repro.flashsim.engine.run_event_core` on the **open-loop fast
path**: every per-channel shard loop advances in lockstep inside one
compiled kernel (:mod:`repro.kernels.fcfs_core`) instead of running
sequentially in Python.  The result is bit-identical to the interpreter
— the kernel replays the exact event order (push-order seq discipline)
and the exact float arithmetic (the busy-until collapse's add/max
sequence) of :func:`repro.flashsim.engine._run_shard` per lane; see the
kernel module docstring for the construction.

Eligibility (the supported matrix) is checked **explicitly** — an
unsupported configuration raises :class:`BatchedUnsupported` rather
than silently falling back to the interpreter:

  ===================  ========================================
  scheduler            any policy with a ring lowering —
                       ``fcfs`` (single FIFO ring),
                       ``host_prio`` and ``host_prio_aged[:b]``
                       (dual priority rings, traced aging
                       bound); ``tokens`` and ``preempt`` have
                       none and are rejected
  GC                   ``none`` or ``prepass`` (the prepass
                       schedule is just a longer admission
                       stream); ``online`` injects ops mid-loop
  faults               ``None`` (recovery ladders are serial
                       continuations the kernel doesn't model)
  frontend             open loop (``ncq_depth=None``) — checked
                       by the caller, which owns the config
  validate             ``False`` (work-conservation asserts are
                       interpreter instrumentation)
  ===================  ========================================

``engine="auto"`` resolution lives here too (:func:`resolve_engine`):
it runs the same checks non-fatally and returns ``("batched", "")``
when eligible, else ``("array", reason)`` — the recorded reason string
is the matching ``BatchedUnsupported`` message, so auto documents
rather than hides its fallback.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

from repro.flashsim.engine import EngineResult
from repro.flashsim.sched import SchedulerPolicy


class BatchedUnsupported(NotImplementedError):
    """Raised when a run configuration is outside the batched core's
    supported matrix (never a silent fallback)."""


def check_batched_config(cfg) -> None:
    """Config-level eligibility for ``engine='batched'`` (fail fast at
    construction; run-time state is checked again by
    :func:`check_batched_supported`)."""
    from repro.flashsim.sched import get_scheduler

    pol = get_scheduler(cfg.scheduler)
    if pol.ring_lowering is None:
        raise BatchedUnsupported(
            f"engine='batched' supports ring-lowerable schedulers only "
            f"(fcfs, host_prio, host_prio_aged[:bound]), got "
            f"{cfg.scheduler!r}; use engine='array'"
        )
    if cfg.gc.enabled and cfg.gc.mode == "online":
        raise BatchedUnsupported(
            "engine='batched' does not support online GC (ops are "
            "injected mid-loop); use gc='prepass' or engine='array'"
        )
    if cfg.faults is not None:
        raise BatchedUnsupported(
            "engine='batched' does not support fault injection; use "
            "engine='array'"
        )
    if cfg.ncq_depth is not None:
        raise BatchedUnsupported(
            "engine='batched' is open-loop only (ncq_depth=None); the "
            "closed-loop frontend requires engine='array'"
        )


def check_batched_supported(
    policy: SchedulerPolicy,
    bufs,
    online,
    validate: bool,
) -> None:
    """Raise :class:`BatchedUnsupported` unless this run is eligible."""
    if policy.ring_lowering is None:
        raise BatchedUnsupported(
            f"engine='batched' supports ring-lowerable schedulers only "
            f"(fcfs, host_prio, host_prio_aged[:bound]), got "
            f"{policy.name!r}; run this scheduler with engine='array'"
        )
    if online is not None:
        raise BatchedUnsupported(
            "engine='batched' does not support online GC (ops are "
            "injected mid-loop); use gc='prepass' or engine='array'"
        )
    if bufs.xa is not None:
        raise BatchedUnsupported(
            "engine='batched' does not support fault injection "
            "(recovery-ladder continuations); use engine='array'"
        )
    if validate:
        raise BatchedUnsupported(
            "validate=True is interpreter instrumentation; use "
            "engine='array' for work-conservation checks"
        )


def resolve_engine(cfg, validate: bool = False) -> Tuple[str, str]:
    """Resolve ``engine="auto"`` for a config: ``(engine, reason)``.

    Returns ``("batched", "")`` when the config is inside the batched
    matrix, else ``("array", reason)`` where ``reason`` is the exact
    :class:`BatchedUnsupported` message the explicit engine would have
    raised — auto records, never hides, its fallback.  ``validate=True``
    always resolves to the instrumented interpreter.
    """
    if validate:
        return ("array", "validate=True is interpreter instrumentation")
    try:
        check_batched_config(cfg)
    except BatchedUnsupported as e:
        return ("array", str(e))
    return ("batched", "")


def _lane_tables(cfg, bufs):
    """Build the per-channel (P_l, 7) op tables of one run.

    Returns ``(tables, lane_idx, rid)`` — the per-lane tables in
    admission order, the per-channel index partition, and the op→request
    id map (used to reassemble ``req_done``).  This is the shared front
    half of both the per-run and the fused batched drivers.
    """
    n_ch = cfg.n_channels
    P = len(bufs.arrival)

    arrival = np.asarray(bufs.arrival, dtype=np.float64)
    rid = np.asarray(bufs.rid, dtype=np.int64)
    die = np.asarray(bufs.die, dtype=np.int64)
    ch = np.asarray(bufs.ch, dtype=np.int64)
    read = np.asarray(bufs.read, dtype=bool)
    erase = np.asarray(bufs.erase, dtype=bool)
    dur = np.asarray(bufs.dur, dtype=np.float64)
    att = np.asarray(bufs.a, dtype=np.float64)
    tr = np.asarray(bufs.tr, dtype=np.float64)

    if P and not np.array_equal(ch, die % n_ch):
        # The lockstep decomposition leans on the static die stripe the
        # same way shard=True does; an op off its die's channel would
        # break lane ownership.
        raise BatchedUnsupported(
            "engine='batched' requires the die->channel stripe "
            "(ch == die % n_channels) for every op"
        )

    kind = np.where(read, 0.0, np.where(erase, 2.0, 1.0))
    die_local = (die // n_ch).astype(np.float64)
    # Scheduling class: the interpreter's host_read table is
    # ``read and rid >= 0`` (GC copy-back reads carry rid = -1; the
    # fault ladder's parity reads are excluded from this matrix).
    hp = (read & (rid >= 0)).astype(np.float64)
    table = np.stack([arrival, kind, die_local, dur, att, tr, hp],
                     axis=1)

    # Per-channel admission substreams, original order preserved — the
    # same partition run_event_core's shard path builds.
    lane_idx = [np.flatnonzero(ch == c) for c in range(n_ch)]
    return [table[idx] for idx in lane_idx], lane_idx, rid


def _assemble_result(cfg, rid, lane_idx, fin, diestat, lane,
                     n_requests: int, fused_cells: int = 0) -> EngineResult:
    """Reassemble an :class:`EngineResult` from one cell's kernel rows
    exactly as ``merge_shard_results`` would."""
    n_ch, n_dies = cfg.n_channels, cfg.n_dies
    n_dies_local = -(-n_dies // n_ch)

    req_done = np.zeros(n_requests, dtype=np.float64)
    live = [(c, idx) for c, idx in enumerate(lane_idx) if idx.size]
    if live:
        # One flat scatter-max over every lane's ops (max is
        # order-free, so flattening the per-channel loop is exact).
        rid_all = np.concatenate([rid[idx] for _, idx in live])
        fin_all = np.concatenate([fin[c, : idx.size] for c, idx in live])
        sel = rid_all >= 0
        np.maximum.at(req_done, rid_all[sel], fin_all[sel])

    # diestat rows are (lane c, local die j) for die d = j*n_ch + c;
    # transpose to d-order and trim the padding rows past n_dies.
    ds = np.asarray(diestat).transpose(1, 0, 2).reshape(-1, 2)[:n_dies]
    die_tot = ds[:, 0].tolist()
    die_busy = ds[:, 1].tolist()

    n_events = int(lane[:, 2].sum())
    return EngineResult(
        req_done=req_done.tolist(),
        die_tot=die_tot,
        ch_tot=lane[:, 1].tolist(),
        die_busy=die_busy,
        ch_busy=lane[:, 0].tolist(),
        n_events=n_events,
        gc_suspensions=0,
        online_attempts=0,
        online_read_pages=0,
        fast_path_events=n_events,
        fused_cells=fused_cells,
    )


def run_event_core_batched(
    cfg,
    pipelined: bool,
    policy: SchedulerPolicy,
    bufs,
    n_requests: int,
    online=None,
    validate: bool = False,
) -> EngineResult:
    """Run the admission stream through the lockstep kernel.

    Same contract as ``run_event_core(..., shard=True)`` on the
    supported matrix: one lane per channel, results merged exactly as
    :func:`repro.flashsim.engine.merge_shard_results` would.
    """
    check_batched_supported(policy, bufs, online, validate)

    tables, lane_idx, rid = _lane_tables(cfg, bufs)

    from repro.kernels.fcfs_core import fcfs_core
    from repro.kernels.fcfs_core.ops import pad_ops

    mode, bound = policy.ring_lowering
    ops = pad_ops(tables)
    n_dies_local = -(-cfg.n_dies // cfg.n_channels)
    fin, diestat, lane = fcfs_core(
        ops, n_dies_local, pipelined, bufs.tdma, bufs.tecc,
        age_bound=bound if mode == "prio" else None)
    return _assemble_result(cfg, rid, lane_idx, fin, diestat, lane,
                            n_requests)


@dataclasses.dataclass
class FusedRun:
    """One prepared cell of a fused sweep dispatch: the same inputs
    ``run_event_core_batched`` takes, held so many cells can share one
    kernel launch."""

    cfg: object
    pipelined: bool
    policy: SchedulerPolicy
    bufs: object
    n_requests: int


#: Lane budget of one fused dispatch.  The kernel's per-lane-step cost
#: is flat while the working set (op table + state rows) stays
#: cache-resident and climbs ~30% past it; 64 lanes is the measured
#: knee on the 8-channel default geometry, so groups chunk at
#: ``_FUSE_LANE_CAP // n_channels`` cells rather than stacking without
#: bound.
_FUSE_LANE_CAP = 64

#: Step-homogeneity bound of one chunk.  Every lane of a fused dispatch
#: runs the *group-max* step count (finished lanes no-op but still pay
#: the lockstep body), so stacking a short cell under a long one wastes
#: (max - own) steps of per-lane work.  Fusing saves roughly the fixed
#: per-dispatch cost (~ the cell's own step count in lane-step units),
#: so cells within a 1.5x step band win and wider bands lose — chunks
#: split when the next cell's bound exceeds the chunk minimum by more.
_FUSE_STEP_RATIO = 1.5


def _fuse_cell_cap(n_channels: int) -> int:
    """Max cells of one fused chunk for an ``n_channels``-lane cell."""
    return max(1, _FUSE_LANE_CAP // max(1, n_channels))


def _fuse_chunks(cells, n_channels: int):
    """Split one static-shape group into step-homogeneous chunks.

    ``cells`` is a sequence of ``(steps, index, payload)`` triples; the
    split is deterministic — sort by (steps, index), then greedily chunk
    while the cell count stays under :func:`_fuse_cell_cap` and the step
    bound within ``_FUSE_STEP_RATIO`` of the chunk minimum.  Chunking
    never affects results (the cell-axis law), only which cells share a
    dispatch.
    """
    cap = _fuse_cell_cap(n_channels)
    chunks, cur = [], []
    for steps, idx, payload in sorted(cells, key=lambda t: t[:2]):
        if cur and (len(cur) >= cap
                    or steps > cur[0][0] * _FUSE_STEP_RATIO):
            chunks.append(cur)
            cur = []
        cur.append((steps, idx, payload))
    if cur:
        chunks.append(cur)
    return chunks


def run_event_cores_fused(runs) -> list:
    """Run many eligible cells in as few kernel dispatches as possible.

    Stacks the per-cell padded op tables of ``runs`` (a sequence of
    :class:`FusedRun`) along the lane axis — cell c's channels occupy
    lane rows [c*L, (c+1)*L) — and dispatches each *chunk* once.  A
    group is the maximal sub-grid sharing every static kernel parameter:
    (n_channels, local die count, pipelined, scheduler lowering mode,
    padded-width bucket); each group then chunks by the two measured
    perf cliffs (:func:`_fuse_chunks`): at most ``_FUSE_LANE_CAP``
    stacked lanes per dispatch (cache residency) and step bounds within
    ``_FUSE_STEP_RATIO`` of each other (every lane runs the chunk-max
    step count, so step-heterogeneous stacking wastes lane-steps).  The
    cap doubles as the shape-bucket bound: chunk cell counts range over
    at most ``_fuse_cell_cap`` values per static key, so the compiled
    (and persistently cached) kernel-variant count stays small without
    padding dead filler lanes.  Ring capacities / step cap are the
    chunk maxima — all semantics-neutral, so each cell's rows are
    bit-identical to its own :func:`run_event_core_batched` dispatch
    (the cell-axis law; see the kernel docstring and
    :func:`fused_core_ref`).  Per-cell scalars (tdma, tecc, aging
    bound) ride as per-lane traced timing rows, so cells with different
    timing models or ``host_prio_aged`` bounds still fuse.

    Eligibility is checked per cell up front —
    :class:`BatchedUnsupported` propagates before any dispatch (callers
    route ineligible cells to their own engine runs and record the
    reason; nothing silently falls back here).  Returns one
    :class:`EngineResult` per run, in order, each with
    ``fused_cells = len(its chunk)``.
    """
    from repro.kernels.fcfs_core.ops import (
        count_steps, fused_core, pad_ops, pad_width, ring_caps,
        _pow2_at_least)

    prepped = []
    for r in runs:
        check_batched_supported(r.policy, r.bufs, None, False)
        tables, lane_idx, rid = _lane_tables(r.cfg, r.bufs)
        mode, bound = r.policy.ring_lowering
        widest = max((t.shape[0] for t in tables), default=0)
        prepped.append((r, tables, lane_idx, rid, mode, bound, widest))

    # Group key = every static kernel parameter; per-cell dynamics
    # (timing, bound, table contents) ride in traced operands.
    groups = {}
    for i, (r, tables, lane_idx, rid, mode, bound, widest) in \
            enumerate(prepped):
        n_ch = r.cfg.n_channels
        key = (n_ch, -(-r.cfg.n_dies // n_ch), r.pipelined, mode,
               pad_width(widest))
        groups.setdefault(key, []).append(i)

    results = [None] * len(prepped)
    for (n_ch, n_dies_local, pipelined, mode, maxp), idxs in \
            groups.items():
        cells = []
        for i in idxs:
            _, tables, _, _, _, _, _ = prepped[i]
            ops_c = pad_ops(tables, maxp=maxp)
            cells.append((count_steps(ops_c), i, ops_c))
        for chunk in _fuse_chunks(cells, n_ch):
            C = len(chunk)
            cell_ops = [ops_c for _, _, ops_c in chunk]
            timing_rows = []
            for _, i, _ in chunk:
                r, _, _, _, _, bound, _ = prepped[i]
                b = bound if mode == "prio" else 0.0
                timing_rows.append(np.tile(
                    [[r.bufs.tdma, r.bufs.tecc, b]],
                    (n_ch, 1)))
            stacked = np.concatenate(cell_ops, axis=0)
            timing = np.concatenate(timing_rows,
                                    axis=0).astype(np.float64)

            # Chunk-wide static caps: ring bounds read off the stacked
            # table in one pass — the lane-wise max over all cells, and
            # pow2 bucketing commutes with max (ring pairing is by
            # monotone counters and idle lanes no-op, so growing a cap
            # never changes a cell's rows).  The chunk-max step count
            # doubles as the stacked table's exact step bound (max over
            # lanes), so the dispatch skips its recount.
            steps = max(st for st, _, _ in chunk)
            capq, capw = ring_caps(stacked, n_dies_local)
            caps = (capq, capw, _pow2_at_least(max(steps, 16)))

            fin, diestat, lane = fused_core(
                stacked, n_dies_local, pipelined, timing,
                prio=(mode == "prio"), caps=caps, steps=steps)
            for j, (_, i, _) in enumerate(chunk):
                r, _, lane_idx, rid, _, _, _ = prepped[i]
                rows = slice(j * n_ch, (j + 1) * n_ch)
                results[i] = _assemble_result(
                    r.cfg, rid, lane_idx, fin[rows], diestat[rows],
                    lane[rows], r.n_requests, fused_cells=C)
    return results
