"""Event-driven multi-queue SSD simulator (MQSim-analogue), layered.

A true discrete-event simulation of what matters for read-retry latency at
the device level:

  * 8 channels x 8 dies; per-die queues under a pluggable scheduling
    policy and FCFS channel arbitration;
  * every retry attempt senses on the die, transfers over the shared
    channel, and decodes on the channel's LDPC engine — retries consume
    channel bandwidth, so heavy retry regresses *other* dies' reads too.
    (With one LDPC engine per channel and tECC < tDMA the decode stage can
    never backpressure a serial channel, so decode is folded in as a fixed
    +tECC after each transfer — an exact simplification, not an
    approximation.)
  * CACHE READ semantics for PR²: the die has a page register and a cache
    register; sensing of attempt i+1 overlaps the transfer+decode of
    attempt i (the copy into the cache register waits for the previous
    transfer to finish); one speculative sense is charged to die occupancy
    when a retried sequence terminates;
  * AR² scales every attempt's tR by the characterized safe scale for the
    operating condition — resolved **per block** when the FTL tracks
    block wear — and samples attempt counts from the reduced-tR retry
    distribution so its rare extra attempts are charged;
  * the SOTA baseline [25] starts the retry search at its predicted entry,
    shrinking attempt counts ~70%.

Per-read attempt counts are sampled from the 160-chip characterization
histograms (repro.core.characterize) for the simulated (retention, P/E)
condition — the same transplant of real-device statistics into MQSim that
the paper performs.

Layered architecture
--------------------
This module is the orchestration layer of a four-module package:

  * :mod:`repro.flashsim.engine` — the array event-core: integer-opcode
    heap records ``(time, seq << 40 | op_id << 2 | opcode)``, the
    busy-until channel collapse, and op-kind dispatch;
  * :mod:`repro.flashsim.sched` — die-queue scheduling policies
    (``fcfs`` / ``host_prio`` / ``preempt``, selected by
    ``SSDConfig.scheduler`` or the run APIs' ``scheduler=`` knob);
  * :mod:`repro.flashsim.gc_online` — completion-time-triggered garbage
    collection (``GCConfig.mode = "online"`` or the ``gc="online"``
    knob);
  * **this module** — policy/CDF setup, batched attempt sampling, run
    orchestration (:class:`SSDSim`), statistics, and the
    ``simulate`` / ``compare_mechanisms`` / ``simulate_batch`` run APIs;
  * :mod:`repro.flashsim.runtime` — the parallel sweep executor behind
    the run APIs' ``workers=`` knob (process-pool fan-out of grid cells
    with deterministic assembly), complementing the engine's
    per-channel ``shard=`` decomposition.

The whole trace is expanded to flat per-page-op NumPy arrays up front
(:func:`expand_trace`); attempt counts for every read page are sampled in
one batched pass (RNG-stream-compatible with the retired per-request
sampler), and the event core interprets the flat schedule.

FTL / garbage collection (``SSDConfig.gc.enabled``)
---------------------------------------------------
By default writes program in place and the flash never fills.  With the
page-mapping FTL enabled (:mod:`repro.flashsim.ftl`):

  * ``gc="prepass"`` (default): a deterministic pre-pass maps every host
    op and interleaves GC copy-back page-ops into the admission stream —
    the PR 2 behavior, retained as the compatibility mode the
    equivalence suite pins;
  * ``gc="online"``: the FTL advances *inside* the event loop — writes
    allocate at simulated program start, GC triggers on free-block-pool
    watermarks, erased blocks return to the pool when their erase
    completes, and writes stall when the pool runs dry (see
    :mod:`repro.flashsim.gc_online`).

Either way GC page-ops run through the same heap and contend with host
reads on the die queues, GC reads sample retry attempts at the victim
block's *per-block* wear (``OperatingCondition.with_wear``), and — new
in this layer — AR² resolves its safe tR scale per block as well, so a
worn block senses at the scale its own characterization bin allows
rather than the device-level one.

The seed engine (PR 1's closure-based DES) is preserved in
:mod:`repro.flashsim.engine_ref` (``engine="reference"``); the array core
reproduces its SimStats bit-for-bit on fixed in-place traces under the
default ``scheduler="fcfs"`` (see tests/test_flashsim_equiv.py and
tests/test_sched.py) at a large wall-clock speedup (tracked in
``BENCH_sim.json`` by ``benchmarks/microbench_sim.py``).  One caveat:
die releases are scheduled with issue-time sequence numbers, so when two
events collide at the *exact same float timestamp* their order can
differ from the reference engine's; such ties are rare (a handful of
requests per hundred thousand) and shift per-request times by at most a
transfer slot, leaving every distribution statistically unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterable, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core import characterize as CH
from repro.core.retry import RetryPolicy
from repro.flashsim.config import (
    DEFAULT_SSD,
    FaultConfig,
    OperatingCondition,
    SSDConfig,
)
from repro.flashsim.engine import make_buffers, run_event_core
from repro.flashsim.sched import get_scheduler
from repro.flashsim.simtime import on_grid
from repro.flashsim.workloads import (
    RequestTrace,
    SyntheticSource,
    TraceSource,
    Truncate,
    Workload,
    cached_trace,
    get_source,
)

PAGE_TYPE_ORDER = ("lsb", "csb", "msb")

#: What the run APIs accept as a workload: a synthetic profile, a
#: registry spec string ("websearch", "msr:web_0?rescale=0.5", ...), or
#: any TraceSource.
WorkloadLike = Union[Workload, str, TraceSource]


def _pctl(a: np.ndarray, qs) -> np.ndarray:
    """``np.percentile(a, qs)`` for 1-D float64 without the per-call
    dispatch machinery (argument normalization costs more than the
    partition on sweep-cell-sized arrays).  Bit-identical to numpy's
    default linear method: same ``q/100 * (n-1)`` virtual indexes, the
    same shared partition across quantiles, and numpy's own two-sided
    lerp (the ``t >= 0.5`` branch computes ``b - (b-a)*(1-t)``).
    """
    n = a.size
    virt = np.true_divide(np.asarray(qs, np.float64), 100) * (n - 1)
    prev = np.floor(virt)
    nxt = np.minimum(prev + 1, n - 1)
    pi = prev.astype(np.intp)
    ni = nxt.astype(np.intp)
    part = np.partition(a, np.concatenate([pi, ni]))
    va, vb = part[pi], part[ni]
    t = virt - prev
    diff = vb - va
    out = va + diff * t
    hi = t >= 0.5
    out[hi] = vb[hi] - diff[hi] * (1 - t[hi])
    return out


def resolve_trace(
    workload: WorkloadLike, seed: int = 0, n_requests: Optional[int] = None
) -> RequestTrace:
    """Resolve a workload-like argument to a (cached, frozen) trace.

    :class:`Workload` profiles take the exact legacy path —
    ``dataclasses.replace(n_requests=...)`` + :func:`cached_trace` — so
    synthetic runs stay bit-identical to the pre-package module.  Spec
    strings resolve through :func:`repro.flashsim.workloads.registry.
    get_source`; for sources, ``n_requests`` adds a ``Truncate``
    transform (first N requests in arrival order), slotted *before* any
    dense footprint remap so the registry's canonical order — and the
    dense ``[0, footprint)`` guarantee — hold exactly as they would for
    ``?limit=N``.
    """
    if isinstance(workload, Workload):
        if n_requests is not None:
            workload = dataclasses.replace(workload, n_requests=n_requests)
        return cached_trace(workload, seed=seed)
    src = workload if isinstance(workload, TraceSource) else \
        get_source(workload)
    if n_requests is not None:
        if isinstance(src, SyntheticSource) and not src.transforms:
            # A bare profile spelled as a string regenerates at length N
            # exactly like the Workload-object call — the two spellings
            # must never diverge (truncating the full default-length
            # trace would give different arrays AND cost a 40x build).
            w = dataclasses.replace(src.workload, n_requests=n_requests)
            return cached_trace(w, seed=seed)
        from repro.flashsim.workloads.registry import POST_LIMIT_TRANSFORMS

        tfs = list(src.transforms)
        # Canonical ?limit=N position (defined by the registry order).
        at = next((i for i, t in enumerate(tfs)
                   if isinstance(t, POST_LIMIT_TRANSFORMS)), len(tfs))
        tfs.insert(at, Truncate(n_requests))
        src = dataclasses.replace(src, transforms=tuple(tfs))
    return src.trace(seed)


@dataclasses.dataclass
class SimStats:
    """Response-time statistics over completed requests.

    All times are microseconds; utilizations are fractions of the trace
    span.  The GC block (``wa`` onward) is populated only when the run
    went through the FTL (``SSDConfig.gc.enabled``); with the FTL off the
    defaults state the in-place-program facts (WA = 1.0, no GC traffic).
    ``gc_suspensions`` counts preempt-scheduler suspend events;
    ``write_stalls`` counts online-GC host-write stalls (both 0 when the
    feature is off).

    The fault block (``mispredicted_reads`` onward) is populated only
    when a fault model is attached (``SSDConfig.faults`` / the run APIs'
    ``faults=`` knob — :mod:`repro.flashsim.faults`); with faults off
    the defaults state the no-failure facts.  ``recovery_p99_us`` is the
    p99 response time over the *recovery-affected* requests only (0.0
    when none were).
    """

    mean_us: float            # mean response time over ALL requests (us)
    p50_us: float             # response-time percentiles, all requests (us)
    p95_us: float
    p99_us: float
    read_mean_us: float       # mean response time over host READS only (us)
    n_requests: int           # completed requests (reads + writes)
    mean_read_attempts: float # read attempts per host read page (>= 1)
    die_util: float           # busy fraction, averaged over dies [0, 1]
    channel_util: float       # busy fraction, averaged over channels [0, 1]
    read_p99_us: float = 0.0  # p99 response time over host READS only (us)
    wa: float = 1.0           # write amplification: phys/host programs
    gc_invocations: int = 0   # GC victim-collection passes
    gc_page_reads: int = 0    # pages read back by GC copy-back
    gc_page_progs: int = 0    # pages re-programmed by GC copy-back
    blocks_erased: int = 0    # blocks erased by GC
    gc_suspensions: int = 0   # preempt: GC ops suspended for host reads
    write_stalls: int = 0     # online GC: host writes stalled on free pool
    mispredicted_reads: int = 0  # AR² reduced-tR decode failures (re-read)
    rescued_reads: int = 0    # uncorrectables recovered by escalation
    parity_rebuilds: int = 0  # superpage stripe rebuilds run
    rebuild_reads: int = 0    # stripe-peer read page-ops issued
    retired_blocks: int = 0   # bad blocks retired
    program_fails: int = 0    # host programs that needed a reprogram
    erase_fails: int = 0      # erases that failed verification
    unrecoverable: int = 0    # reads lost after the full recovery ladder
    recovery_p99_us: float = 0.0  # p99 response over recovery-affected reqs
    # Closed-loop block: populated only when the NCQ frontend is on
    # (``SSDConfig.ncq_depth`` / the run APIs' ``ncq_depth=`` knob).
    # Response time decomposes exactly:  response = hostq wait
    # + device time + host_overhead_us.
    hostq_wait_mean_us: float = 0.0   # mean admission wait in the host queue
    hostq_wait_p99_us: float = 0.0    # p99 admission wait
    device_mean_us: float = 0.0       # mean admit -> complete device time
    read_device_p99_us: float = 0.0   # p99 device time over host reads —
    #                                   the QD-bounded latency figure
    throughput_iops: float = 0.0      # sustained n_requests / makespan
    max_inflight: int = 0             # peak admitted-and-incomplete requests
    cache_hit_reads: int = 0          # reads served entirely from the cache
    cache_hit_pages: int = 0          # read pages served from dirty lines
    cache_absorbed_writes: int = 0    # writes absorbed by the write cache
    cache_flush_pages: int = 0        # page programs issued by cache flushes
    cache_stalled_writes: int = 0     # writes that waited on cache capacity
    die_sense_util: float = 0.0       # fraction of span dies spent sensing
    #: Events retired by the batched lockstep fast path — 0 for
    #: interpreter runs, ``== n_events`` for ``engine="batched"`` runs.
    #: Observability only: excluded from equality so batched-vs-array
    #: bit-identity asserts compare the simulation outcome, not the
    #: engine that produced it.
    fast_path_events: int = dataclasses.field(default=0, compare=False)
    #: Engine that actually ran this cell — the resolved concrete engine
    #: for ``engine="auto"``, the engine's own name for explicit
    #: selections.  ``engine_fallback_reason`` is non-empty exactly when
    #: auto fell back to the interpreter: it carries the
    #: ``BatchedUnsupported`` message the explicit batched engine would
    #: have raised, so auto documents rather than hides its decision.
    #: Observability only (``compare=False``): auto-vs-explicit equality
    #: asserts compare the simulation outcome, not the selection path.
    engine_selected: str = dataclasses.field(default="", compare=False)
    engine_fallback_reason: str = dataclasses.field(default="",
                                                    compare=False)
    #: Number of sweep cells that shared this cell's kernel dispatch
    #: (0 = the cell ran alone).  Observability only (``compare=False``):
    #: fused-vs-sequential bit-identity asserts compare the simulation
    #: outcome, not the dispatch grouping.
    fused_cells: int = dataclasses.field(default=0, compare=False)

    def as_row(self) -> str:
        row = (
            f"mean={self.mean_us:9.1f}us p50={self.p50_us:8.1f} p95={self.p95_us:9.1f} "
            f"p99={self.p99_us:9.1f} attempts={self.mean_read_attempts:5.2f} "
            f"die_u={self.die_util:.2f} ch_u={self.channel_util:.2f}"
        )
        if self.wa > 1.0 or self.gc_invocations:
            row += f" wa={self.wa:.2f} gc={self.gc_invocations}"
        return row


@dataclasses.dataclass(frozen=True)
class TraceExpansion:
    """Mechanism-independent flat page-op view of a trace (admission order).

    Shared across all mechanisms of a sweep: only the per-op attempt counts
    and sense times depend on the policy, and those are sampled separately.
    """

    arrival_us: np.ndarray   # (P,) op admission time = its request's arrival (us)
    rid: np.ndarray          # (P,) owning request index
    die: np.ndarray          # (P,) die id
    chan: np.ndarray         # (P,) channel id
    ptype: np.ndarray        # (P,) page type index into PAGE_TYPE_ORDER
    is_read: np.ndarray      # (P,) bool
    page_id: np.ndarray      # (P,) logical page number (FTL input)
    n_requests: int

    @property
    def n_ops(self) -> int:
        return int(self.rid.shape[0])

    @functools.cached_property
    def admission_lists(self):
        """Mechanism-independent per-op buffers as plain Python lists.

        The event loop reads flat lists (scalar list indexing is ~4x faster
        than ndarray scalar access); converting once here instead of per
        ``run()`` lets a mechanism sweep reuse the views.
        """
        return (
            self.arrival_us.tolist(),
            self.rid.tolist(),
            self.die.tolist(),
            self.chan.tolist(),
            self.is_read.tolist(),
        )

    @functools.cached_property
    def admission_arrays(self):
        """The same per-op buffers as dtype-pinned numpy columns.

        The batched engine consumes whole columns (``_lane_tables``
        re-``asarray``s every buffer), so batched-resolved runs take the
        expansion's own arrays and skip the list round-trip entirely;
        the interpreter keeps :attr:`admission_lists` (scalar list
        indexing is faster there).  Values are identical either way.
        """
        return (
            np.asarray(self.arrival_us, np.float64),
            np.asarray(self.rid, np.int64),
            np.asarray(self.die, np.int64),
            np.asarray(self.chan, np.int64),
            np.asarray(self.is_read, bool),
        )


def expand_trace(trace: RequestTrace, cfg: SSDConfig = DEFAULT_SSD) -> TraceExpansion:
    """Vectorized request -> page-op expansion (no per-request Python loop).

    Ops come out in admission order.  Traces from :func:`generate_trace`
    arrive sorted; externally-supplied traces (e.g. future MSR/blktrace
    ingestion) may not, so unsorted arrivals are stably sorted here —
    matching the retired heap engine's (time, request-index) admission
    order exactly.
    """
    arrival = trace.arrival_us
    n = len(arrival)
    if np.any(np.diff(arrival) < 0):
        req_order = np.argsort(arrival, kind="stable")
    else:
        req_order = np.arange(n)
    n_pages = trace.n_pages[req_order]
    rid = np.repeat(req_order, n_pages)
    # Within-request page offsets 0..n_pages[r]-1, flattened.
    starts = np.cumsum(n_pages) - n_pages
    off = np.arange(int(n_pages.sum()), dtype=np.int64) - np.repeat(starts, n_pages)
    page_ids = trace.start_page[rid] + off
    die = (page_ids % cfg.n_dies).astype(np.int64)
    return TraceExpansion(
        arrival_us=trace.arrival_us[rid],
        rid=rid,
        die=die,
        chan=cfg.channel_of(die),
        ptype=(page_ids % 3).astype(np.int64),
        is_read=trace.is_read[rid],
        page_id=page_ids.astype(np.int64),
        n_requests=n,
    )


class SSDSim:
    """One simulation run = (workload trace, operating condition, policy)."""

    def __init__(
        self,
        cfg: SSDConfig = DEFAULT_SSD,
        condition: OperatingCondition = OperatingCondition(),
        policy: RetryPolicy = RetryPolicy("baseline"),
        seed: int = 0,
        engine: str = "array",
    ):
        if engine not in ("array", "batched", "auto"):
            raise ValueError(
                f"SSDSim engine must be 'array', 'batched' or 'auto', got "
                f"{engine!r} (engine='reference' is SSDSimRef)"
            )
        if engine == "batched":
            from repro.flashsim.engine_batched import check_batched_config

            check_batched_config(cfg)
        # engine="auto" defers resolution to run(), where validate= is
        # known; it never raises BatchedUnsupported — the decision (and
        # any fallback reason) is recorded on the returned SimStats.
        self.cfg = cfg
        self.cond = condition
        self.policy = policy
        self.seed = seed
        self.engine = engine
        self.rng = np.random.default_rng(seed)
        self.events_processed = 0
        # AR² tR scale for this operating condition (characterized table).
        if policy.adaptive_tr:
            if policy.tr_scale == "auto":
                self.tr_scale = CH.characterize_condition(
                    condition.retention_days, condition.pec
                ).safe_tr_scale
            else:
                self.tr_scale = float(policy.tr_scale)
        else:
            self.tr_scale = 1.0
        # Per-block AR² scale memo: snapped effective P/E -> safe scale.
        self._wear_scales: Dict[float, float] = {}
        # Worn-block attempt-CDF memo: (page type, wear) -> CDF.  One
        # resolution per distinct (condition, mechanism, wear bin) for
        # the whole run — the sharded/batched paths and every unique-wear
        # loop hit this dict instead of re-deriving the worn condition
        # and re-keying the characterization LRU per lookup.
        self._wear_cdfs: Dict[Tuple[str, float], np.ndarray] = {}
        # Unscaled per-page-type tR (scale applied per op: device-level for
        # unworn blocks, per-block for GC-worn ones).
        self._tr_base = np.array(
            [cfg.timing.tr_us[pt] for pt in PAGE_TYPE_ORDER]
        )
        # Per-page-type attempt-count CDFs under this mechanism (cached
        # across SSDSim instances in repro.core.characterize).
        self._attempt_cdfs = {
            pt: CH.attempt_cdf(
                condition.retention_days,
                condition.pec,
                page_type=pt,
                sota=policy.sota_start,
                tr_scale=self.tr_scale,
            )
            for pt in PAGE_TYPE_ORDER
        }

    # -- attempt sampling ----------------------------------------------------

    def _scale_for(self, wear_pec: float) -> float:
        """AR² tR scale at a block's effective wear (per-block resolution).

        Zero wear — or a non-adaptive / pinned-scale policy — uses the
        device-condition scale.  Worn blocks resolve the condition per
        block (``OperatingCondition.with_wear``), snap the effective P/E
        count up to the characterization grid, and look up *that* bin's
        safe scale: a worn block senses at the scale its own
        characterization allows, not the (faster) device-level one.
        Memoized per snapped bin, so the set of distinct lookups stays
        grid-bounded.
        """
        if (wear_pec <= 0.0 or not self.policy.adaptive_tr
                or self.policy.tr_scale != "auto"):
            return self.tr_scale
        worn = self.cond.with_wear(wear_pec)
        key = CH.snap_pec(worn.pec)
        s = self._wear_scales.get(key)
        if s is None:
            s = CH.characterize_condition(
                self.cond.retention_days, key
            ).safe_tr_scale
            self._wear_scales[key] = s
        return s

    def _cdf_for(self, page_type: str, wear_pec: float) -> np.ndarray:
        """Attempt CDF for one page type at a block's effective wear.

        ``wear_pec`` is the block-local added P/E count from GC erases.
        Zero wear uses the device-condition table untouched (bit-identical
        to the pre-FTL sampler); worn blocks resolve the condition per
        block (``OperatingCondition.with_wear``), snap the effective
        P/E count up to the characterization grid (so the handful of
        distinct wear bins stays cache-bounded), and — for adaptive-tR
        policies — evaluate the search at the *per-block* AR² scale
        (:meth:`_scale_for`), so the attempt distribution and the sense
        time of a worn block come from the same characterization bin.
        """
        if wear_pec <= 0.0:
            return self._attempt_cdfs[page_type]
        key = (page_type, wear_pec)
        cdf = self._wear_cdfs.get(key)
        if cdf is None:
            worn = self.cond.with_wear(wear_pec)
            cdf = CH.attempt_cdf(
                self.cond.retention_days,
                CH.snap_pec(worn.pec),
                page_type=page_type,
                sota=self.policy.sota_start,
                tr_scale=self._scale_for(wear_pec),
            )
            self._wear_cdfs[key] = cdf
        return cdf

    def _draw_attempts(self, ptype_idx: int, wear_pec: float,
                       rng: Optional[np.random.Generator] = None) -> int:
        """One attempt count at (page type, block wear).

        The online-GC driver samples reads one at a time as the mapping
        resolves them (wear is not known until the simulated instant),
        passing its per-die substream as ``rng`` so the draw order is a
        die-local property (shard-invariant); ``None`` falls back to the
        run-global ``self.rng``.
        """
        pt = PAGE_TYPE_ORDER[ptype_idx]
        r = self.rng if rng is None else rng
        a = int(np.searchsorted(self._cdf_for(pt, wear_pec), r.random()))
        return a if a > 1 else 1

    def _tr_for(self, ptype_idx: int, wear_pec: float) -> float:
        """Per-attempt sense time at (page type, block wear), on the
        simulated-time tick grid."""
        return on_grid(float(self._tr_base[ptype_idx])
                       * self._scale_for(wear_pec))

    def _sample_attempts(
        self,
        page_types: np.ndarray,
        wear_pec: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """Inverse-CDF attempt counts for a batch of page-type indices.

        Consumes ``self.rng`` exactly like the retired per-request sampler
        (one uniform per read page, in admission order), so a given seed
        yields identical attempts under both engines.  With ``wear_pec``
        (FTL runs) each read samples from the CDF of its block's effective
        wear; the uniform stream is unchanged, only the inverse CDF varies.
        """
        u = self.rng.random(page_types.shape)
        out = np.empty(page_types.shape, np.int64)
        for i, pt in enumerate(PAGE_TYPE_ORDER):
            m = page_types == i
            if not m.any():
                continue
            if wear_pec is None:
                out[m] = np.searchsorted(self._attempt_cdfs[pt], u[m])
            else:
                um, wm = u[m], wear_pec[m]
                om = np.empty(um.shape, np.int64)
                for wv in np.unique(wm):
                    sel = wm == wv
                    om[sel] = np.searchsorted(self._cdf_for(pt, float(wv)),
                                              um[sel])
                out[m] = om
        return np.maximum(out, 1)

    # -- run orchestration ---------------------------------------------------

    def _tr_scales_for_schedule(self, schedule, read_like: np.ndarray):
        """Per-op AR² scale over an FTL schedule (per-block resolution)."""
        P = schedule.n_ops
        scale = np.full(P, self.tr_scale)
        if self.policy.adaptive_tr and self.policy.tr_scale == "auto":
            wear = schedule.wear_pec
            worn = read_like & (wear > 0.0)
            if worn.any():
                for wv in np.unique(wear[worn]):
                    scale[worn & (wear == wv)] = self._scale_for(float(wv))
        return scale

    def _prepare(
        self,
        trace: RequestTrace,
        expansion: Optional[TraceExpansion] = None,
        schedule=None,
        validate: bool = False,
    ) -> "_PreparedRun":
        """Everything :meth:`run` does before the engine dispatch.

        Resolves the engine, samples the attempt schedule (consuming
        ``self.rng`` in admission order, exactly as the sequential path
        does), and builds the admission buffers.  Split out so the fused
        sweep driver can prepare many cells, run them in one kernel
        dispatch, and :meth:`_finalize` each — any fusion decision sees
        byte-identical inputs and produces byte-identical stats.
        """
        cfg, t = self.cfg, self.cfg.timing
        tprog = t.tprog_us
        pipelined = self.policy.pipelined
        sched_policy = get_scheduler(cfg.scheduler)
        gc_mode = cfg.gc.mode if cfg.gc.enabled else None
        closed = cfg.ncq_depth is not None
        engine_selected = self.engine
        engine_reason = ""
        if self.engine == "auto":
            from repro.flashsim.engine_batched import resolve_engine

            engine_selected, engine_reason = resolve_engine(cfg, validate)
        batched = engine_selected == "batched"
        if batched and self.engine == "batched":
            from repro.flashsim.engine_batched import check_batched_config

            check_batched_config(cfg)
        if closed:
            if gc_mode == "online":
                raise NotImplementedError(
                    "closed-loop frontend (ncq_depth) does not support "
                    "online GC yet — use gc='prepass'"
                )
            if sched_policy.preemptive:
                raise NotImplementedError(
                    "closed-loop frontend (ncq_depth) does not support "
                    "the preempt scheduler"
                )

        if schedule is None and gc_mode == "prepass":
            from repro.flashsim.ftl import build_ftl_schedule

            schedule = build_ftl_schedule(trace, cfg)

        fm = None
        if cfg.faults is not None:
            # Fresh model per run: per-die fault substreams seeded
            # (run seed, salt, die), separate from the attempt streams.
            from repro.flashsim.faults import FaultModel

            fm = FaultModel(cfg.faults, cfg, self.cond, self.policy,
                            self.seed, self)

        online = None
        if schedule is not None:
            # Prepass FTL path: host + GC page-ops, attempts and AR² tR
            # scale resolved per block wear.
            from repro.flashsim import ftl as _ftl

            P = schedule.n_ops
            host_read_np = schedule.kind == _ftl.OP_READ
            read_like_np = schedule.kind <= _ftl.OP_GC_READ
            attempts_np = np.ones(P, np.int64)
            attempts_np[read_like_np] = self._sample_attempts(
                schedule.ptype[read_like_np],
                schedule.wear_pec[read_like_np],
            )
            total_read_pages = int(host_read_np.sum())
            total_attempts = int(attempts_np[host_read_np].sum())
            tr_np = (self._tr_base[schedule.ptype]
                     * self._tr_scales_for_schedule(schedule, read_like_np))
            if not (fm is None and batched):
                (adm_t, op_rid, op_die, op_ch, op_read,
                 op_erase, op_dur) = schedule.admission_lists
            n_requests = schedule.n_requests
            # Only the closed-loop frontend and the fault planner read
            # the per-op lpn list; batched runs are neither.
            op_lpn = (schedule.lpn.tolist()
                      if schedule.lpn is not None and not batched
                      else None)
            if fm is None and batched:
                # Batched runs read whole columns; hand them the
                # schedule's numpy views and the per-cell sample arrays
                # directly — same values, no list round-trip.
                (adm_a, rid_a, die_a, ch_a, read_a,
                 erase_a, dur_a) = schedule.admission_arrays
                bufs = make_buffers(adm_a, rid_a, die_a, ch_a, read_a,
                                    erase_a, dur_a, attempts_np, tr_np)
            elif fm is None:
                bufs = make_buffers(adm_t, op_rid, op_die, op_ch, op_read,
                                    op_erase, op_dur, attempts_np.tolist(),
                                    tr_np.tolist())
            else:
                from repro.flashsim.faults import plan_faults

                plan = plan_faults(
                    fm, adm_t, op_rid, op_die, op_ch, op_read, op_erase,
                    op_dur, attempts_np.tolist(), tr_np.tolist(),
                    schedule.ptype.tolist(), schedule.wear_pec.tolist(),
                    lpn=op_lpn,
                )
                bufs = make_buffers(plan.arrival, plan.rid, plan.die,
                                    plan.ch, plan.read, plan.erase,
                                    plan.dur, plan.a, plan.tr)
                bufs.xa, bufs.xtr = plan.xa, plan.xtr
                op_lpn = plan.lpn
        elif gc_mode == "online":
            # Online FTL path: host ops only in the admission stream;
            # attempt counts / tR resolve at admission, GC injects live.
            from repro.flashsim.gc_online import OnlineGC

            ex = expansion if expansion is not None else expand_trace(trace, cfg)
            P = ex.n_ops
            adm_t, op_rid, op_die, op_ch, op_read = ex.admission_lists
            # The buffers grow (GC injection): copy the shared views.
            bufs = make_buffers(
                adm_t, list(op_rid), list(op_die), list(op_ch),
                list(op_read), [False] * P, [tprog] * P,
                [1] * P, [0.0] * P,
            )
            if fm is not None:
                bufs.xa = [0] * P
                bufs.xtr = [0.0] * P
            online = OnlineGC(cfg, ex, self, faults=fm)
            n_requests = ex.n_requests
            op_lpn = None
            total_read_pages = total_attempts = 0   # engine-accumulated
        else:
            ex = expansion if expansion is not None else expand_trace(trace, cfg)
            P = ex.n_ops
            read_mask = ex.is_read

            # Batched per-trace attempt schedule (admit-time work, up front).
            attempts_np = np.ones(P, np.int64)
            attempts_np[read_mask] = self._sample_attempts(ex.ptype[read_mask])
            total_read_pages = int(read_mask.sum())
            total_attempts = int(attempts_np[read_mask].sum())
            tr_np = (self._tr_base * self.tr_scale)[ex.ptype]
            n_requests = ex.n_requests
            # Only the closed-loop frontend and the fault planner read
            # the per-op lpn list; batched runs are neither.
            op_lpn = None if batched else ex.page_id.tolist()
            if fm is None and batched:
                # Batched runs read whole columns; hand them the
                # expansion's numpy views and the per-cell sample arrays
                # directly — same values, no list round-trip.
                adm_a, rid_a, die_a, ch_a, read_a = ex.admission_arrays
                bufs = make_buffers(adm_a, rid_a, die_a, ch_a, read_a,
                                    np.zeros(P, bool),
                                    np.full(P, tprog, np.float64),
                                    attempts_np, tr_np)
            elif fm is None:
                adm_t, op_rid, op_die, op_ch, op_read = ex.admission_lists
                bufs = make_buffers(adm_t, op_rid, op_die, op_ch, op_read,
                                    [False] * P,    # no erases without FTL
                                    [tprog] * P,    # write-like ops: tPROG
                                    attempts_np.tolist(), tr_np.tolist())
            else:
                adm_t, op_rid, op_die, op_ch, op_read = ex.admission_lists
                from repro.flashsim.faults import plan_faults

                plan = plan_faults(
                    fm, adm_t, op_rid, op_die, op_ch, op_read,
                    [False] * P, [tprog] * P, attempts_np.tolist(),
                    tr_np.tolist(), ex.ptype.tolist(), None,
                    lpn=op_lpn,
                )
                bufs = make_buffers(plan.arrival, plan.rid, plan.die,
                                    plan.ch, plan.read, plan.erase,
                                    plan.dur, plan.a, plan.tr)
                bufs.xa, bufs.xtr = plan.xa, plan.xtr
                op_lpn = plan.lpn

        _put_on_grid(bufs, t)
        return _PreparedRun(
            trace=trace, arrival_us=on_grid(trace.arrival_us),
            validate=validate, pipelined=pipelined,
            sched_policy=sched_policy, closed=closed, batched=batched,
            engine_selected=engine_selected, engine_reason=engine_reason,
            schedule=schedule, online=online, fm=fm, bufs=bufs,
            n_requests=n_requests, op_lpn=op_lpn,
            total_read_pages=total_read_pages,
            total_attempts=total_attempts,
        )

    def run(
        self,
        trace: RequestTrace,
        expansion: Optional[TraceExpansion] = None,
        schedule=None,
        validate: bool = False,
        shard: bool = False,
        trace_phases: bool = False,
    ) -> SimStats:
        """Simulate one trace.

        ``expansion`` (in-place and online-GC runs) or ``schedule`` (an
        :class:`repro.flashsim.ftl.FTLSchedule`, prepass-GC runs) may be
        shared across the mechanisms of a sweep.  When ``cfg.gc.enabled``
        and no schedule is supplied, the configured GC mode decides:
        ``prepass`` builds the FTL schedule here; ``online`` attaches a
        :class:`repro.flashsim.gc_online.OnlineGC` driver to the event
        core.  ``shard=True`` runs the event core as one loop per channel
        with a deterministic merge — bit-identical to the monolithic
        default (see :mod:`repro.flashsim.engine`).  ``validate=True``
        turns on the engine's work-conservation checks (test
        instrumentation).

        With ``cfg.ncq_depth`` set the run goes through the closed-loop
        frontend (:func:`repro.flashsim.engine.run_closed_loop`): NCQ-
        gated admission, optional write-back cache, explicit channel DMA
        phase.  Closed-loop supports prepass GC and faults but not the
        preempt scheduler or online GC; ``shard=`` is ignored (the NCQ
        couples channels through the shared slot pool — the monolithic
        closed loop is the defined semantics for any ``shard``/
        ``workers`` setting).  ``trace_phases=True`` (closed loop only)
        records per-op sense/transfer/program intervals into
        ``self.last_phases`` for the interval-invariant property tests.
        """
        cfg = self.cfg
        prep = self._prepare(trace, expansion=expansion,
                             schedule=schedule, validate=validate)
        bufs, n_requests = prep.bufs, prep.n_requests
        if prep.closed:
            from repro.flashsim.engine import run_closed_loop

            cache = None
            if cfg.host_cache is not None:
                from repro.flashsim.hostcache import WriteCache

                cache = WriteCache(cfg.host_cache)
            res = run_closed_loop(
                cfg, prep.pipelined, prep.sched_policy, bufs, n_requests,
                prep.arrival_us.tolist(), trace.is_read.tolist(),
                cfg.ncq_depth, op_lpn=prep.op_lpn, cache=cache,
                validate=validate, trace_phases=trace_phases,
            )
        elif prep.batched:
            from repro.flashsim.engine_batched import run_event_core_batched

            res = run_event_core_batched(cfg, prep.pipelined,
                                         prep.sched_policy, bufs,
                                         n_requests, online=prep.online,
                                         validate=validate)
        else:
            res = run_event_core(cfg, prep.pipelined, prep.sched_policy,
                                 bufs, n_requests, online=prep.online,
                                 validate=validate, shard=shard)
        return self._finalize(prep, res)

    def _finalize(self, prep: "_PreparedRun", res) -> SimStats:
        """Assemble :class:`SimStats` from one engine result — the back
        half of :meth:`run` (pure code motion from it; any change here
        is a bit-parity change for every engine and fusion decision)."""
        cfg = self.cfg
        trace = prep.trace
        schedule, online, fm = prep.schedule, prep.online, prep.fm
        closed = prep.closed
        n_requests = prep.n_requests
        engine_selected = prep.engine_selected
        engine_reason = prep.engine_reason
        total_attempts = prep.total_attempts
        total_read_pages = prep.total_read_pages
        closed_kw = {}
        if closed:
            gc_suspensions = 0
            total_attempts = res.attempts_issued
            total_read_pages = res.read_pages_issued
            self.last_phases = res.phases
        else:
            gc_suspensions = res.gc_suspensions
            self.last_phases = None
            if online is not None:
                total_attempts = res.online_attempts
                total_read_pages = res.online_read_pages
        self.events_processed = res.n_events
        self.last_gc_suspensions = gc_suspensions
        self.last_die_busy_us = float(sum(res.die_tot))

        req_done_at = np.asarray(res.req_done)
        self.last_req_done_us = req_done_at
        response = req_done_at - prep.arrival_us + cfg.host_overhead_us
        read_resp = response[trace.is_read]
        span = float(req_done_at.max())
        if closed:
            # Closed-loop span: the makespan of everything the device did
            # (flush programs / GC can outlive the last host completion).
            span = max(span, max(res.die_busy), max(res.ch_busy))
            admit_at = np.asarray(res.req_admit)
            wait = admit_at - prep.arrival_us
            device = req_done_at - admit_at
            read_dev = device[trace.is_read]
            closed_kw = dict(
                hostq_wait_mean_us=float(wait.mean()),
                hostq_wait_p99_us=float(np.percentile(wait, 99)),
                device_mean_us=float(device.mean()),
                read_device_p99_us=(
                    float(np.percentile(read_dev, 99))
                    if read_dev.size else 0.0
                ),
                throughput_iops=n_requests / span * 1e6,
                max_inflight=res.max_inflight,
                cache_hit_reads=res.full_hit_reads,
                cache_hit_pages=res.hit_pages,
                cache_absorbed_writes=res.absorbed_writes,
                cache_flush_pages=res.flush_pages,
                cache_stalled_writes=res.stalled_writes,
                die_sense_util=sum(res.die_sense_tot) / (span * cfg.n_dies),
            )
        gc_kw = {}
        if schedule is not None or online is not None:
            # GC traffic can outlive the last host completion (an erase
            # triggered by the final write holds its die past it); extend
            # the utilization span to the last resource release so
            # die/channel utilization stays a fraction in [0, 1].  After
            # the loop every die_busy/ch_busy entry is a finite release
            # time.  (In-place runs keep the host-completion span for
            # bit-parity with the reference engine.)
            span = max(span, max(res.die_busy), max(res.ch_busy))
            fs = schedule.stats if schedule is not None else online.stats()
            gc_kw = dict(
                wa=fs.write_amplification,
                gc_invocations=fs.gc_invocations,
                gc_page_reads=fs.gc_page_reads,
                gc_page_progs=fs.gc_page_progs,
                blocks_erased=fs.blocks_erased,
                gc_suspensions=gc_suspensions,
                write_stalls=online.write_stalls if online is not None else 0,
            )
        elif gc_suspensions:
            gc_kw = dict(gc_suspensions=gc_suspensions)
        fault_kw = {}
        if fm is not None:
            oc = fm.outcome
            rec_p99 = 0.0
            if oc.affected_rids:
                idx = np.fromiter(oc.affected_rids, np.int64,
                                  len(oc.affected_rids))
                rec_p99 = float(np.percentile(response[idx], 99))
            fault_kw = dict(
                mispredicted_reads=oc.mispredicted_reads,
                rescued_reads=oc.rescued_reads,
                parity_rebuilds=oc.parity_rebuilds,
                rebuild_reads=oc.rebuild_reads,
                retired_blocks=oc.retired_blocks,
                program_fails=oc.program_fails,
                erase_fails=oc.erase_fails,
                unrecoverable=oc.unrecoverable,
                recovery_p99_us=rec_p99,
            )
        # One percentile call shares the partition pass across the three
        # quantiles; per-q interpolation is unchanged, so the values are
        # bit-identical to three separate calls.
        p50, p95, p99 = _pctl(response, (50.0, 95.0, 99.0))
        return SimStats(
            mean_us=float(response.mean()),
            p50_us=float(p50),
            p95_us=float(p95),
            p99_us=float(p99),
            read_mean_us=float(read_resp.mean()) if read_resp.size else 0.0,
            n_requests=n_requests,
            mean_read_attempts=(
                total_attempts / total_read_pages if total_read_pages else 0.0
            ),
            die_util=sum(res.die_tot) / (span * cfg.n_dies),
            channel_util=sum(res.ch_tot) / (span * cfg.n_channels),
            read_p99_us=(
                float(_pctl(read_resp, (99.0,))[0]) if read_resp.size
                else 0.0
            ),
            fast_path_events=getattr(res, "fast_path_events", 0),
            engine_selected=engine_selected,
            engine_fallback_reason=engine_reason,
            fused_cells=getattr(res, "fused_cells", 0),
            **gc_kw,
            **fault_kw,
            **closed_kw,
        )


def _put_on_grid(bufs, timing) -> None:
    """Round the event core's time inputs onto the simulated-time tick.

    Arrivals, durations, sense times (and fault re-read sense times),
    tDMA and tECC — the inputs every engine shares — are rounded here,
    once per run (:mod:`repro.flashsim.simtime`), so the interpreter's
    f64 arithmetic and the lockstep core's int64 ticks agree exactly.
    """
    for name in ("arrival", "dur", "tr", "xtr"):
        v = getattr(bufs, name)
        if v is not None:
            g = on_grid(v)
            setattr(bufs, name, g if isinstance(v, np.ndarray)
                    else g.tolist())
    bufs.tdma = on_grid(float(timing.tdma_us))
    bufs.tecc = on_grid(float(timing.tecc_us))


@dataclasses.dataclass
class _PreparedRun:
    """Inputs of one engine dispatch, held between :meth:`SSDSim._prepare`
    and :meth:`SSDSim._finalize` so the fused sweep driver can batch many
    cells into one kernel launch."""

    trace: RequestTrace
    arrival_us: np.ndarray    # the trace's arrivals on the tick grid
    validate: bool
    pipelined: bool
    sched_policy: object
    closed: bool
    batched: bool
    engine_selected: str
    engine_reason: str
    schedule: object
    online: object
    fm: object
    bufs: object
    n_requests: int
    op_lpn: object
    total_read_pages: int
    total_attempts: int


def _run_prepared_fused(items):
    """Run many prepared batched-eligible cells in fused kernel dispatches.

    ``items``: sequence of ``(sim, prep)`` pairs (from
    :meth:`SSDSim._prepare`, every cell resolved to the batched engine).
    Dispatches them through
    :func:`repro.flashsim.engine_batched.run_event_cores_fused` — cells
    grouped by static kernel parameters, each group one kernel launch —
    and finalizes each cell on its own sim.  Bit-identical to calling
    ``sim.run(...)`` per cell (the cell-axis law); raises
    :class:`~repro.flashsim.engine_batched.BatchedUnsupported` before
    any dispatch if a cell is ineligible (callers pre-filter, so this is
    a fail-fast guard, never a silent fallback).  Returns one
    :class:`SimStats` per item, in order.
    """
    from repro.flashsim.engine_batched import (FusedRun,
                                               run_event_cores_fused)

    runs = [FusedRun(sim.cfg, prep.pipelined, prep.sched_policy,
                     prep.bufs, prep.n_requests) for sim, prep in items]
    res_list = run_event_cores_fused(runs)
    return [sim._finalize(prep, res)
            for (sim, prep), res in zip(items, res_list)]


# -- run API ---------------------------------------------------------------


def _with_knobs(
    cfg: SSDConfig, scheduler: Optional[str], gc: Optional[str],
    faults: Optional[FaultConfig] = None,
    ncq_depth: Optional[int] = None,
    host_cache=None,
) -> SSDConfig:
    """Overlay the run-API ``scheduler=`` / ``gc=`` / ``faults=`` knobs
    onto a config.

    ``scheduler`` picks the die-queue policy; ``gc`` is ``"off"``,
    ``"prepass"``, or ``"online"`` (the latter two imply
    ``gc.enabled=True``); ``faults`` attaches a
    :class:`~repro.flashsim.config.FaultConfig`; ``ncq_depth`` /
    ``host_cache`` switch on the closed-loop frontend
    (:class:`~repro.flashsim.config.HostCacheConfig`).  None leaves the
    config untouched.
    """
    if scheduler is not None:
        cfg = dataclasses.replace(cfg, scheduler=scheduler)
    if faults is not None:
        cfg = dataclasses.replace(cfg, faults=faults)
    if ncq_depth is not None:
        cfg = dataclasses.replace(cfg, ncq_depth=ncq_depth)
    if host_cache is not None:
        cfg = dataclasses.replace(cfg, host_cache=host_cache)
    if gc is not None:
        if gc == "off":
            gcc = dataclasses.replace(cfg.gc, enabled=False)
        elif gc in ("prepass", "online"):
            gcc = dataclasses.replace(cfg.gc, enabled=True, mode=gc)
        else:
            raise ValueError(
                f"gc knob must be 'off', 'prepass' or 'online', got {gc!r}"
            )
        cfg = dataclasses.replace(cfg, gc=gcc)
    return cfg


def _shared_views(trace, cfg):
    """(expansion, schedule) pair shared by every mechanism of a sweep.

    Online GC has no shareable schedule (the FTL advances inside each
    run), so only the expansion is shared there.
    """
    expansion = expand_trace(trace, cfg)
    if not cfg.gc.enabled or cfg.gc.mode != "prepass":
        return expansion, None
    from repro.flashsim.ftl import build_ftl_schedule

    return expansion, build_ftl_schedule(trace, cfg, expansion=expansion)


def _fuse_resolved(cfg, engine: str, fuse: Optional[bool]) -> bool:
    """Whether a sweep over ``cfg`` takes the fused batched path.

    True iff fusion is enabled (the ``fuse=`` knob, defaulting to
    ``cfg.fuse``) *and* the config resolves inside the batched matrix
    for the requested engine.  ``engine="batched"`` with an ineligible
    config returns False so the sequential loop raises the exact
    :class:`BatchedUnsupported` the non-fused path would — fusion never
    changes error behavior, and ``engine="auto"`` fallbacks record
    their reason per cell as before.
    """
    if engine not in ("batched", "auto"):
        return False
    if not (cfg.fuse if fuse is None else fuse):
        return False
    from repro.flashsim.engine_batched import resolve_engine

    return resolve_engine(cfg)[0] == "batched"


def _make_sim(cfg, condition, mechanism, seed, engine):
    if engine in ("array", "batched", "auto"):
        # "batched": SSDSim validates the config against the batched
        # core's supported matrix (ring-lowerable scheduler / gc
        # off|prepass / no faults / open loop) and raises
        # BatchedUnsupported outside it.  "auto" never raises — it
        # resolves per run (validate-aware) and records the decision on
        # SimStats.engine_selected / engine_fallback_reason.
        return SSDSim(cfg, condition, RetryPolicy(mechanism), seed=seed,
                      engine=engine)
    if engine == "reference":
        if cfg.faults is not None:
            raise NotImplementedError(
                "faults require the array engine (the reference engine "
                "predates the fault-injection subsystem)"
            )
        if cfg.ncq_depth is not None:
            raise NotImplementedError(
                "the closed-loop frontend (ncq_depth) requires the array "
                "engine"
            )
        from repro.flashsim.engine_ref import SSDSimRef

        return SSDSimRef(cfg, condition, RetryPolicy(mechanism), seed=seed)
    raise ValueError(
        f"unknown engine {engine!r} (use 'array', 'batched', 'auto' or "
        f"'reference')"
    )


def simulate(
    workload: WorkloadLike,
    condition: OperatingCondition,
    mechanism: str,
    seed: int = 0,
    cfg: SSDConfig = DEFAULT_SSD,
    n_requests: Optional[int] = None,
    trace: Optional[RequestTrace] = None,
    engine: Optional[str] = None,
    scheduler: Optional[str] = None,
    gc: Optional[str] = None,
    shard: bool = False,
    faults: Optional[FaultConfig] = None,
    ncq_depth: Optional[int] = None,
    host_cache=None,
    validate: bool = False,
) -> SimStats:
    """Convenience wrapper: one (workload, condition, mechanism) cell.

    ``workload`` is a synthetic :class:`Workload` profile, a trace-source
    spec string (``"websearch"``, ``"msr:web_0?rescale=0.5"`` — see
    :mod:`repro.flashsim.workloads.registry`), or any
    :class:`~repro.flashsim.workloads.TraceSource`.  Pass ``trace=`` to
    reuse a pre-generated trace across calls (all mechanisms then see
    the *same* arrivals); otherwise the trace is resolved (and memoized)
    from ``(workload, seed)``.  ``scheduler=`` (``"fcfs"`` /
    ``"host_prio"`` / ``"host_prio_aged"`` / ``"preempt"``) and ``gc=``
    (``"off"`` / ``"prepass"`` / ``"online"``) overlay the config without
    building an ``SSDConfig`` by hand.  With GC enabled the trace runs
    through the page-mapping FTL (:mod:`repro.flashsim.ftl`) and the
    returned stats carry WA/GC counters; the reference engine predates
    the FTL and the scheduler layer and rejects both.  ``shard=True``
    runs the array event core as one loop per channel (bit-identical;
    :mod:`repro.flashsim.engine`); the reference engine rejects it.
    ``engine="batched"`` runs all channel loops in lockstep inside one
    compiled kernel (:mod:`repro.flashsim.engine_batched`) — bit-
    identical to the array engine on its supported matrix (fcfs /
    host_prio / host_prio_aged[:bound] schedulers, gc off/prepass, no
    faults, open loop) and raising
    :class:`~repro.flashsim.engine_batched.BatchedUnsupported`
    elsewhere, never silently falling back.  ``engine="auto"`` picks the
    batched core when the cell is inside that matrix and the array
    interpreter otherwise — results identical either way, with the
    decision (and any fallback reason) recorded on
    ``SimStats.engine_selected`` / ``engine_fallback_reason``.
    ``faults=`` attaches a :class:`~repro.flashsim.config.FaultConfig`
    (:mod:`repro.flashsim.faults` — array engine only).  ``ncq_depth=``
    switches on the closed-loop frontend (bounded NCQ admission, explicit
    channel DMA phase); ``host_cache=`` additionally attaches the host
    write-back cache (:class:`~repro.flashsim.config.HostCacheConfig`).
    Closed-loop runs are always monolithic (``shard`` is ignored) and
    reject the preempt scheduler, online GC, and the reference engine.
    """
    if engine is None:
        engine = cfg.engine
    cfg = _with_knobs(cfg, scheduler, gc, faults, ncq_depth, host_cache)
    if trace is None:
        trace = resolve_trace(workload, seed=seed, n_requests=n_requests)
    sim = _make_sim(cfg, condition, mechanism, seed + 7, engine)
    if shard:
        if engine == "reference":
            raise NotImplementedError(
                "shard=True requires the array engine (the reference "
                "engine predates the sharded event core)"
            )
        # engine="batched" IS the per-channel decomposition: shard=True
        # is a no-op there (the lockstep core always runs one lane per
        # channel, bit-identical to both array paths).
        return sim.run(trace, shard=True, validate=validate)
    return sim.run(trace, validate=validate)


def compare_mechanisms(
    workload: WorkloadLike,
    condition: OperatingCondition,
    mechanisms=("baseline", "sota", "pr2", "ar2", "pr2ar2", "sota+pr2ar2"),
    seed: int = 0,
    cfg: SSDConfig = DEFAULT_SSD,
    n_requests: Optional[int] = None,
    engine: Optional[str] = None,
    scheduler: Optional[str] = None,
    gc: Optional[str] = None,
    shard: bool = False,
    workers: int = 1,
    faults: Optional[FaultConfig] = None,
    ncq_depth: Optional[int] = None,
    host_cache=None,
    fuse: Optional[bool] = None,
) -> Dict[str, SimStats]:
    """All mechanisms over ONE shared trace (resolved once, expanded once).

    ``workload`` accepts profiles, registry spec strings, and
    :class:`TraceSource`\\ s (see :func:`resolve_trace`) — real ingested
    traces replay through the identical shared-trace machinery.  With
    prepass GC the FTL pre-pass also runs once and its schedule is
    shared: every mechanism sees identical GC traffic and per-block wear,
    so mechanism deltas isolate the retry policy.  (Online GC advances
    the FTL inside each run — mechanisms still share the trace and
    expansion, but GC timing legitimately responds to each mechanism's
    latencies.)  ``shard=True`` selects the per-channel sharded event
    core; ``workers > 1`` fans mechanisms over a process pool
    (:func:`repro.flashsim.runtime.run_compare` — fork platforms and
    the ``array`` engine only, results identical to the inline run;
    compares that may run the lockstep core stay in this process, and
    ``engine="reference"`` runs its mechanisms sequentially as before).
    ``ncq_depth=`` / ``host_cache=`` select the closed-loop frontend for
    every mechanism (see :func:`simulate`).  ``fuse=`` controls the
    fused sweep path (default ``cfg.fuse``): when the config resolves
    inside the batched matrix, the mechanisms' op tables are stacked
    along the kernel's lane axis and dispatched together (one launch
    per static-shape group) — results bit-identical to the sequential
    batched runs either way.
    """
    if engine is None:
        engine = cfg.engine
    cfg = _with_knobs(cfg, scheduler, gc, faults, ncq_depth, host_cache)
    if workers > 1 and engine in ("array", "batched", "auto"):
        from repro.flashsim.runtime import run_compare

        return run_compare(workload, condition, mechanisms, seed, cfg,
                           n_requests, None, None, shard, workers,
                           engine=engine, fuse=fuse)
    trace = resolve_trace(workload, seed=seed, n_requests=n_requests)
    if engine == "reference":
        return {
            m: simulate(workload, condition, m, seed, cfg, trace=trace,
                        engine=engine, shard=shard)
            for m in mechanisms
        }
    expansion, schedule = _shared_views(trace, cfg)
    if _fuse_resolved(cfg, engine, fuse) and len(tuple(mechanisms)) > 1:
        items = []
        for m in mechanisms:
            sim = _make_sim(cfg, condition, m, seed + 7, engine)
            items.append((sim, sim._prepare(trace, expansion=expansion,
                                            schedule=schedule)))
        return dict(zip(mechanisms, _run_prepared_fused(items)))
    out = {}
    for m in mechanisms:
        sim = _make_sim(cfg, condition, m, seed + 7, engine)
        out[m] = sim.run(trace, expansion=expansion, schedule=schedule,
                         shard=shard)
    return out


def simulate_batch(
    workload: WorkloadLike,
    conditions: Iterable[OperatingCondition],
    mechanisms: Sequence[str] = (
        "baseline", "sota", "pr2", "ar2", "pr2ar2", "sota+pr2ar2",
    ),
    seeds: Sequence[int] = (0,),
    cfg: SSDConfig = DEFAULT_SSD,
    n_requests: Optional[int] = None,
    engine: Optional[str] = None,
    scheduler: Optional[str] = None,
    gc: Optional[str] = None,
    shard: bool = False,
    workers: int = 1,
    faults: Optional[FaultConfig] = None,
    journal=None,
    ncq_depth: Optional[int] = None,
    host_cache=None,
    fuse: Optional[bool] = None,
) -> Dict[Tuple[str, OperatingCondition, int], SimStats]:
    """Sweep (mechanism x condition x seed) cells for one workload.

    Throughput-structured: each seed's trace is generated and expanded
    once — and, with prepass GC, run through the FTL pre-pass once —
    then shared by every (mechanism, condition) cell; characterization
    tables (AR² safe scales, attempt histograms) are memoized per
    condition in :mod:`repro.core.characterize`, so the grid pays each
    JAX characterization exactly once.  ``workload`` accepts profiles,
    registry spec strings, and :class:`TraceSource`\\ s; for
    deterministic file traces, seed variation comes from seeded
    transforms (e.g. ``?sample=0.9``) — without one, every seed replays
    the same trace (only attempt sampling varies, via ``seed + 7``).
    ``shard=True`` selects the per-channel sharded event core;
    ``workers > 1`` schedules seed groups across a process pool
    (:func:`repro.flashsim.runtime.run_sweep`) — cell values and dict
    order are identical for every worker count.  ``faults=`` attaches a
    :class:`~repro.flashsim.config.FaultConfig` to every cell;
    ``journal=`` names a checkpoint file — completed cells are recorded
    as they finish and a re-run resumes from them byte-identically
    (:func:`repro.flashsim.runtime.run_cells`).
    ``ncq_depth=`` / ``host_cache=`` select the closed-loop frontend for
    every cell (see :func:`simulate`).  ``fuse=`` controls the fused
    sweep path (default ``cfg.fuse``): when the config resolves inside
    the batched matrix, each seed's (condition × mechanism) cells are
    stacked along the kernel's lane axis and dispatched together (one
    launch per static-shape group) — cell values bit-identical to the
    sequential batched runs for any fusion decision.
    Returns ``{(mechanism, condition, seed): SimStats}``.
    """
    if engine is None:
        engine = cfg.engine
    if shard and engine == "reference":
        raise NotImplementedError(
            "shard=True requires the array engine (the reference engine "
            "predates the sharded event core)"
        )
    cfg = _with_knobs(cfg, scheduler, gc, faults, ncq_depth, host_cache)
    if workers > 1 or journal is not None:
        from repro.flashsim.runtime import run_sweep

        # Engine-agnostic: seed-group cells re-enter this function with
        # workers=1 inside each worker, reference engine included.
        return run_sweep(workload, conditions, mechanisms, seeds, cfg,
                         n_requests, engine, None, None, shard, workers,
                         journal=journal, fuse=fuse)
    conditions = tuple(conditions)
    seeds = tuple(seeds)
    fused = (_fuse_resolved(cfg, engine, fuse)
             and len(conditions) * len(mechanisms) * len(seeds) > 1)
    if fused:
        # Cross-seed fusion: every (seed, condition, mechanism) cell of
        # the grid is prepared (each seed's trace resolved and expanded
        # once, shared by its cells) and dispatched through ONE fused
        # engine call — the engine chunks the whole grid by static
        # kernel shape and step homogeneity, so same-condition cells of
        # different seeds share a dispatch.  Output order (seed-major)
        # is unchanged.
        keys, items = [], []
        for s in seeds:
            trace = resolve_trace(workload, seed=s,
                                  n_requests=n_requests)
            expansion, schedule = _shared_views(trace, cfg)
            for cond in conditions:
                for m in mechanisms:
                    sim = _make_sim(cfg, cond, m, s + 7, engine)
                    keys.append((m, cond, s))
                    items.append((sim, sim._prepare(
                        trace, expansion=expansion, schedule=schedule)))
        return dict(zip(keys, _run_prepared_fused(items)))
    out: Dict[Tuple[str, OperatingCondition, int], SimStats] = {}
    for s in seeds:
        trace = resolve_trace(workload, seed=s, n_requests=n_requests)
        if engine in ("array", "batched", "auto"):
            expansion, schedule = _shared_views(trace, cfg)
        else:
            expansion = schedule = None
        for cond in conditions:
            for m in mechanisms:
                sim = _make_sim(cfg, cond, m, s + 7, engine)
                if expansion is not None:
                    out[(m, cond, s)] = sim.run(trace, expansion=expansion,
                                                schedule=schedule,
                                                shard=shard)
                else:
                    out[(m, cond, s)] = sim.run(trace)
    return out
