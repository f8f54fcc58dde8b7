"""Dispatch wrapper for the lockstep sched-aware shard core.

``fcfs_core`` takes the padded per-lane op table as numpy (µs, f64),
converts its times to int64 ticks of :mod:`repro.flashsim.simtime` —
raising on a time off the tick grid, never rounding — runs the core as
one jitted XLA program on CPU and TPU alike, and returns numpy results
in µs.  There is no interpret mode and no fallback: the program runs on
JAX's default device, and fails there rather than falling back; the
only thing the platform picks is how the program writes its carry
(:func:`carry_lowering`), which never changes a result.  All JAX work
happens inside a scoped ``jax.enable_x64`` context so the int64
requirement never leaks into the process-global JAX config (the
characterization compiles under the default 32 bits).

Integer ticks are what make the result exact on every backend: the
interpreter's f64 add/max on on-grid values is exact, and so is the
core's int64 arithmetic.  The TPU emulates f64 with pairs of f32, which
is not IEEE f64; its emulated s64 add/max/compare are exact.

Compiled-variant reuse (the dispatch-overhead contract)
-------------------------------------------------------
The core is jit-cached per (lane count, padded width, die count, ring
capacities, pipelined flag, scheduler lowering, carry lowering — the
last chosen by :func:`carry_lowering` from the device's platform and
the lane count); the step count, timing
constants, and aging bound are *traced*, so different workload sizes,
timing models, and ``host_prio_aged`` bounds all reuse one executable.
Every static shape is bucketed to a power of two with a small floor
(``pad_ops``, ``ring_caps``, ``capsteps``), so a sweep grid's cells
collapse onto a handful of compiled variants.  On top of the in-process
jit cache, the first dispatch turns on JAX's *persistent* compilation
cache at :func:`compile_cache_dir`, so a fresh process of the same
checkout skips XLA compilation after the first run.
"""

from __future__ import annotations

import os
import pathlib
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.flashsim import obs
from repro.flashsim.simtime import from_ticks, to_ticks
from repro.kernels.fcfs_core.kernel import (_ARR, _DUR, _GDT, _KIND, _TR,
                                            NEVER, fcfs_core_fwd)


#: The checkout this module runs from (``src/repro/kernels/fcfs_core``
#: is four levels below it).
_CHECKOUT = pathlib.Path(__file__).resolve().parents[4]

_COMP_CACHE_READY = False


def compile_cache_dir() -> Optional[str]:
    """Directory of JAX's persistent compilation cache.

    ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself);
    otherwise the fixed ``.jax_cache`` directory of the checkout — a
    stable path, so every process of this checkout hits the same
    entries.  ``None`` when the package does not run from a checkout
    (no ``pyproject.toml`` beside ``src/``): no persistent cache then.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if (_CHECKOUT / "pyproject.toml").is_file():
        return str(_CHECKOUT / ".jax_cache")
    return None


def _enable_persistent_cache() -> None:
    """Turn on JAX's persistent compilation cache at
    :func:`compile_cache_dir` (idempotent).

    Sets the directory only when ``JAX_COMPILATION_CACHE_DIR`` is unset.
    The size and compile-time thresholds are zeroed so every lockstep
    variant is persisted, however fast it compiled.
    """
    global _COMP_CACHE_READY
    if _COMP_CACHE_READY:
        return
    _COMP_CACHE_READY = True
    d = compile_cache_dir()
    if d is None:
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


_core_jit = jax.jit(
    fcfs_core_fwd,
    static_argnames=("n_dies", "capq", "capw", "capsteps", "pipelined",
                     "prio", "lowering"))


#: Static keys of the core variants this process has dispatched.
_VARIANTS = set()


def __getattr__(name):
    # ``KERNEL_DISPATCHES``: the number of kernel dispatches issued by
    # this process (both the per-run and the fused entry points), read
    # from the recorder's ``dispatches`` counter.
    if name == "KERNEL_DISPATCHES":
        return obs.total("dispatches")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


#: Lane counts above this take the ``"scatter"`` carry lowering on CPU.
#: The unrolled per-lane dynamic_update_slice is measurably faster there
#: everywhere the fused sweep operates (its cell cap keeps stacked
#: dispatches at or under 64 lanes), so the scatter only takes over
#: beyond that — oversized single-cell topologies where the unroll
#: would bloat the traced loop body.
_WIDE_LANES = 64


def carry_lowering(platform: str, lanes: int) -> str:
    """The core's carry lowering for a dispatch of ``lanes`` lanes on a
    device of ``platform`` (see :mod:`repro.kernels.fcfs_core.kernel`).

    ``"select"`` on the TPU at any lane count: there every per-lane
    update waits on its indices' handoff to the scalar unit.  Elsewhere
    ``"unrolled"`` up to :data:`_WIDE_LANES` lanes, ``"scatter"`` above.
    """
    if platform == "tpu":
        return "select"
    return "unrolled" if lanes <= _WIDE_LANES else "scatter"


def _platform(x) -> str:
    """Platform of the one device that holds array ``x``."""
    (dev,) = x.devices()
    return dev.platform


def pad_width(widest: int) -> int:
    """Padded-table width bucket: next power of two strictly above
    ``widest`` (floor 16), the :func:`pad_ops` policy."""
    maxp = 16
    while maxp <= widest:
        maxp *= 2
    return maxp


def pad_ops(lanes_ops, maxp: Optional[int] = None) -> np.ndarray:
    """Stack per-lane (P_l, 7) op tables into one padded (L, MAXP, 7).

    Pad rows carry ``arrival = inf`` (the admission cursor's stop
    sentinel) and ``hp = 0.0``; the padded width is the next power of
    two strictly above the widest lane (floor 16), so the cursor's
    clipped lookahead always lands on a pad row and nearby cell sizes
    share one compiled variant.  ``maxp`` forces a wider bucket (the
    fused sweep pads every cell of a group to the group-wide bucket);
    it must still exceed the widest lane.
    """
    L = len(lanes_ops)
    widest = max((t.shape[0] for t in lanes_ops), default=0)
    if maxp is None:
        maxp = pad_width(widest)
    elif maxp <= widest:
        raise ValueError(f"maxp {maxp} <= widest lane {widest}")
    ops = np.full((L, maxp, 7), np.inf, dtype=np.float64)
    ops[:, :, 1] = 3.0          # kind: pad
    ops[:, :, 2] = 0.0          # pad die: keep int casts well-defined
    ops[:, :, 6] = 0.0          # pad hp: low class, never enqueued
    for l, t in enumerate(lanes_ops):
        ops[l, :t.shape[0]] = t
    return ops


def augment_ops(ops: np.ndarray, pipelined: bool) -> np.ndarray:
    """Append the host-precomputed grant-attribute columns.

    ``gdt`` — delta from grant time to the op's first event (tR for
    reads, dur for writes/erases); ``gk0`` — the first event's kind
    (0 sense, 1 release), which doubles as the op's non-read flag;
    ``grem0`` — initial remaining-attempt counter (serial mode counts
    down from ``attempts``; pipelined counts issued copies up from 0).
    These collapse the read/write/erase dispatch at grant time to
    single blends inside the kernel.
    """
    kind = ops[:, :, 1]
    is_read = kind == 0.0
    gdt = np.where(is_read, ops[:, :, 5], ops[:, :, 3])
    gk0 = np.where(is_read, 0.0, 1.0)
    if pipelined:
        grem0 = np.zeros_like(gdt)
    else:
        grem0 = np.where(is_read, ops[:, :, 4], 0.0)
    return np.concatenate(
        [ops, np.stack([gdt, gk0, grem0], axis=2)], axis=2)


def lane_steps(ops: np.ndarray) -> np.ndarray:
    """Per-lane lockstep step bound: admissions + heap pops of each lane.

    Per op the interpreter pops ``attempts + 1`` events for a read
    (senses + release), 2 for a write (transfer-landed + release), and 1
    for an erase (release) — computable up front because the supported
    matrix has no preemption or online injection.  Priority policies
    reorder events but never change their count, so the bound is
    lowering-independent.
    """
    kind = ops[:, :, 1]
    att = ops[:, :, 4]
    is_r = kind == 0.0
    per_op = np.where(is_r, np.where(np.isfinite(att), att, 0.0) + 1.0,
                      np.where(kind == 1.0, 2.0,
                               np.where(kind == 2.0, 1.0, 0.0)))
    n_adm = (kind != 3.0).sum(axis=1)
    return (n_adm + per_op.sum(axis=1)).astype(np.int64)


def count_steps(ops: np.ndarray) -> int:
    """Lockstep step bound of a dispatch: the max of :func:`lane_steps`
    (every lane runs it; finished lanes no-op)."""
    return int(lane_steps(ops).max(initial=0))


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def ring_caps(ops: np.ndarray, n_dies: int):
    """Static FIFO/ACQ ring capacities for a padded op table.

    ``capq`` bounds the deepest per-die FIFO (every op targeting a die
    can be queued there at once, at most — and per-class occupancy of
    the dual priority rings is bounded by the same per-die total, so
    one capacity serves both lowerings); ``capw`` bounds the in-flight
    write transfers of a lane (each write pushes ACQ exactly once).
    Rounded up to powers of two with a floor of 4 so jit variants stay
    few and the ``%`` ring arithmetic is trivially safe for op-free
    lanes.
    """
    kind = ops[:, :, 1]
    real = kind != 3.0
    per_die = 0
    if real.any():
        # One flat bincount over (lane, die) pairs — same counts as a
        # per-lane loop, without L Python iterations.
        lane_of = np.broadcast_to(
            np.arange(ops.shape[0])[:, None], kind.shape)
        flat = lane_of[real] * n_dies + ops[:, :, 2][real].astype(np.int64)
        per_die = int(np.bincount(flat).max())
    writes = int((kind == 1.0).sum(axis=1).max(initial=0.0))
    return _pow2_at_least(max(per_die, 4)), _pow2_at_least(max(writes, 4))


def dispatch_caps(ops: np.ndarray, n_dies: int, steps: int):
    """Static ``(capq, capw, capsteps)`` of a dispatch: :func:`ring_caps`
    and the step count bucketed to a power of two (floor 16)."""
    return (*ring_caps(ops, n_dies), _pow2_at_least(max(steps, 16)))


def _tick_table(aug: np.ndarray) -> np.ndarray:
    """The augmented (L, MAXP, 10) f64 op table as int64: time columns in
    ticks (pad arrivals ``NEVER``), count columns as integers."""
    real = aug[:, :, _KIND] != 3.0
    out = np.where(np.isfinite(aug), aug, 0.0).astype(np.int64)
    out[:, :, _ARR] = NEVER
    for c in (_ARR, _DUR, _TR, _GDT):
        out[:, :, c][real] = to_ticks(aug[:, :, c][real])
    return out


def _tick_timing(timing: np.ndarray) -> np.ndarray:
    """Per-lane [tdma, tecc, age_bound] rows as int64: times in ticks,
    the bound rounded up to a count (``byp >= 2.5`` is ``byp >= 3``),
    ``inf`` as ``NEVER``."""
    out = np.empty(timing.shape, np.int64)
    out[:, :2] = to_ticks(timing[:, :2])
    out[:, 2] = np.minimum(np.ceil(timing[:, 2]), NEVER).astype(np.int64)
    return out


def _dispatch(ops: np.ndarray, n_dies: int, pipelined: bool,
              timing: np.ndarray, prio: bool,
              caps=None, steps=None):
    """One dispatch of the core on a padded table with per-lane timing rows.

    ``timing`` is (L, 3) f64 — per-lane [tdma, tecc, age_bound] (µs, µs,
    count).  ``caps`` optionally forces static ``(capq, capw,
    capsteps)`` (the fused sweep buckets them group-wide; capacity is
    semantics-neutral because the rings pair via monotone counters).
    ``steps`` skips the :func:`count_steps` recount when the caller
    already knows the bound (the fused router counts per cell before
    stacking; the max over a chunk's cells equals the stacked count).
    Returns numpy ``(fin, diestat, lane)`` in µs (counts as f64).
    """
    _enable_persistent_cache()
    if steps is None:
        steps = count_steps(ops)
    if caps is None:
        caps = dispatch_caps(ops, n_dies, steps)
    capq, capw, capsteps = caps
    if steps > capsteps:
        raise ValueError(f"steps {steps} > capsteps {capsteps}")
    L, maxp = ops.shape[0], ops.shape[1]
    with obs.span("flashsim.dispatch"):
        with jax.enable_x64(True):
            with obs.span("flashsim.stage"):
                table = jnp.asarray(_tick_table(augment_ops(ops, pipelined)))
                tick_timing = jnp.asarray(_tick_timing(timing))
            lowering = carry_lowering(_platform(table), L)
            variant = (n_dies, capq, capw, capsteps, pipelined, prio,
                       lowering, L, maxp)
            new = variant not in _VARIANTS
            # The core's wall time: np.asarray waits for the device.
            with obs.span("flashsim.core", lanes=L, steps=steps,
                          capsteps=capsteps, variant=variant,
                          new_variant=new):
                log, diestat, lane = _core_jit(
                    table, jnp.int32(steps), tick_timing,
                    n_dies=n_dies, capq=capq, capw=capw, capsteps=capsteps,
                    pipelined=pipelined, prio=prio, lowering=lowering)
                log, diestat, lane = (np.asarray(log), np.asarray(diestat),
                                      np.asarray(lane))
        _VARIANTS.add(variant)
        obs.count("dispatches")
        with obs.span("flashsim.unpack"):
            # Scatter the per-step completion log into the per-op fin
            # table.  Each real op id appears at most once; idle rows
            # carry the sink id maxp, zeroed afterwards.  Rows past
            # ``steps`` were never written (all-sink) — skip them.
            fin = np.zeros((L, maxp + 1), dtype=np.float64)
            fin[np.arange(L)[None, :], log[:steps, L:]] = from_ticks(
                log[:steps, :L])
            fin[:, maxp] = 0.0
            lane_us = np.concatenate(
                [from_ticks(lane[:, :2]), lane[:, 2:].astype(np.float64)],
                axis=1)
            diestat_us = from_ticks(diestat)
        if obs.active():
            # Every lane runs ``steps``; its own bound is the useful part.
            obs.count("lanes", L)
            obs.count("lane_steps_run", L * steps)
            obs.count("lane_steps_useful", int(lane_steps(ops).sum()))
            if new:
                obs.count("core_variants_new")
    return fin, diestat_us, lane_us


def fcfs_core(ops: np.ndarray, n_dies: int, pipelined: bool,
              tdma: float, tecc: float,
              age_bound: Optional[float] = None):
    """Run the lockstep shard core on a padded op table.

    ``age_bound`` selects the scheduler lowering: ``None`` = single
    FIFO ring (fcfs); a float (``inf`` = plain host_prio) = dual
    priority rings with that aging bound, classified by the op table's
    ``hp`` column.  Returns numpy ``(fin, diestat, lane)`` — per-op
    completion contributions (L, MAXP+1), per-die
    [busy_total, last_release] (L, n_dies, 2), and per-lane
    [ch_busy, ch_tot, n_events, seq] (L, 4).  Bit-identical to
    :func:`fcfs_core_ref` on any backend; every time in ``ops`` and
    ``tdma``/``tecc`` must lie on the tick grid
    (:func:`repro.flashsim.simtime.on_grid`), else ``ValueError``.
    """
    prio = age_bound is not None
    bound = float(age_bound) if prio else 0.0
    timing = np.tile(
        np.asarray([[float(tdma), float(tecc), bound]], np.float64),
        (ops.shape[0], 1))
    return _dispatch(ops, n_dies, pipelined, timing, prio)


def fused_core(ops: np.ndarray, n_dies: int, pipelined: bool,
               timing: np.ndarray, prio: bool, caps=None, steps=None):
    """Run one dispatch over the lanes of many stacked cells.

    ``ops`` is the (C*L, MAXP, 7) cell-stacked padded table (cell c's
    lanes occupy rows [c*L, (c+1)*L)), ``timing`` the matching (C*L, 3)
    per-lane [tdma, tecc, age_bound] rows — each cell's scalars
    repeated on its lanes, which is what lets cells with different
    timing models or aging bounds share the dispatch.  ``pipelined``
    and ``prio`` are static and must be uniform across the stacked
    cells (the fused router groups by them).  Returns the same
    ``(fin, diestat, lane)`` triple as :func:`fcfs_core`; slice rows
    [c*L, (c+1)*L) for cell c.  Bit-identical per cell to a separate
    :func:`fcfs_core` dispatch — the cell-axis law restated (and
    property-pinned) by :func:`repro.kernels.fcfs_core.ref.fused_core_ref`.
    """
    if timing.shape != (ops.shape[0], 3):
        raise ValueError(
            f"timing shape {timing.shape} != ({ops.shape[0]}, 3)")
    return _dispatch(ops, n_dies, pipelined, np.asarray(timing, np.float64),
                     prio, caps=caps, steps=steps)
