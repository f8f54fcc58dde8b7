"""Lockstep core for the sched-aware open-loop shard loop: one jitted
XLA program on CPU and TPU, with a carry lowering for each (below).

One invocation advances *all* channel shards of a run in lockstep:
the lane dimension (axis 0 everywhere) is the shard/channel, and each
``fori_loop`` step retires exactly one event — an admission, a sense
completion, a die release, or a write-transfer landing — per active lane.
The channel busy-until collapse is the sequential max-plus recurrence

    done = max(ch_busy, t) + tDMA ;  ch_busy = done

carried as a lane vector across steps, evaluated in event order.  Times
are int64 ticks of :mod:`repro.flashsim.simtime`; the interpreter loop in
:mod:`repro.flashsim.engine` computes the same on-grid values in f64
exactly, so the result is bit-identical to it on any backend — the
event order of ``_run_shard`` is replayed per lane, and integer
arithmetic has no rounding to reproduce.

The interpreter's heap is replaced by a bounded merge that is exact by
construction for the supported matrix (fcfs / host_prio /
host_prio_aged, gc in {none, prepass}, no faults, open loop):

  * each die holds at most one scheduled event (next sense/copy, or its
    release) — a (time, seq) pair in the die-state row;
  * write transfers in flight form a FIFO whose times and seqs are
    pushed in admission order (monotone, since the channel collapse
    grants at issue) — the ACQ queue;
  * the admission cursor wins ties (the interpreter's ``next_adm <= tt``).

``seq`` counters are incremented exactly where ``_run_shard`` increments
``seqc``, so heap tie-breaking (push order) is reproduced, not
approximated.

State layout (int64, times in ticks; ``NEVER`` is "no event"):

  ops   (L, MAXP, 10) — [arrival, kind, die, dur, attempts, tr, hp,
                        gdt, gk0, grem0] per op in admission order;
                        kind 0=read 1=write 2=erase 3=pad (arrival
                        NEVER); hp is the scheduling class (1 = host
                        read, the ``host_read`` table of
                        :mod:`repro.flashsim.sched`; pads 0).
                        The g* columns are host-precomputed grant
                        attributes (see :func:`augment_ops`): first
                        event delta (tR for reads, dur otherwise),
                        initial event kind (0 sense / 1 release), and
                        initial remaining-attempts — they collapse the
                        read/write/erase dispatch at grant time to
                        single blends.
  state (L, D+1, NC)  — per-die rows [evt, evseq, evop, evkind, held,
                        free, rem, a_act, tr_act, qhead, qtail, tot,
                        busy, nonread] (NC=14, the fifo lowering), plus
                        [qhead2, qtail2, byp] under the prio lowering
                        (NC=17); row D is the masked-write sink.
  fifo  (L, D+1, CAPQ)— per-die FIFO ring of queued op ids (int32);
                        CAPQ is a host-computed bound (max ops on one
                        die), so the ring never overwrites a live
                        entry.  Under
                        the prio lowering the last axis doubles
                        (2*CAPQ): the *host-read* (hi) ring lives in
                        slots [0, CAPQ) and the low class (programs, GC
                        copy-back, erases) in [CAPQ, 2*CAPQ) of the
                        *same* buffer — one push write and one pop
                        read per step regardless of class, instead of
                        a second buffer with writes of its own.
                        Per-class occupancy is bounded by the per-die
                        total, so CAPQ bounds both regions.
  acq   (L, CAPW+1, 4)— ring of in-flight write transfers [done, seq,
                        op, die]; CAPW bounds the writes of one lane;
                        slot CAPW is the masked-write sink.
  log   (CAPSTEPS, 2L)— per-step completion log, one row per lockstep
                        step: [fin values | fin op ids].  Inactive
                        lanes log op id MAXP (the sink).  The per-op
                        ``fin`` table (reads: done+tECC of the final
                        attempt; writes/erases: release time) is never
                        read inside the loop, so it is reconstructed
                        from the log by one host-side scatter in
                        :func:`repro.kernels.fcfs_core.ops.fcfs_core`
                        — one log write per step instead of L per-lane
                        updates.

Scheduler lowering
------------------
``prio=False`` traces the single-ring FCFS pop — byte-for-byte the PR 8
kernel.  ``prio=True`` traces the dual-ring pop implementing
``AgedHostPrioQueue.pop_next`` exactly (``sched.py``): a release that
finds work pops the low ring when the hi ring is empty *or* when the
per-die bypass counter has reached the aging bound (both rings
non-empty), else pops the hi ring — incrementing the counter iff the
low ring was bypassed; every low-ring pop resets the counter.  The
bound rides in ``timing[2]`` as a *traced* scalar, so plain
``host_prio`` (bound = +inf: the low class never ages to the front) and
every ``host_prio_aged:N`` share one compiled kernel.  The counter
changes only at ring pops — admissions and ACQ landings that grant a
free die directly never consult the queue object in the interpreter, so
they never touch the counter here either.

Carry lowering
--------------
Each step writes one element (row) of three carry buffers per lane —
``state`` at the lane's die row, ``fifo`` at its pushed (die, slot),
``acq`` at its ACQ slot — and reads one of each.  The static
``lowering`` argument picks how; the written values, and so every
result, are identical in all three:

  * ``"unrolled"`` — one ``dynamic_update_slice`` per lane (static lane,
    computed row) and a gather per read.  The cheapest in-place update
    XLA:CPU emits for a handful of computed indices: both the generic
    scatter and a one-hot select measured slower there.
  * ``"scatter"`` — one batched scatter per buffer over unique, sorted
    lane indices.  On XLA:CPU beyond some 64 lanes, where the unroll
    would bloat the loop body.
  * ``"select"`` — one lane-parallel ``where`` over the one-hot of the
    small die/slot axes per write, and a masked sum over the same
    one-hot per read (one non-zero term: exact).  For the TPU: there
    each unrolled update waits on its lane's indices, handed from the
    vector unit to the scalar unit one lane at a time, so the unroll's
    step time grows with the lane count; the selects have no per-lane
    code, and the loop body's size does not depend on L (on a TPU v5e
    an 8-lane websearch call's step fell from 52.9 to 10.3 µs).  Their
    work does: each step rewrites and reads every buffer whole, and
    the ``fifo`` ring dominates, L·(D+1)·CAPQ elements (twice that
    under prio) against one element per lane for the other two
    lowerings.  CAPQ is the deepest die queue, so it grows with the
    trace's length; on a v5e the selects still beat the unroll 3.6–14×
    at CAPQ 4096 (``benchmarks/core_lowering.py``).

Every write is *unconditional*: inactive lanes are redirected to a sink
row/slot instead of blending with the gathered current value, so under
the update-slice and scatter lowerings each carry buffer has the write
as its only consumer and XLA updates it in place across ``fori_loop``
steps (masked blends forced a full copy of every buffer per step).  The
FIFO push runs before the pop read for the same reason — a lane popping
this step never pushes, so reading the pushed buffer is semantically
identical, and it keeps the write the buffer's only carry consumer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# ops columns
(_ARR, _KIND, _DIE, _DUR, _A, _TR, _HP, _GDT, _GK0, _GREM0) = range(10)
# die-state columns (the last three exist only under the prio lowering)
(_EVT, _EVSEQ, _EVOP, _EVKIND, _HELD, _FREE, _REM, _AACT, _TRACT,
 _QHEAD, _QTAIL, _TOT, _BUSY, _NR, _QHEAD2, _QTAIL2, _BYP) = range(17)

#: "Never" for event times and seqs, and the unbounded aging bound: far
#: above any reachable tick or count, with headroom to add a duration
#: without overflow.
NEVER = 2 ** 62


#: The carry lowerings (see the module docstring).
LOWERINGS = ("unrolled", "scatter", "select")


def _hot(shape, idx):
    """(L, *shape[1:1+k]) one-hot of lane l's index ``idx[i][l]`` on axis
    ``1 + i`` of a buffer of ``shape``, for k = ``len(idx)``."""
    k = len(idx)
    hot = None
    for ax, i in enumerate(idx, start=1):
        iota = jax.lax.broadcasted_iota(jnp.int32, shape[:1 + k], ax)
        eq = iota == i.reshape((-1,) + (1,) * k)
        hot = eq if hot is None else hot & eq
    return hot


def _get(buf, idx, lowering):
    """``buf[l, idx[0][l], ..., idx[k-1][l]]`` for every lane l."""
    if lowering != "select":
        return buf[(jnp.arange(buf.shape[0]), *idx)]
    hot = _hot(buf.shape, idx)
    hot = hot.reshape(hot.shape + (1,) * (buf.ndim - hot.ndim))
    return jnp.where(hot, buf, 0).sum(axis=tuple(range(1, 1 + len(idx))))


def _set(buf, idx, val, lowering):
    """``buf`` with ``buf[l, idx[0][l], ..., idx[k-1][l]] = val[l]`` for
    every lane l (indices int32, at most one element per lane)."""
    L, k = buf.shape[0], len(idx)
    rest = buf.shape[1 + k:]
    if lowering == "select":
        hot = _hot(buf.shape, idx)
        hot = hot.reshape(hot.shape + (1,) * len(rest))
        return jnp.where(hot, val.reshape((L,) + (1,) * k + rest), buf)
    if lowering == "scatter":
        return buf.at[(jnp.arange(L), *idx)].set(
            val, unique_indices=True, indices_are_sorted=True)
    zeros = (jnp.int32(0),) * len(rest)
    for l in range(L):
        buf = jax.lax.dynamic_update_slice(
            buf, val[l].reshape((1,) * (1 + k) + rest),
            (jnp.int32(l), *(i[l] for i in idx), *zeros))
    return buf


def _core(ops, steps, timing, *, n_dies, capq, capw, capsteps, pipelined,
          prio, lowering):
    if lowering not in LOWERINGS:
        raise ValueError(f"lowering {lowering!r} not in {LOWERINGS}")
    L, maxp, _ = ops.shape
    D = n_dies
    lanes = jnp.arange(L)
    i64 = jnp.int64
    # Per-lane timing rows: a fused sweep carries each cell's tDMA,
    # tECC and aging bound (NEVER = plain host_prio; unread when
    # prio=False) on that cell's lanes.
    tdma = timing[:, 0]
    tecc = timing[:, 1]
    bound = timing[:, 2]

    def body(t, carry):
        (state, fifo, acq, log, chb, ch_tot, seqc, n_ev,
         ai, aq_head, aq_tail) = carry

        # ---- candidate selection: per-die events + ACQ head ----------
        evt = state[:, :D, _EVT]
        evseq = state[:, :D, _EVSEQ]
        aq_row = _get(acq, (aq_head % capw,), lowering)
        aq_empty = aq_head >= aq_tail
        aq_t = jnp.where(aq_empty, NEVER, aq_row[:, 0])
        aq_sq = jnp.where(aq_empty, NEVER, aq_row[:, 1])
        cand_t = jnp.concatenate([evt, aq_t[:, None]], axis=1)
        cand_s = jnp.concatenate([evseq, aq_sq[:, None]], axis=1)
        tmin = cand_t.min(axis=1)
        is_min = cand_t == tmin[:, None]
        smin = jnp.where(is_min, cand_s, NEVER).min(axis=1)
        widx = jnp.argmax(is_min & (cand_s == smin[:, None]), axis=1)

        adm_row = ops[lanes, ai]
        adm_t = adm_row[:, _ARR]
        active = (adm_t < NEVER) | (tmin < NEVER)
        take_adm = (adm_t <= tmin) & active
        take_ev = (~take_adm) & active

        a_kind = adm_row[:, _KIND]
        a_die = adm_row[:, _DIE].astype(jnp.int32)
        is_r = take_adm & (a_kind == 0)
        is_w = take_adm & (a_kind == 1)
        is_e = take_adm & (a_kind == 2)

        ev_acq = take_ev & (widx == D)
        ev_die = take_ev & (widx < D)
        o_acq = aq_row[:, 2].astype(jnp.int32)
        acq_die = aq_row[:, 3].astype(jnp.int32)
        aq_head = aq_head + ev_acq.astype(jnp.int32)

        # the one die row this step reads/writes
        tgt = jnp.where(take_adm & (is_r | is_e), a_die,
                        jnp.where(ev_die, widx.astype(jnp.int32),
                                  jnp.where(ev_acq, acq_die, D)))
        row = _get(state, (tgt,), lowering)

        if prio:
            hi_empty = row[:, _QTAIL] == row[:, _QHEAD]
            lo_empty = row[:, _QTAIL2] == row[:, _QHEAD2]
            q_empty = hi_empty & lo_empty
        else:
            q_empty = row[:, _QTAIL] == row[:, _QHEAD]
        die_free = (row[:, _FREE] == 1) & q_empty

        ev_kind = row[:, _EVKIND]
        ev_sense = ev_die & (ev_kind == 0)
        ev_rel = ev_die & (ev_kind == 1)

        # -- the channel collapse (write admission DMA or sense DMA;
        #    a step is one or the other, so one max-plus update) --
        touches = is_w | ev_sense
        c_done = jnp.maximum(chb, jnp.where(take_adm, adm_t, tmin)) + tdma
        chb = jnp.where(touches, c_done, chb)
        ch_tot = jnp.where(touches, ch_tot + tdma, ch_tot)

        # write admission: ACQ push at its DMA-done time, unconditional
        # (non-write lanes land in the sink slot capw, never read).
        aq_slot = jnp.where(is_w, aq_tail % capw, capw)
        aq_new = jnp.stack([c_done, seqc, ai.astype(i64),
                            adm_row[:, _DIE]], axis=1)
        acq = _set(acq, (aq_slot,), aq_new, lowering)
        aq_tail = aq_tail + is_w.astype(jnp.int32)

        # -- sense / copy handler --
        s_tm = tmin
        s_tr = row[:, _TRACT]
        if not pipelined:
            s_more = row[:, _REM] > 1
            s_next = jnp.where(s_more, c_done + tecc + s_tr, c_done)
            s_rem = row[:, _REM] - 1
        else:
            s_more = row[:, _REM] + 1 < row[:, _AACT]
            s_rel = jnp.where(row[:, _AACT] > 1, s_tm + s_tr, s_tm)
            s_next = jnp.where(s_more,
                               jnp.maximum(s_tm + s_tr, c_done), s_rel)
            s_rem = row[:, _REM] + 1
        s_fin = c_done + tecc

        # -- grants: admission (free die), ACQ landing, release pop --
        r_tm = tmin
        g_adm = (is_r | is_e) & die_free
        g_acq = ev_acq & die_free
        queue_push = ((is_r | is_e) & ~die_free) | (ev_acq & ~die_free)
        push_val = jnp.where(take_adm, ai, o_acq)

        # FIFO push before the pop gather (see module docstring)
        push_die = jnp.where(queue_push, tgt, D)
        if prio:
            # Class of the pushed op — the kernel's ``host_read`` table
            # lookup.  Non-pushing lanes read a harmless row (push_die
            # is the sink for them).  Class picks the ring *region* of
            # the shared buffer: hi at [0, capq), lo at [capq, 2*capq)
            # — one write either way.
            push_hp = ops[lanes, push_val, _HP] == 1
            push_hi = queue_push & push_hp
            push_lo = queue_push & ~push_hp
            push_slot = jnp.where(
                push_hp, row[:, _QTAIL].astype(jnp.int32) % capq,
                capq + row[:, _QTAIL2].astype(jnp.int32) % capq)
        else:
            push_slot = row[:, _QTAIL].astype(jnp.int32) % capq
        fifo = _set(fifo, (push_die, push_slot), push_val, lowering)

        q_nonempty = ~q_empty
        grant2 = ev_rel & q_nonempty
        if prio:
            # AgedHostPrioQueue.pop_next, vectorized: pop the low ring
            # when the hi ring is empty or the head-of-line low op has
            # aged past the bound; else pop hi, counting the bypass iff
            # low work was waiting.  Any low pop resets the counter.
            # Selecting the ring = selecting the slot region, so one
            # gather serves both classes.
            byp = row[:, _BYP]
            lo_ne = ~lo_empty
            aged = ~hi_empty & lo_ne & (byp >= bound)
            pop_lo = aged | hi_empty
            qh = jnp.where(
                pop_lo, capq + row[:, _QHEAD2].astype(jnp.int32) % capq,
                row[:, _QHEAD].astype(jnp.int32) % capq)
        else:
            qh = row[:, _QHEAD].astype(jnp.int32) % capq
        o2 = _get(fifo, (tgt, qh), lowering)

        # one gather serves every grant source: popped op, admitted op,
        # or the ACQ-landed op (masked lanes read a harmless row)
        grant_any = g_adm | g_acq | grant2
        g_op = jnp.where(grant2, o2,
                         jnp.where(take_adm, ai, o_acq))
        g_row = ops[lanes, g_op]
        gr_tm = jnp.where(take_adm, adm_t, r_tm)

        # ---- assemble the new die row --------------------------------
        new_evt = jnp.where(
            ev_sense, s_next,
            jnp.where(grant_any, gr_tm + g_row[:, _GDT],
                      jnp.where(ev_rel, NEVER, row[:, _EVT])))
        sets_ev = ev_sense | grant_any
        new_evseq = jnp.where(sets_ev, seqc, row[:, _EVSEQ])
        new_evop = jnp.where(grant_any, g_op.astype(i64), row[:, _EVOP])
        # kind after this step: sense chains stay 0 until the final
        # attempt converts to a release; grants start at the op's
        # precomputed gk0 (reads 0, writes/erases 1).
        new_evkind = jnp.where(ev_sense,
                               jnp.where(s_more, 0, 1),
                               jnp.where(grant_any, g_row[:, _GK0],
                                         row[:, _EVKIND]))
        new_held = jnp.where(grant_any, gr_tm, row[:, _HELD])
        new_free = jnp.where(grant_any, 0,
                             jnp.where(ev_rel & ~q_nonempty, 1,
                                       row[:, _FREE]))
        new_rem = jnp.where(ev_sense, s_rem,
                            jnp.where(grant_any, g_row[:, _GREM0],
                                      row[:, _REM]))
        new_aact = jnp.where(grant_any, g_row[:, _A], row[:, _AACT])
        new_tract = jnp.where(grant_any, g_row[:, _TR], row[:, _TRACT])
        new_nr = jnp.where(grant_any, g_row[:, _GK0], row[:, _NR])
        if prio:
            new_qhead = row[:, _QHEAD] + (grant2 & ~pop_lo).astype(i64)
            new_qhead2 = row[:, _QHEAD2] + (grant2 & pop_lo).astype(i64)
            new_qtail = row[:, _QTAIL] + push_hi.astype(i64)
            new_qtail2 = row[:, _QTAIL2] + push_lo.astype(i64)
            new_byp = jnp.where(
                grant2, jnp.where(pop_lo, 0, byp + lo_ne.astype(i64)),
                byp)
        else:
            new_qhead = row[:, _QHEAD] + grant2.astype(i64)
            new_qtail = row[:, _QTAIL] + queue_push.astype(i64)
        new_tot = jnp.where(ev_rel, row[:, _TOT] + (r_tm - row[:, _HELD]),
                            row[:, _TOT])
        new_busy = jnp.where(ev_rel, r_tm, row[:, _BUSY])

        cols = [new_evt, new_evseq, new_evop, new_evkind, new_held,
                new_free, new_rem, new_aact, new_tract, new_qhead,
                new_qtail, new_tot, new_busy, new_nr]
        if prio:
            cols += [new_qhead2, new_qtail2, new_byp]
        new_row = jnp.stack([c.astype(i64) for c in cols], axis=1)
        state = _set(state, (tgt,), new_row, lowering)

        # fin events: final sense (reads) or release of a non-read.
        # Logged as one (2L,) row per step — the fin table is never
        # read in the loop, so one dynamic_update_slice replaces L
        # per-lane writes; the host scatters the log afterwards.
        fin_sense = ev_sense & ~s_more
        fin_rel = ev_rel & (row[:, _NR] == 1)
        fin_idx = jnp.where(fin_sense | fin_rel, row[:, _EVOP], maxp)
        fin_val = jnp.where(fin_sense, s_fin, r_tm)
        entry = jnp.concatenate([fin_val, fin_idx])[None, :]
        log = jax.lax.dynamic_update_slice(log, entry,
                                           (t, jnp.int32(0)))

        # seq counter: one push per admission of a write (ACQ), per
        # grant, and per sense continuation — exactly the interpreter's
        # seqc increments.
        pushed = is_w | grant_any | ev_sense
        seqc = seqc + pushed.astype(i64)
        n_ev = n_ev + take_ev.astype(i64)
        ai = ai + take_adm.astype(jnp.int32)

        return (state, fifo, acq, log, chb, ch_tot, seqc, n_ev,
                ai, aq_head, aq_tail)

    zero_l = jnp.zeros((L,), i64)
    zero_i = jnp.zeros((L,), jnp.int32)
    ncols = 17 if prio else 14
    state0 = jnp.zeros((L, D + 1, ncols), i64)
    state0 = state0.at[:, :, _EVT].set(NEVER)
    state0 = state0.at[:, :, _FREE].set(1)
    # Under the prio lowering the slot axis doubles: hi ring at
    # [0, capq), low ring at [capq, 2*capq) of the same buffer.
    fifo0 = jnp.zeros((L, D + 1, capq * (2 if prio else 1)), jnp.int32)
    acq0 = jnp.zeros((L, capw + 1, 4), i64)
    # Unwritten log rows (t >= steps) keep op id maxp — the sink slot
    # the host scatter discards.
    log0 = jnp.concatenate(
        [jnp.zeros((capsteps, L), i64),
         jnp.full((capsteps, L), maxp, i64)], axis=1)

    carry = (state0, fifo0, acq0, log0, zero_l, zero_l, zero_l,
             zero_l, zero_i, zero_i, zero_i)
    (state, fifo, acq, log, chb, ch_tot, seqc, n_ev,
     ai, aq_head, aq_tail) = jax.lax.fori_loop(0, steps, body, carry)

    diestat = jnp.stack([state[:, :D, _TOT], state[:, :D, _BUSY]], axis=2)
    return log, diestat, jnp.stack([chb, ch_tot, n_ev, seqc], axis=1)


def fcfs_core_fwd(ops, steps, timing, *, n_dies, capq, capw, capsteps,
                  pipelined, prio=False, lowering="unrolled"):
    """Run the lockstep shard core (traced under ``jax.enable_x64``).

    ``ops``: (L, MAXP, 10) int64 augmented padded op table, times in
    ticks (admission order per lane; see :func:`augment_ops`).
    ``steps``: () int32 — total lockstep steps (max lane admissions +
    events; idle lanes no-op).  ``timing``: (L, 3) int64 — per-lane
    [tdma, tecc, age_bound] rows (ticks, ticks, count); a single run
    broadcasts one row to all lanes, a fused sweep carries each cell's
    values on that cell's lanes.  The bound is traced (``NEVER`` = plain
    host_prio) and unread when ``prio`` is False.  ``capq``/``capw`` —
    static FIFO/ACQ ring capacities (host-computed bounds: max ops on
    one die / max writes on one lane); ``capsteps`` — static log length,
    a power of two >= steps.  ``prio`` selects the dual-ring scheduler
    lowering and ``lowering`` the carry lowering, one of
    :data:`LOWERINGS` (module docstring; both static: distinct compiled
    programs, identical results).  Returns ``(log, diestat, lane)``,
    all int64: the per-step completion log (scatter it into the per-op
    ``fin`` table host-side), per-die [tot, busy], and per-lane
    [ch_busy, ch_tot, n_events, seqc].  Runs under the ``fcfs_core``
    named scope, which is how a profile finds it.
    """
    with jax.named_scope("fcfs_core"):
        return _core(ops, steps, timing, n_dies=n_dies, capq=capq,
                     capw=capw, capsteps=capsteps, pipelined=pipelined,
                     prio=prio, lowering=lowering)
