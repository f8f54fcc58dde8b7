"""The reference simulator: a plain, independent statement of what one
simulated cell (drive configuration, host workload, operating condition,
read-retry mechanism, seed) must produce.

It follows the semantics the configuration files state, from the host
trace to the statistics, written out straightforwardly:

  * host trace: a Markov-modulated Poisson arrival process with
    geometric request sizes (:func:`generate_trace`), dealt to each call
    in an order drawn from the call's seed (:func:`call_trace`);
  * page ops: every request touches consecutive logical pages, striped
    over dies (``page % dies``) and channels (``die % channels``), page
    type ``page % 3`` (:func:`expand`); no garbage collection;
  * read retry: each read's attempt count is drawn by inverse CDF from
    the characterized table of its page type (:mod:`nand`), one uniform
    per read in admission order;
  * the drive: one heap of timed events; each die serves one op at a
    time from its FIFO queue; each channel transfers one page at a time,
    FIFO; PR² overlaps the next sense with the transfer; every time lies
    on a 2**-10 us grid;
  * statistics: response = completion - arrival + host overhead, its
    mean and percentiles (numpy's linear rule), utilizations over the
    span.

It imports nothing of the program under test.  ``time_dtype`` exists for
the benchmark's control: the same computation with simulated time held
in a narrower float.
"""

from __future__ import annotations

import heapq
import zlib
from collections import deque

import numpy as np

from . import nand

TICKS_PER_US = 1024
PAGE_TYPES = ("lsb", "csb", "msb")
OP_READ, OP_PROG = range(2)

#: Every statistic a cell reports, in the program's field order.
STAT_FIELDS = (
    "mean_us", "p50_us", "p95_us", "p99_us", "read_mean_us", "n_requests",
    "mean_read_attempts", "die_util", "channel_util", "read_p99_us", "wa",
    "gc_invocations", "gc_page_reads", "gc_page_progs", "blocks_erased",
    "gc_suspensions", "write_stalls", "mispredicted_reads", "rescued_reads",
    "parity_rebuilds", "rebuild_reads", "retired_blocks", "program_fails",
    "erase_fails", "unrecoverable", "recovery_p99_us", "hostq_wait_mean_us",
    "hostq_wait_p99_us", "device_mean_us", "read_device_p99_us",
    "throughput_iops", "max_inflight", "cache_hit_reads", "cache_hit_pages",
    "cache_absorbed_writes", "cache_flush_pages", "cache_stalled_writes",
    "die_sense_util",
)

#: Optional configuration keys it models: none beyond the core.
DRIVE_KEYS = frozenset()
WORKLOAD_KEYS = frozenset()


def accepts(drive: dict, workload: dict):
    """Why this reference cannot model ``drive``, or None: it models
    FIFO die queues without garbage collection."""
    if drive["scheduler"] != "fcfs" or drive["gc"] != {"enabled": False}:
        return ("the reference models FIFO die queues (fcfs) without "
                "garbage collection")
    return None


MECHANISMS = {
    #           pipelined, adaptive tR, SOTA start
    "baseline": (False, False, False),
    "sota": (False, False, True),
    "pr2": (True, False, False),
    "ar2": (False, True, False),
    "pr2ar2": (True, True, False),
    "sota+pr2ar2": (True, True, True),
}


def on_grid(x):
    """Round µs to the nearest 2**-10 µs tick (ties to even)."""
    if isinstance(x, float):
        return round(x * TICKS_PER_US) / TICKS_PER_US
    return np.rint(np.asarray(x, np.float64) * TICKS_PER_US) / TICKS_PER_US


# -- host trace -----------------------------------------------------------------

def generate_trace(profile: dict, n: int, seed: int):
    """Arrivals (µs), read flags, page counts and first pages of ``n``
    requests: bursty and idle phases of 64 requests each, half the
    requests in bursts at ``burstiness * iops``, the idle rate set so the
    mean rate is ``iops``; sizes geometric with mean ``mean_pages``
    (1..64 pages); first pages uniform over ``span_pages``.  The stream
    is seeded by ``seed`` xor the CRC32 of the profile name."""
    rng = np.random.default_rng(seed ^ zlib.crc32(profile["name"].encode()))
    iops, b = profile["iops"], profile["burstiness"]
    if b > 1.0:
        r_burst = b * iops
        r_idle = 0.5 * iops / max(1.0 - 0.5 / b, 1e-6)
        run_of = np.arange(n) // 64
        burst = (rng.random(run_of.max() + 1) < 0.5)[run_of]
        g_burst = rng.exponential(1e6 / r_burst, n)
        g_idle = rng.exponential(1e6 / r_idle, n)
        gaps = np.where(burst, g_burst, g_idle)
    else:
        gaps = rng.exponential(1e6 / iops, n)
    arrival = np.cumsum(gaps)
    is_read = rng.random(n) < profile["read_ratio"]
    p = min(1.0 / profile["mean_pages"], 1.0)
    n_pages = rng.geometric(p, n).clip(1, 64).astype(np.int64)
    start = rng.integers(0, profile["span_pages"], n)
    return arrival, is_read, n_pages, start


def call_trace(base, seed: int):
    """The trace of one call, from its base trace: the arrival times
    kept, the requests (read flag, size, first page) dealt to them in
    the order of a permutation drawn from ``seed``."""
    arrival, is_read, n_pages, start = base
    p = np.random.default_rng(seed).permutation(len(arrival))
    return arrival.copy(), is_read[p], n_pages[p], start[p]


def expand(trace, n_dies: int):
    """Per-page ops in admission order: (arrival, request id, logical
    page, read flag).  Requests are admitted in arrival order (stable)."""
    arrival, is_read, n_pages, start = trace
    order = np.argsort(arrival, kind="stable")
    ops = []
    for r in order.tolist():
        for k in range(int(n_pages[r])):
            ops.append((float(arrival[r]), r, int(start[r]) + k,
                        bool(is_read[r])))
    return ops


# -- one cell -------------------------------------------------------------------------

def _run_drive(drive, ops, pipelined, n_requests, T):
    """The event simulation.  ``ops``: (arrival, rid, die, kind, attempts,
    tr, dur) per op, times on the grid as ``T``.  Returns per-request
    completion times and per-die / per-channel busy totals."""
    n_ch = drive["n_channels"]
    n_dies = n_ch * drive["dies_per_channel"]
    tdma = T(on_grid(float(drive["timing"]["tdma_us"])))
    tecc = T(on_grid(float(drive["timing"]["tecc_us"])))
    zero = T(0.0)
    queues = [deque() for _ in range(n_dies)]
    held = [False] * n_dies
    die_tot = [zero] * n_dies
    ch_busy = [zero] * n_ch
    ch_tot = [zero] * n_ch
    req_done = [zero] * n_requests
    since = [zero] * len(ops)
    left = [0] * len(ops)          # serial: senses left; PR²: copies done
    heap, seq = [], 0
    SENSE, COPY, LANDED, RELEASE = range(4)

    def push(t, kind, o):
        nonlocal seq
        heapq.heappush(heap, (t, seq, kind, o))
        seq += 1

    def start(o, t):
        """Die granted to op ``o`` at ``t``."""
        d = ops[o][2]
        held[d] = True
        since[o] = t
        if ops[o][3] == OP_READ:
            left[o] = 0 if pipelined else ops[o][4]
            push(t + ops[o][5], COPY if pipelined else SENSE, o)
        else:
            push(t + ops[o][6], RELEASE, o)

    def transfer(c, t):
        b = ch_busy[c]
        done = (b if b > t else t) + tdma
        ch_busy[c] = done
        ch_tot[c] += tdma
        return done

    def complete(o, t):
        r = ops[o][1]
        if r >= 0 and t > req_done[r]:
            req_done[r] = t

    nxt = 0
    while nxt < len(ops) or heap:
        if nxt < len(ops) and (not heap or ops[nxt][0] <= heap[0][0]):
            o, nxt = nxt, nxt + 1
            t, _, d, kind = ops[o][:4]
            if kind == OP_PROG:
                push(transfer(d % n_ch, t), LANDED, o)
            elif not held[d] and not queues[d]:
                start(o, t)
            else:
                queues[d].append(o)
            continue
        t, _, ev, o = heapq.heappop(heap)
        d = ops[o][2]
        if ev == SENSE:
            done = transfer(d % n_ch, t)
            left[o] -= 1
            if left[o]:
                push(done + tecc + ops[o][5], SENSE, o)
            else:
                complete(o, done + tecc)
                push(done, RELEASE, o)
        elif ev == COPY:
            done = transfer(d % n_ch, t)
            a = ops[o][4]
            if left[o] + 1 < a:
                left[o] += 1
                tn = t + ops[o][5]
                push(done if done > tn else tn, COPY, o)
            else:
                complete(o, done + tecc)
                push(t + ops[o][5] if a > 1 else t, RELEASE, o)
        elif ev == LANDED:
            if not held[d] and not queues[d]:
                start(o, t)
            else:
                queues[d].append(o)
        else:
            die_tot[d] += t - since[o]
            held[d] = False
            if queues[d]:
                start(queues[d].popleft(), t)
            if ops[o][3] == OP_PROG:
                complete(o, t)
    return req_done, die_tot, ch_tot


def simulate(drive: dict, trace, condition, mechanism: str, seed: int,
             time_dtype=float) -> dict:
    """Statistics of one cell: ``trace`` (arrivals, read flags, sizes,
    first pages) on ``drive`` at ``condition`` = (retention days, P/E)
    under ``mechanism``; attempts are drawn from ``seed + 7``.
    ``time_dtype`` holds simulated time (the benchmark's control passes
    a narrower float)."""
    T = time_dtype
    retention, pec = float(condition[0]), float(condition[1])
    pipelined, adaptive, sota = MECHANISMS[mechanism]
    n_dies = drive["n_channels"] * drive["dies_per_channel"]
    n_requests = len(trace[0])
    tprog = drive["timing"]["tprog_us"]
    page_ops = expand(trace, n_dies)

    scale = nand.safe_tr_scale(retention, pec) if adaptive else 1.0
    cdfs = [nand.attempt_cdf(retention, pec, pt, sota, scale)
            for pt in PAGE_TYPES]
    rng = np.random.default_rng(seed + 7)
    u = iter(rng.random(sum(rd for _, _, _, rd in page_ops)).tolist())
    tr_base = drive["timing"]["tr_us"]
    ops = []
    host_reads = host_attempts = 0
    for a, r, lpn, rd in page_ops:
        pt = lpn % 3
        attempts = (max(int(np.searchsorted(cdfs[pt], next(u))), 1)
                    if rd else 1)
        tr = tr_base[PAGE_TYPES[pt]] * scale
        ops.append((T(on_grid(a)), r, lpn % n_dies,
                    OP_READ if rd else OP_PROG, attempts, T(on_grid(tr)),
                    T(on_grid(0.0 if rd else tprog))))
        if rd:
            host_reads += 1
            host_attempts += attempts

    req_done, die_tot, ch_tot = _run_drive(drive, ops, pipelined,
                                           n_requests, T)

    arrival, is_read = trace[0], trace[1]
    done = np.asarray(req_done, T)
    response = done - on_grid(arrival).astype(T) + T(drive["host_overhead_us"])
    read_resp = response[is_read]
    span = float(done.max())
    stats = dict.fromkeys(STAT_FIELDS, 0)
    stats.update(
        mean_us=float(response.mean()),
        p50_us=float(np.percentile(response, 50.0)),
        p95_us=float(np.percentile(response, 95.0)),
        p99_us=float(np.percentile(response, 99.0)),
        read_mean_us=float(read_resp.mean()) if read_resp.size else 0.0,
        n_requests=n_requests,
        mean_read_attempts=(host_attempts / host_reads if host_reads
                            else 0.0),
        die_util=float(sum(die_tot)) / (span * n_dies),
        channel_util=float(sum(ch_tot)) / (span * drive["n_channels"]),
        read_p99_us=(float(np.percentile(read_resp, 99.0))
                     if read_resp.size else 0.0),
        wa=1.0, recovery_p99_us=0.0, hostq_wait_mean_us=0.0,
        hostq_wait_p99_us=0.0, device_mean_us=0.0, read_device_p99_us=0.0,
        throughput_iops=0.0, die_sense_util=0.0,
    )
    return stats
