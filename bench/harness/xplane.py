"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time (the union of op and program
executions), the device time of one jitted program, the device ops that
took most time, and the longest idle gaps labelled by what the host was
doing.

Device planes are those named ``/device:<PLATFORM>:<n>``.  On the TPU
each holds an ``XLA Ops`` line (one event per executed HLO op — inside
a ``while`` loop, one per op per iteration) and an ``XLA Modules`` line
(one event per program execution, named ``jit_<function>(<id>)``).  An
op event's name is its HLO text; it is shortened to the instruction
name.  Host-side marks are the events the benchmark writes with
``jax.profiler.TraceAnnotation``, named ``bench.<...>``.  Times are in
seconds on the trace's own clock.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

import numpy as np

_DEVICE_PLANE = re.compile(r"^/device:([A-Z]+):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK_PREFIX = "bench."


def op_name(hlo_text: str) -> str:
    """``%fusion.131 = s32[8] fusion(...)`` -> ``%fusion.131``."""
    return hlo_text.split(" = ", 1)[0][:80]


def module_name(name: str) -> str:
    """``jit_fcfs_core_fwd(5277062531553324127)`` -> ``jit_fcfs_core_fwd``."""
    return name.split("(", 1)[0]


@dataclasses.dataclass
class Device:
    """One device's op intervals (sorted by start), op names as indexes
    into ``names``, and its program executions."""

    starts: np.ndarray
    ends: np.ndarray
    name_ids: np.ndarray
    names: List[str]
    modules: List[Tuple[float, float, str]]


def read(path: str):
    """(devices, marks): each device plane of the trace, and the host
    marks as ``{name: [(start, end), ...]}``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, marks = [], {}
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            starts, durs, ids, index, mods = [], [], [], {}, []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        starts.append(ev.start_ns)
                        durs.append(ev.duration_ns)
                        ids.append(index.setdefault(op_name(ev.name),
                                                    len(index)))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        mods.append((s, s + ev.duration_ns * 1e-9,
                                     module_name(ev.name)))
            s = np.asarray(starts, np.float64) * 1e-9
            e = s + np.asarray(durs, np.float64) * 1e-9
            order = np.argsort(s, kind="stable")
            devices.append(Device(s[order], e[order],
                                  np.asarray(ids, np.int64)[order],
                                  list(index), sorted(mods)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(MARK_PREFIX):
                        s = ev.start_ns * 1e-9
                        marks.setdefault(ev.name, []).append(
                            (s, s + ev.duration_ns * 1e-9))
    return devices, marks


def _segments(starts: np.ndarray, ends: np.ndarray):
    """Merged (start, end) segments of intervals sorted by start."""
    if not starts.size:
        return starts, ends
    reach = np.maximum.accumulate(ends)
    new = np.ones(starts.size, bool)
    new[1:] = starts[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, starts.size - 1)
    return starts[first], reach[last]


@dataclasses.dataclass
class Reduced:
    """What a trace says over ``window``; ``spans`` label the host's
    work ``(start, end, label)``; ``core`` names the jitted program whose
    executions the core metric counts."""

    window: Tuple[float, float]
    devices: List[Device]
    spans: List[Tuple[float, float, str]]
    core: str = "jit_fcfs_core_fwd"

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _clipped(self, d: Device):
        lo, hi = self.window
        keep = (d.ends > lo) & (d.starts < hi)
        return (np.clip(d.starts[keep], lo, hi), np.clip(d.ends[keep], lo, hi),
                d.name_ids[keep])

    def _busy_segments(self, d: Device):
        """Merged stretches of the window in which an op or a program ran
        on ``d``.  A program counts as a whole: a trace that stops inside
        a long loop holds the loop's inner ops but not the loop op, which
        is recorded when it ends."""
        s, e, _ = self._clipped(d)
        lo, hi = self.window
        ms = np.array([max(a, lo) for a, b, _ in d.modules
                       if b > lo and a < hi])
        me = np.array([min(b, hi) for a, b, _ in d.modules
                       if b > lo and a < hi])
        s, e = np.concatenate([s, ms]), np.concatenate([e, me])
        order = np.argsort(s, kind="stable")
        return _segments(s[order], e[order])

    def busy_s(self) -> float:
        """Seconds in which an op or a program ran, averaged over the
        devices."""
        if not self.devices:
            return 0.0
        tot = 0.0
        for d in self.devices:
            a, b = self._busy_segments(d)
            tot += float((b - a).sum())
        return tot / len(self.devices)

    def core_s(self) -> float:
        """Device seconds of the core program's executions inside the
        window, averaged over the devices."""
        if not self.devices:
            return 0.0
        lo, hi = self.window
        tot = sum(max(0.0, min(e, hi) - max(s, lo))
                  for d in self.devices for s, e, n in d.modules
                  if n == self.core)
        return tot / len(self.devices)

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """Op names by device seconds inside the window (averaged over
        the devices)."""
        tot: Dict[str, float] = {}
        for d in self.devices:
            s, e, ids = self._clipped(d)
            per = np.bincount(ids, weights=e - s, minlength=len(d.names))
            for i in np.flatnonzero(per):
                tot[d.names[i]] = tot.get(d.names[i], 0.0) + float(per[i])
        n = max(len(self.devices), 1)
        return sorted(((k_, v / n) for k_, v in tot.items()),
                      key=lambda kv: -kv[1])[:k]

    def label(self, t: float) -> str:
        inside = [(s, lab) for s, e, lab in self.spans if s <= t < e]
        return max(inside)[1] if inside else "between calls"

    def idle_gaps(self, k: int = 10) -> List[Tuple[str, float]]:
        """Longest stretches of the window in which nothing ran on
        device 0, each labelled by the host span around its midpoint."""
        if not self.devices:
            return []
        a, b = self._busy_segments(self.devices[0])
        lo, hi = self.window
        g0 = np.concatenate([[lo], b])
        g1 = np.concatenate([a, [hi]])
        length = g1 - g0
        top = np.argsort(-length, kind="stable")[:k]
        return [(self.label(0.5 * (g0[i] + g1[i])), float(length[i]))
                for i in top if length[i] > 0]
