"""Spans and counters of the simulator's run APIs, recorded in memory.

A caller that wants to know where a call's time goes opens a recording
around it::

    from repro.flashsim import obs

    with obs.recording() as rec:
        simulate_batch(...)
    print(rec.summary())        # {name: (count, total ms, self ms)}
    print(rec.counters)         # {"dispatches": 2, "lane_steps_run": ...}

Spans are named ``flashsim.<step>`` and nest: each records its name, its
start and end on ``time.perf_counter_ns()``, the index of its parent in
``Recording.spans`` (-1 for none), the id of the run-API call it belongs
to (every span opened with no span open starts a new call), and the
attributes its site gave.  Spans nest by the order they open, so record
one thread's calls at a time.  While a recording is open each span also
enters ``jax.profiler.TraceAnnotation(name)``, so a profile taken at the
same time holds it on its own host plane and clock.

With no recording open, :func:`span` returns one shared object whose
``with`` does nothing: no clock read, no allocation.  Counters
(:func:`count`) add to one process-wide dict of ints; a recording reports
how much each grew while it was open.  The ``dispatches`` counter counts
every lockstep-core dispatch, recording or not; the lane counters of a
dispatch are counted only while a recording is open.  Worker processes
of a sweep (``workers > 1``) record nothing in the parent.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Dict, Iterator, List, Optional, Tuple

from jax.profiler import TraceAnnotation

#: Process-wide counters (never reset; recordings report deltas).
_COUNTS: Dict[str, int] = {}

#: The open recording, or None.
_REC: Optional["Recording"] = None


@dataclasses.dataclass
class Span:
    name: str
    t0_ns: int
    t1_ns: int           # 0 while the span is open
    parent: int          # index into Recording.spans, -1 for a root
    call: int            # id of the run-API call the span belongs to
    attrs: dict

    @property
    def ms(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-6


class Recording:
    """The spans and counter deltas of one ``with recording()`` block."""

    def __init__(self):
        self.spans: List[Span] = []
        self._open: List[int] = []          # indexes of the open spans
        self._calls = 0
        self._base = dict(_COUNTS)
        self._final: Optional[Dict[str, int]] = None

    @property
    def counters(self) -> Dict[str, int]:
        """How much each counter grew while the recording was open."""
        now = self._final if self._final is not None else _COUNTS
        return {k: v - self._base.get(k, 0) for k, v in now.items()
                if v != self._base.get(k, 0)}

    def self_ms(self) -> List[float]:
        """Each span's duration less the time its children cover
        (children of one span never overlap: spans nest)."""
        own = [s.ms for s in self.spans]
        for s in self.spans:
            if s.parent >= 0:
                own[s.parent] -= s.ms
        return own

    def summary(self) -> Dict[str, Tuple[int, float, float]]:
        """``{name: (count, total ms, self ms)}`` over the closed spans."""
        out: Dict[str, Tuple[int, float, float]] = {}
        for s, own in zip(self.spans, self.self_ms()):
            n, tot, slf = out.get(s.name, (0, 0.0, 0.0))
            out[s.name] = (n + 1, tot + s.ms, slf + own)
        return out


class _Off:
    """The span returned while nothing records."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Live:
    __slots__ = ("rec", "name", "attrs", "index", "note")

    def __init__(self, rec: Recording, name: str, attrs: dict):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self):
        rec = self.rec
        if rec._open:
            parent = rec._open[-1]
            call = rec.spans[parent].call
        else:
            parent, call = -1, rec._calls
            rec._calls += 1
        self.note = TraceAnnotation(self.name)
        self.note.__enter__()
        self.index = len(rec.spans)
        rec._open.append(self.index)
        rec.spans.append(Span(self.name, time.perf_counter_ns(), 0,
                              parent, call, self.attrs))
        return self

    def __exit__(self, *exc):
        rec = self.rec
        rec.spans[self.index].t1_ns = time.perf_counter_ns()
        rec._open.pop()
        self.note.__exit__(*exc)
        return False


def span(name: str, **attrs):
    """A context manager timing ``name`` while a recording is open."""
    rec = _REC
    if rec is None:
        return _OFF
    return _Live(rec, name, attrs)


def spanned(name: str, **attrs):
    """Decorator: run the function inside ``span(name, **attrs)``."""
    def wrap(f):
        @functools.wraps(f)
        def inner(*args, **kwargs):
            with span(name, **attrs):
                return f(*args, **kwargs)
        return inner
    return wrap


def active() -> bool:
    """Whether a recording is open (sites guard optional counting)."""
    return _REC is not None


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the process-wide counter ``name``."""
    _COUNTS[name] = _COUNTS.get(name, 0) + n


def total(name: str) -> int:
    """The process-wide value of counter ``name``."""
    return _COUNTS.get(name, 0)


@contextlib.contextmanager
def recording() -> Iterator[Recording]:
    """Record spans and counter deltas until the block ends.  Recordings
    do not nest."""
    global _REC
    if _REC is not None:
        raise RuntimeError("a recording is already open")
    rec = Recording()
    _REC = rec
    try:
        yield rec
    finally:
        _REC = None
        rec._final = dict(_COUNTS)
