"""Spans: where the host's time goes, on the device trace's clock.

``span(name, source=..., rid=..., nbytes=...)`` is a context manager that
times one stretch of host work. It does three things:

  * opens a ``jax.profiler.TraceAnnotation`` of the same name, so that
    under the JAX profiler the span lands in the trace beside the device's
    operations and names what the host was doing while the device idled;
  * adds its ``time.perf_counter`` duration, a count and its bytes to
    process-wide totals kept per ``(name, source)``;
  * appends a ``SpanRecord`` (name, start, end, the span open around it on
    the same thread, source, request id, bytes) to a bounded ring, which
    the benchmark's per-layer readers window by time.

A span times the host and nothing else: it never waits on the device. A
dispatch span ends when the work is enqueued; the wait that follows is a
span of its own where the program already waits. Every name starts with
``PREFIX``. The recorder is process-wide, as the profiler's annotations
are: the serving thread, the prefetcher's threads and the re-tiering
daemon all record into it, each under names of their own.

Code that also keeps a stats field for the same interval reads the span's
``seconds`` after it closes, so one timing feeds both.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation

PREFIX = "repro."
RING = 1 << 16  # span records kept; a 51-s serving window makes about 10^4


class SpanRecord(NamedTuple):
    seq: int                 # order of opening, process-wide
    name: str
    start: float             # time.perf_counter seconds
    end: float
    parent: Optional[int]    # seq of the span open around it on the same thread
    source: str              # a tier-1 load's source: "fault" | "preload" | "prefetch"
    rid: Optional[int]       # request id, where the span serves one request
    nbytes: int

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Span:
    """One open span; ``seconds`` is its duration once closed. ``nbytes``
    may be set inside the block, where the bytes are known only then."""

    __slots__ = ("_rec", "_ann", "name", "source", "rid", "nbytes", "seq", "parent",
                 "start", "end")

    def __init__(self, rec: "Recorder", name: str, source: str, rid: Optional[int],
                 nbytes: int):
        self._rec = rec
        self.name, self.source, self.rid, self.nbytes = name, source, rid, nbytes
        self.start = self.end = 0.0

    def __enter__(self) -> "Span":
        stack = self._rec._stack()
        self.parent = stack[-1].seq if stack else None
        self.seq = next(self._rec._seq)
        stack.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._ann.__exit__(*exc)
        self._rec._stack().pop()
        self._rec.add(SpanRecord(self.seq, self.name, self.start, self.end, self.parent,
                                 self.source, self.rid, int(self.nbytes)))

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Recorder:
    """Totals per ``(name, source)`` and a ring of the last ``ring`` span
    records. Thread-safe."""

    def __init__(self, ring: int = RING):
        self._ring: deque = deque(maxlen=ring)
        self._totals: dict[tuple[str, str], list] = {}
        self._lock = threading.Lock()
        self._seq = itertools.count()
        self._local = threading.local()
        self.added = 0  # records ever added; more than the ring holds once it wraps

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, *, source: str = "", rid: Optional[int] = None,
             nbytes: int = 0) -> Span:
        if not name.startswith(PREFIX):
            raise ValueError(f"span name {name!r} does not start with {PREFIX!r}")
        return Span(self, name, source, rid, nbytes)

    def add(self, r: SpanRecord) -> None:
        with self._lock:
            self._ring.append(r)
            self.added += 1
            t = self._totals.get((r.name, r.source))
            if t is None:
                self._totals[(r.name, r.source)] = [1, r.end - r.start, r.nbytes]
            else:
                t[0] += 1
                t[1] += r.end - r.start
                t[2] += r.nbytes

    def totals(self) -> dict[tuple[str, str], tuple[int, float, int]]:
        """``(name, source)`` -> (count, seconds, bytes) since the last reset."""
        with self._lock:
            return {k: tuple(v) for k, v in self._totals.items()}

    def records(self) -> list[SpanRecord]:
        with self._lock:
            return list(self._ring)

    def between(self, t0: float, t1: float) -> Optional[list[SpanRecord]]:
        """The records of spans that started in ``[t0, t1)``, or None where
        the ring has already dropped one that may have. Records enter the
        ring as their spans close, so a dropped record closed no later than
        the oldest kept one: if that one closed before ``t0``, nothing
        dropped started inside the window."""
        with self._lock:
            recs = list(self._ring)
            dropped = self.added > len(recs)
        if dropped and recs and recs[0].end > t0:
            return None
        return [r for r in recs if t0 <= r.start < t1]

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self._totals.clear()
            self.added = 0


RECORDER = Recorder()


def span(name: str, *, source: str = "", rid: Optional[int] = None, nbytes: int = 0) -> Span:
    """A span on the process-wide recorder (see the module docstring)."""
    return RECORDER.span(name, source=source, rid=rid, nbytes=nbytes)
