"""The program's timeline: host spans -> an in-memory buffer, and
Chrome-trace (Perfetto-loadable) events when the plane is configured.

Spans are explicit host-side begin/end windows with ids:

* ``trace_id``   — one per request (or train step); hedged fleet
  attempts share their request's trace_id, so the whole request tree is
  one query away.
* ``span_id`` / ``parent_id`` — parent/child integrity (an attempt span
  is a child of the fleet request span; the engine's queue/device spans
  are children of the attempt).

**Cost.**  A span is two ``time.monotonic_ns()`` reads, an id from a
process counter and one ``deque.append``: no ``uuid4``, no wall-clock
read (``ts_wall`` is derived from one wall/monotonic pair taken when the
module is imported), no dict built until somebody reads the span.

**Where finished spans go.**  Always into the tracer's own bounded buffer
(:data:`SPAN_BUFFER` spans; :meth:`Tracer.recent` reads it) — that is
"tracing off", what a benchmark run with the profiler off sees, and what
the per-layer readers (``perfbench/program_spans.py``) and
``utils/profiling.py`` take their spans from when a run ends.  The plane
(``obs/__init__.py``) also hands each span to the flight ring for crash
dumps and, when it is configured with ``spans=True`` ("durable"), appends
it to ``spans.jsonl`` — one Chrome-trace complete event (``"ph": "X"``,
ts/dur in microseconds) per line, via the same single-``write(2)``
crash-safe discipline as the journal.  ``tools/obs_report.py`` reads that
file.

**Clock.**  Spans sit on ``time.monotonic_ns()`` — on Linux the same
clock as ``time.perf_counter_ns()`` (tests/test_timeline.py holds them equal).
The XPlane's device events sit on a clock no host call reads: nanoseconds
since the profiler session began, which the trace file itself dates
(``profile_start_time``, Unix nanoseconds, in its ``Task Environment``
plane) to within the 0.5-2.3 ms the device's zero was measured to lie
after it (PERF.md section 3).  So the bridge the host can offer is the
wall clock: :func:`wall_offset_ns` — wall minus monotonic, read where a
trace starts — turns a span's time into Unix nanoseconds, and whoever
holds the trace file subtracts its ``profile_start_time``
(``tools/obs_report.py --profile-dir``).  Good to a few milliseconds: for
reading a 270 ms step, not for blaming a 1 ms gap — the benchmark fits its
barriers instead (``perfbench/trace_reduce.py::clock_offset_ns``).
"""

from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from typing import Callable, Optional

__all__ = [
    "Span", "Tracer", "new_trace_id", "SPAN_BUFFER", "wall_of",
    "wall_offset_ns",
]

# Set-up plus a 20 s training window with room to spare: a start is ~230
# programs, each a lowering and a compile (or a load), ~60 traces over a
# millisecond and the six set-up spans; a step is five spans (train_step,
# data, step, feed.wait, feed.put) and ~75 steps fit a window — a whole
# benchmark run, the plain reference's programs after the window included,
# left 595 spans here (measured, PERF.md section 6).  8,192 keeps such a
# run many times over; at ~250 bytes a span that is 2 MB at most.
SPAN_BUFFER = 8192

_ids = itertools.count(1)  # next() on it is atomic under the GIL
_id_prefix = 0


def _new_prefix() -> None:
    """32 random bits, once per process (again in a forked child), so that
    two processes writing one run's spans never share an id."""
    global _id_prefix
    _id_prefix = int.from_bytes(os.urandom(4), "big")


_new_prefix()
os.register_at_fork(after_in_child=_new_prefix)

def wall_offset_ns() -> int:
    """Unix nanoseconds minus ``time.monotonic_ns()``, read now: add it to a
    span's time to get the wall clock, the one a trace file dates its own
    start on.  Read it where ``jax.profiler.start_trace`` is called and keep
    the reading with the trace (the wall clock can be stepped later)."""
    return time.time_ns() - time.monotonic_ns()


# Read once: every span's ``ts_wall`` is derived from it.
_WALL_OFFSET0_NS = wall_offset_ns()


def _new_id() -> str:
    return f"{_id_prefix:08x}{next(_ids) & 0xFFFFFFFF:08x}"


def new_trace_id() -> str:
    return _new_id()


def wall_of(mono_ns: float) -> float:
    """Unix seconds of a ``time.monotonic_ns()`` reading, by the offset read
    at import (a wall clock stepped since then is not followed)."""
    return (mono_ns + _WALL_OFFSET0_NS) / 1e9


class Span:
    """One explicit begin/end window.  Context-manager or manual end()."""

    __slots__ = (
        "name", "subsystem", "trace_id", "span_id", "parent_id",
        "attrs", "_t0_ns", "dur_ns", "_tracer", "_ended", "_tid",
    )

    def __init__(self, tracer: "Tracer", name: str, subsystem: str,
                 trace_id: Optional[str], parent_id: Optional[str],
                 attrs: Optional[dict]) -> None:
        self.name = name
        self.subsystem = subsystem
        self.span_id = _new_id()
        self.trace_id = trace_id or self.span_id
        self.parent_id = parent_id
        self.attrs = dict(attrs) if attrs else {}
        self._tracer = tracer
        self.dur_ns = 0
        self._ended = False
        self._tid = 0
        self._t0_ns = time.monotonic_ns()

    @property
    def start_ns(self) -> int:
        """``time.monotonic_ns()`` at the span's start."""
        return self._t0_ns

    @property
    def end_ns(self) -> int:
        return self._t0_ns + self.dur_ns

    def child(self, name: str, attrs: Optional[dict] = None) -> "Span":
        return self._tracer.span(
            name, subsystem=self.subsystem, trace_id=self.trace_id,
            parent_id=self.span_id, attrs=attrs,
        )

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def end(self, **attrs) -> None:
        if self._ended:
            return
        self.dur_ns = time.monotonic_ns() - self._t0_ns
        self._ended = True
        if attrs:
            self.attrs.update(attrs)
        self._tid = threading.get_ident()
        self._tracer._finish(self)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        self.end()

    def to_chrome(self) -> dict:
        """Chrome-trace "complete" event; ts/dur in microseconds on the
        process monotonic clock (one timeline per pid)."""
        return {
            "ph": "X",
            "name": self.name,
            "cat": self.subsystem,
            "ts": self._t0_ns / 1e3,
            "dur": self.dur_ns / 1e3,
            "pid": os.getpid(),
            "tid": (self._tid or threading.get_ident()) % 2**31,
            "args": {
                "trace_id": self.trace_id,
                "span_id": self.span_id,
                "parent_id": self.parent_id,
                "ts_wall": round(wall_of(self._t0_ns), 3),
                **self.attrs,
            },
        }


class Tracer:
    """Span factory.  Keeps the last :data:`SPAN_BUFFER` finished spans and
    routes each to a sink (plane-installed)."""

    def __init__(self, sink: Optional[Callable[[Span], None]] = None,
                 capacity: int = SPAN_BUFFER) -> None:
        self._sink = sink
        self._buf: collections.deque[Span] = collections.deque(maxlen=capacity)

    def set_sink(self, sink: Optional[Callable[[Span], None]]) -> None:
        self._sink = sink

    def span(self, name: str, *, subsystem: str = "app",
             trace_id: Optional[str] = None,
             parent_id: Optional[str] = None,
             attrs: Optional[dict] = None) -> Span:
        return Span(self, name, subsystem, trace_id, parent_id, attrs)

    def record(self, name: str, start_ns: int, dur_ns: int, *,
               subsystem: str = "app", attrs: Optional[dict] = None) -> Span:
        """A finished span from times somebody else took (a listener that
        is told a duration when the work is over)."""
        s = Span(self, name, subsystem, None, None, attrs)
        s._t0_ns = start_ns
        s.dur_ns = dur_ns
        s._ended = True
        s._tid = threading.get_ident()
        self._finish(s)
        return s

    def recent(self, since_ns: Optional[int] = None,
               subsystem: Optional[str] = None) -> list[Span]:
        """Finished spans still in the buffer, oldest first: those that
        ended at or after ``since_ns`` (monotonic), of ``subsystem``."""
        spans = list(self._buf)  # one C call: safe against appends
        if since_ns is not None:
            spans = [s for s in spans if s.end_ns >= since_ns]
        if subsystem is not None:
            spans = [s for s in spans if s.subsystem == subsystem]
        return spans

    def clear(self) -> None:
        self._buf.clear()

    def _finish(self, span: Span) -> None:
        self._buf.append(span)
        sink = self._sink
        if sink is not None:
            try:
                sink(span)
            except Exception:  # noqa: BLE001 - tracing must never throw up
                pass
