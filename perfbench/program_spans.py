"""The program's own timeline, for the per-layer readers that read it: the
spans ``mx_rcnn_tpu.obs`` keeps in memory (``obs.tracer().recent()``; set-up
phases, the feed, compiles), taken in-process when the run is over.

They sit on ``time.monotonic_ns()``, which on Linux is the clock of the
harness's own host spans (``time.perf_counter_ns()``; a test holds the two
equal), so the offset ``readers.prepare`` derived for those - host clock
minus device clock, by the barriers - sets the program's spans against the
device trace too.  A program without the buffer (the parent of PR 25) gives
None, and the readers then report nothing.
"""

from __future__ import annotations

import sys


def spans(subsystem=None, prefix=""):
    """[(name, start_ns, duration_ns, attrs)] of the program's finished
    spans, oldest first, on the host's clock; None where the program keeps
    none, or where its buffer is full: the oldest - the set-up's - may
    have fallen out, and a reader that summed the rest would read low."""
    try:
        from mx_rcnn_tpu import obs
        from mx_rcnn_tpu.obs.tracing import SPAN_BUFFER

        held = obs.tracer().recent()
    except (ImportError, AttributeError):
        return None
    if len(held) >= SPAN_BUFFER:
        print(f"program_spans: the span buffer is full ({len(held)}): not read", file=sys.stderr)
        return None
    return [
        (s.name, float(s.start_ns), float(s.dur_ns), dict(s.attrs))
        for s in held
        if s.name.startswith(prefix) and subsystem in (None, s.subsystem)
    ]


def before_window(reading: dict, rows, apart_from=()):
    """[(start_ns, duration_ns)] of the ``rows`` that ended before the
    window opened and inside none of the ``apart_from`` rows (what another
    metric counts already); None without a window."""
    start = traced_from_ns(reading)
    if start is None:
        return None
    held = [(s, s + d) for _, s, d, _ in apart_from]
    return [
        (s, d) for _, s, d, _ in rows
        if s + d <= start and not any(a <= s + d <= b for a, b in held)
    ]


def host_offset_ns(reading: dict):
    """Host clock minus device clock as ``readers.prepare`` derived it (a
    harness span before and after it was set on the device's clock); None
    when it found no barrier to fit."""
    own, moved = reading.get("host_spans") or [], reading.get("host") or []
    if not own or not moved:
        return None
    offset = own[0][1] - moved[0][1]
    _check_clock(reading, offset)
    return offset


def _check_clock(reading: dict, fitted: float) -> None:
    """One line on stderr a traced run: how far the bridge the host can read
    for itself - its wall clock against the trace file's own
    ``profile_start_time`` - lies from the barrier fit."""
    if reading.get("clock_checked"):
        return
    reading["clock_checked"] = True
    try:
        from mx_rcnn_tpu.obs import tracing
        from perfbench import hlo_module

        hlo_module.module_of_reading(reading)
        start = hlo_module.profile_start_ns(reading["xplane_path"])
        read = start - tracing.wall_offset_ns()  # profile start on the span clock
    except (ImportError, AttributeError, KeyError, TypeError, OSError):
        return
    reading["clock_check_us"] = (fitted - read) / 1e3
    print(
        f"clock: device zero on the host's clock by the barrier fit {fitted:.0f} ns, the "
        f"trace's profile_start_time on it {read:.0f} ns: the fit lies "
        f"{(fitted - read) / 1e3:.1f} us after",
        file=sys.stderr,
    )


def traced_from_ns(reading: dict):
    """Host-clock time at which the traced stretch of the window began (the
    first span the harness recorded); None without one.  Nothing compiles
    inside the window (``built_in_window`` is 0 on a sound run), so what was
    counted before this was counted before the window opened."""
    own = reading.get("host_spans") or []
    return min(s for _, s, _ in own) if own else None


def in_window(reading: dict, rows) -> list:
    """``rows`` set on the device's clock and clipped to the analysed window
    ``lo``..``hi``; [] when the offset is unknown."""
    offset = host_offset_ns(reading)
    if offset is None:
        return []
    lo, hi = reading["lo"], reading["hi"]
    out = []
    for name, s, d, attrs in rows:
        a, b = max(s - offset, lo), min(s - offset + d, hi)
        if b > a:
            out.append((name, a, b - a, attrs))
    return out
