"""Tracing / profiling hooks.

The reference has no profiling beyond the Speedometer samples/sec print
(SURVEY.md §6: ``mx.profiler`` exists engine-side but the repo never uses
it).  Here profiling is a first-class loop feature: the device's side of
a window goes through ``jax.profiler`` (an XPlane, viewable in
XProf/Perfetto/TensorBoard), the host's side is the program's own spans
(``obs/tracing.py``), written beside the XPlane as ``host_spans.json``
with the offset that turns their clock into the Unix wall clock the XPlane
dates its start on (``tools/obs_report.py --profile-dir`` merges the two).

The profiler's own host tracer stays OFF: on the TPU runtime it records
every block of the host's layout transposes (millions of events), which
starved the step to 39-56 % idle and made the file 170-385 MB (PERF.md
section 6).  A trace taken with it on measures the tracer.
"""

from __future__ import annotations

import contextlib
import glob
import json
import logging
import os
import time
from typing import Iterator, Optional

import jax

from mx_rcnn_tpu import obs
from mx_rcnn_tpu.obs import tracing

log = logging.getLogger("mx_rcnn_tpu")

HOST_SPANS_FILE = "host_spans.json"


def _start_trace(logdir: str) -> tuple[int, int]:
    """Start the profiler, host tracers off.  -> (monotonic ns at start,
    offset from the span clock to the Unix wall clock, taken here)."""
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    options.python_tracer_level = 0
    t0_ns = time.monotonic_ns()
    offset_ns = tracing.wall_offset_ns()
    jax.profiler.start_trace(logdir, profiler_options=options)
    return t0_ns, offset_ns


def _stop_trace(logdir: str, t0_ns: int, offset_ns: int) -> None:
    """Stop the profiler and write the window's spans beside its XPlane."""
    jax.profiler.stop_trace()
    runs = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*")))
    out_dir = runs[-1] if runs else logdir
    spans = [s.to_chrome() for s in obs.tracer().recent(since_ns=t0_ns)]
    with open(os.path.join(out_dir, HOST_SPANS_FILE), "w") as f:
        json.dump({
            # ts of a span (us, time.monotonic_ns) * 1000 + this = Unix ns;
            # less the XPlane's profile_start_time = its place on the
            # device events' axis, to a few ms (obs/tracing.py).
            "wall_offset_ns": offset_ns,
            "window_start_mono_ns": t0_ns,
            "spans": spans,
        }, f)
    log.info(
        "profiler trace written to %s (%d host spans beside it)",
        logdir, len(spans),
    )


@contextlib.contextmanager
def trace(logdir: Optional[str]) -> Iterator[None]:
    """Device trace of the enclosed block into ``logdir``, the program's
    spans of the block beside it (no-op when logdir is None)."""
    if not logdir:
        yield
        return
    t0_ns, offset_ns = _start_trace(logdir)
    try:
        yield
    finally:
        _stop_trace(logdir, t0_ns, offset_ns)


class ProfileWindow:
    """Trace a [start, stop) step interval of a training loop.

    Robust to resume (entering the loop mid-window starts the trace on the
    first step inside it) and to runs that end inside the window (the loop
    calls :meth:`close` on exit; an active trace is stopped exactly once).
    """

    def __init__(self, logdir: Optional[str], start: int, stop: int) -> None:
        self.logdir = logdir
        self.start = start
        self.stop = stop
        self._active = False
        self._t0_ns = self._offset_ns = 0

    def step(self, i: int, sync=None) -> None:
        """Call at the top of loop step ``i``."""
        if not self.logdir:
            return
        if not self._active and self.start <= i < self.stop:
            if sync is not None:
                jax.block_until_ready(sync)
            self._t0_ns, self._offset_ns = _start_trace(self.logdir)
            self._active = True
        elif self._active and i >= self.stop:
            self.close(sync)

    def close(self, sync=None) -> None:
        if self._active:
            if sync is not None:
                jax.block_until_ready(sync)
            _stop_trace(self.logdir, self._t0_ns, self._offset_ns)
            self._active = False


class StepTimer:
    """Wall-clock stats for loop steps, with warmup discard.

    Unlike the Speedometer (throughput log line), this keeps percentiles
    for perf work: ``timer.summary()`` -> dict(mean/p50/p90/p99/max in
    ms) — the tail columns (p99/max) are what regression tracking cares
    about; a mean can hide a 10x straggler step.
    """

    def __init__(self, warmup: int = 2) -> None:
        self.warmup = warmup
        self._times: list[float] = []
        self._t0: Optional[float] = None
        self._seen = 0

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._seen += 1
        if self._seen > self.warmup:
            self._times.append(dt)

    def summary(self) -> dict[str, float]:
        if not self._times:
            return {}
        import numpy as np

        arr = np.asarray(self._times) * 1e3
        return {
            "steps": float(len(arr)),
            "mean_ms": float(arr.mean()),
            "p50_ms": float(np.percentile(arr, 50)),
            "p90_ms": float(np.percentile(arr, 90)),
            "p99_ms": float(np.percentile(arr, 99)),
            "max_ms": float(arr.max()),
        }
