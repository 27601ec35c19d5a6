"""One process, one cell, one run:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

builds the cell's system from ``--seed`` on the device, warms this cell's
shapes only (counted as ``setup_s``), measures for ``--seconds``, compares the
timed path's numbers with the plain reference, and prints the result as the
last line of standard output.  ``--trace 0`` reports the cell's end-to-end
metrics with the profiler off; ``--trace 1`` profiles a few seconds of the
steady window and reports the per-layer metrics and a breakdown.

Exits non-zero and prints no result when JAX finds no TPU, or fewer chips
than the cell asks for.  Takes no notice of ``BENCH_RUN``.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()  # process start, as near as Python sees it

import argparse
import contextlib
import importlib
import json
import os
import shutil
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE_START_S = 2.0  # into the window before the profiler starts: the feed is in its stride


class NoChip(RuntimeError):
    pass


class Context:
    """What an entry needs from the harness: the cell, the clock's marks, the
    transfer guard, the profiler and the device's memory."""

    def __init__(self, spec, workload, seed, seconds, trace, t_start):
        self.spec = spec
        self.cell = spec.cell(workload)
        self.config = spec.config(self.cell["config"])
        self.seed, self.seconds, self.trace = seed, seconds, bool(trace)
        self.t_start = t_start
        self.setup_s = None
        self.programs: list[str] = []       # every program built, in order
        self.built_in_window: list[str] = []
        self._in_window = False
        self.trace_dir = os.path.join(spec.root, ".perfbench_trace", workload)
        self._trace_state = "off"           # off -> armed -> on -> done
        self.trace_cost: dict = {}          # seconds the profiler took to start and to stop
        self.host_spans: list = []          # (name, start_ns, duration_ns), own clock
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_compile)

    # -- compile accounting ---------------------------------------------------
    def _on_compile(self, event, seconds, **kw):
        if event == _BACKEND_COMPILE:
            name = str(kw.get("fun_name", "?"))
            self.programs.append(name)
            if self._in_window:
                self.built_in_window.append(name)

    # -- clock marks ----------------------------------------------------------
    def setup_done(self):
        self.setup_s = time.perf_counter() - self.t_start

    def window_open(self):
        self._in_window = True
        if self.trace:
            self._trace_state = "armed"

    def window_close(self):
        self._in_window = False
        if self._trace_state == "on":
            self._stop_trace(time.perf_counter())

    # -- the loop's transfer guard -------------------------------------------
    def guarded(self, fn, *args, first=False):
        """Call ``fn`` as the training loop does: under
        ``transfer_guard("disallow")`` on every call but a program's first."""
        import jax

        mode = os.environ.get("MX_RCNN_TRANSFER_GUARD", "disallow")
        if first or mode == "off":
            return fn(*args)
        with jax.transfer_guard(mode):
            return fn(*args)

    # -- profiler -------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name):
        """A host span of the traced window, on the harness's own clock
        (readers.py sets it against the device's; see ``at_sync``)."""
        if self._trace_state != "on":
            yield
            return
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.host_spans.append((name, float(t0), float(time.perf_counter_ns() - t0)))

    def at_sync(self, elapsed):
        """Called at each barrier of the window: the device is drained, so a
        trace may start or stop here without cutting a step in two."""
        if not self.trace:
            return
        # One barrier-to-barrier stretch for the profiler to start up in,
        # then ``trace_syncs`` stretches to analyse (readers.py::prepare).
        stretches = int(self.cell.get("trace_syncs", 3)) + 1
        now = time.perf_counter()
        if self._trace_state == "armed" and elapsed >= min(TRACE_START_S, 0.25 * self.seconds):
            import jax

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            # The host tracer is off: at any level above 0 it records every
            # block of the host's layout transposes (2.8 million events a
            # thread), which slowed each batch's host-to-device copy to over a
            # second, starved the step and made the trace 385 MB (PERF.md
            # section 6).  The harness keeps its own host spans instead.
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self._trace_state = "on"
            self.trace_cost["start_s"] = time.perf_counter() - now
            self._trace_syncs = 0
        elif self._trace_state == "on":
            self._trace_syncs += 1
            if self._trace_syncs >= stretches:
                self._stop_trace(now)

    def _stop_trace(self, now):
        import jax

        jax.profiler.stop_trace()
        self.trace_cost["stop_s"] = time.perf_counter() - now
        self._trace_state = "done"

    # -- device ---------------------------------------------------------------
    def memory(self) -> dict:
        """Peak bytes on the fullest chip.  ``peak_bytes_in_use`` holds live
        arrays only on this runtime; a running program's temporaries show as
        reserved (PERF.md section 6), so the high-water mark is the largest
        figure a chip reports."""
        import jax

        best = {"peak": 0, "in_use": 0, "reserved": 0}
        for d in jax.local_devices():
            s = d.memory_stats() or {}
            in_use = int(s.get("peak_bytes_in_use", 0))
            reserved = int(max(s.get("peak_bytes_reserved", 0), s.get("bytes_reserved", 0)))
            if max(in_use, reserved) > best["peak"]:
                best = {"peak": max(in_use, reserved), "in_use": in_use, "reserved": reserved}
        return best


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def require_chips(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform!r}; a measured run needs the chip")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")


def per_layer(ctx, res, spec) -> tuple[dict, dict, dict]:
    """The traced run's per-layer metrics, the device's busy seconds and the
    breakdown, from the trace the window left."""
    from perfbench import trace_reduce as tr
    from perfbench.peaks import peak

    trace = tr.load(tr.find_xplane(ctx.trace_dir))
    reading = {
        "trace": trace, "counters": res["counters"],
        "config": ctx.config, "peak": peak(device_info(1)["kind"]),
        "chips": ctx.cell["chips"], "program_name": res.get("program_name", ""),
        "step_flops": res.get("step_flops"),
        "scopes": res.get("scopes"), "host_spans": ctx.host_spans,
        "sync_every": res["counters"].get("sync_every", 1),
    }
    from perfbench import readers

    readers.prepare(reading)
    metrics = {}
    for m in spec.metrics_of(ctx.cell["name"], "per_layer"):
        value = spec.reader(m["name"])(reading)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    busy = {"busy_s": reading["busy_s"], "window_s": reading["window_s"]}
    return metrics, busy, reading["breakdown"]


def run_cell(workload, seed, seconds, trace, root=REPO_ROOT, require_chip=True,
             t_start=None) -> dict:
    """The whole of a run but the printing.  ``require_chip=False`` is for
    tests only: it skips the look for a chip and drives the rest."""
    from perfbench.spec import Spec

    spec = Spec(root)
    cell = spec.cell(workload)
    if require_chip:
        require_chips(cell["chips"])
        from perfbench import program

        program.configure_cache()
    ctx = Context(spec, workload, seed, seconds, trace, t_start or time.perf_counter())
    entry = importlib.import_module(f"perfbench.entries.{cell['entry']}")
    res = entry.run(ctx)

    from perfbench import compare

    numbers = dict(res["numbers"])
    numbers["built_in_window"] = len(ctx.built_in_window)
    limits = dict(cell["limits"], built_in_window=0)
    correct, rows = compare.judge(numbers, limits)
    mem = res["memory"]
    device = dict(device_info(cell["chips"]), memory_peak_bytes=mem["peak"])
    metrics = {}
    out = {"correct": correct, "attempted": res["attempted"], "failed": res["failed"]}
    if trace:
        metrics, busy, breakdown = per_layer(ctx, res, spec)
        device.update(busy)
        out["breakdown"] = breakdown
    else:
        for m in spec.metrics_of(cell["name"], "end_to_end"):
            value = ctx.setup_s if m["name"] == "setup_s" else res["end_to_end"][m["name"]]
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out.update(metrics=metrics, device=device)
    out["run"] = {
        "workload": workload, "seed": seed, "seconds": seconds, "trace": int(bool(trace)),
        "setup_s": ctx.setup_s, "programs_built": len(ctx.programs),
        "memory_in_use_bytes": mem["in_use"], "memory_reserved_bytes": mem["reserved"],
        "counters": res["counters"], "trace_cost": ctx.trace_cost,
        "extra": {k: v for k, v in {**res.get("extra", {}), **numbers}.items() if k not in rows},
    }
    out["compared"] = rows
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds, args.trace, t_start=_T_START)
    except NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 4
    for name, (value, limit) in out["compared"].items():
        print(f"compared {name}: {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
