"""From a profiler trace (``.xplane.pb``) to numbers: which planes are chips,
when each chip was busy, how long named ops took, step intervals, exposed
collectives, and what the host did in the longest gaps.  Pure functions over
plain (name, start_ns, duration_ns) tuples, so that a hand-made fragment
tests them; ``load`` is the only part that needs JAX's reader.

What a TPU trace of this JAX carries (looked at by hand, PR 24; PERF.md
section 6 has the account): one plane ``/device:TPU:<n>`` per chip with lines
``XLA Modules`` (one event per run of a compiled program, named
``jit_<fn>(<id>)``), ``XLA Ops`` (one event per HLO op run) and ``Steps``.
The host tracer is off (run.py); the harness's own host spans come on its own
clock and ``clock_offset_ns`` sets them against the device's.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> dict:
    """{"devices": {n: {line name: [(name, start_ns, dur_ns, scope)]}}}.  An
    op's event is named by its whole HLO text (``%fusion.31 = f32[...]
    fusion(...)``): only the instruction's own name is kept.  The trace carries
    no scope path; the harness fills ``scope`` from the compiled program's
    metadata (readers.py)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = {
                line.name: [
                    (short_name(e.name), float(e.start_ns), float(e.duration_ns), "")
                    for e in line.events
                ]
                for line in plane.lines
            }
    return {"devices": devices}


def clock_offset_ns(barrier_ends, step_ends, steps_per_barrier: int):
    """Host clock minus trace clock.  The trace stops at a barrier of the
    loop, so counted from its end its step programs come in whole stretches of
    ``steps_per_barrier``, and each barrier (a ``device_get`` of the stretch's
    last output) returns just after that stretch's last step ends: the least
    of those differences, so that no barrier lies before its step.  (The
    profiler may miss steps of the stretch it starts up in, so the count runs
    from the end.)  None without a barrier or a step."""
    ends = sorted(step_ends, reverse=True)[::steps_per_barrier]
    pairs = list(zip(sorted(barrier_ends, reverse=True), ends))
    return min(h - e for h, e in pairs) if pairs else None


def short_name(name: str) -> str:
    """``%fusion.31 = f32[8]{0} fusion(...)`` -> ``fusion.31``."""
    return name.split(" ", 1)[0].lstrip("%")


def scopes_from_hlo(hlo_text: str) -> dict:
    """{instruction name: op_name} from a compiled program's HLO text, whose
    ``metadata={op_name="jit(step)/jit(main)/jvp(proposals)/..."}`` carries the
    ``jax.named_scope`` path that the trace's events lack."""
    rx = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"', re.M)
    return dict(rx.findall(hlo_text))


def scope_group(scope: str) -> str:
    """The layer an op belongs to, from its scope path: the first component
    past the ``jit(...)`` wrappers, a flax ``Module.method`` component giving
    way to the module under it; ``.bwd`` where the path runs through
    ``transpose(``.  "" when there is no path."""
    parts = [p for p in scope.split("/") if p and not p.startswith("jit(")]
    if not parts:
        return ""
    head = re.sub(r"^(?:transpose\(|jvp\(|vmap\()+|\)+$", "", parts[0])
    if ("." in head or not head) and len(parts) > 1:
        head = re.sub(r"^(?:transpose\(|jvp\(|vmap\()+|\)+$", "", parts[1])
    return (head or "other") + (".bwd" if "transpose(" in parts[0] else "")


def union_ns(intervals) -> float:
    """Total length of the union of (start, duration) intervals."""
    total, end = 0.0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def clip_to(intervals, lo, hi):
    out = []
    for s, d in intervals:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b - a))
    return out


def busy_ns(ops, lo=None, hi=None) -> float:
    iv = [(s, d) for _, s, d, *_ in ops]
    if lo is not None:
        iv = clip_to(iv, lo, hi)
    return union_ns(iv)


def gaps(ops, lo, hi):
    """Idle (start, duration) stretches of a device between lo and hi."""
    out, end = [], lo
    for s, d in sorted(clip_to([(s, d) for _, s, d, *_ in ops], lo, hi)):
        if s > end:
            out.append((end, s - end))
        end = max(end, s + d)
    if hi > end:
        out.append((end, hi - end))
    return out


def percentile(values, q: float) -> float:
    """Nearest-rank percentile, q in (0, 100]."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    k = max(0, min(len(v) - 1, int(-(-q * len(v) // 100)) - 1))
    return v[k]


def step_intervals_ns(modules, name_part: str):
    """Completion-to-completion intervals of consecutive runs of the program
    whose module event name contains ``name_part``."""
    ends = sorted(s + d for n, s, d, *_ in modules if name_part in n)
    return [b - a for a, b in zip(ends, ends[1:])]


def time_by(ops, pred) -> float:
    """Summed device time of the ops ``pred(name, scope)`` picks."""
    return sum(d for n, _, d, sc in ops if pred(n, sc))


def exposed_collective_ns(ops) -> float:
    """Time inside collective ops during which no other op runs on that
    device: the part of the exchange the step waits for."""
    coll = [(s, d) for n, s, d, *_ in ops if COLLECTIVE.search(n)]
    rest = [(s, d) for n, s, d, *_ in ops if not COLLECTIVE.search(n)]
    covered = 0.0
    rest_sorted = sorted(rest)
    for s, d in coll:
        covered += union_ns(clip_to(rest_sorted, s, s + d))
    return sum(d for _, d in coll) - covered


def top_ops(ops, group, n=10):
    """[(group name, seconds)], the ``n`` groups that took most device time:
    the union of a group's op intervals, so a loop op and the ops that run
    inside it count once."""
    acc = {}
    for name, s, d, sc in ops:
        acc.setdefault(group(name, sc), []).append((s, d))
    rows = {k: union_ns(v) for k, v in acc.items()}
    return [[k, v / 1e9] for k, v in sorted(rows.items(), key=lambda kv: -kv[1])[:n]]


def attribute_gaps(gap_list, host_events, n=10):
    """[(what the host was doing, seconds)] for the idle gaps: each gap goes
    to the annotated host span that covers most of it, else ``unattributed``."""
    acc = {}
    for gs, gd in gap_list:
        best, best_cov = "unattributed", 0.0
        for name, s, d in host_events:
            cov = min(gs + gd, s + d) - max(gs, s)
            if cov > best_cov:
                best, best_cov = name, cov
        acc[best] = acc.get(best, 0.0) + gd
    return [[k, v / 1e9] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
