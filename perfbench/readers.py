"""What the per-layer readers under ``metrics/`` share: the traced window of
a run cut down to the busiest chip, and the few sums most readers want.  A
reader takes the ``reading`` dict and returns a number, or None when the
trace holds nothing for it (the harness then leaves the metric out; it never
reports 0 for a share of a roofline or of a peak).
"""

from __future__ import annotations

import re

from perfbench import trace_reduce as tr

BARRIERS = ("sync", "fetch")  # host spans that end as a stretch's last step ends


def scope_re(scope: str):
    """Matches an op whose scope path has ``scope`` as a component, bare or
    wrapped by a transformation (``jvp(scope)``, ``transpose(jvp(scope))``)."""
    return re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")


def prepare(reading: dict) -> None:
    """Adds: ``ops``/``modules`` of the busiest chip inside the analysed
    window ``lo``..``hi`` (ns), ``window_s``, ``busy_s`` (mean over chips),
    ``steps_traced``, ``breakdown``.

    The window runs from the end of the first traced barrier (``sync`` span,
    the harness's own clock set against the device's) to the end of the last:
    whole barrier-to-barrier stretches of the loop, without the stretch in
    which the profiler itself started up and without the tail in which it
    stops."""
    trace = reading["trace"]
    devices = trace["devices"]
    if not devices:
        raise RuntimeError("the trace holds no device plane: no operation ran on a chip")
    scopes = reading.get("scopes") or {}
    all_ops = {
        n: [(nm, s, d, scopes.get(nm, sc)) for nm, s, d, sc in dev.get("XLA Ops", [])]
        for n, dev in devices.items()
    }
    if not any(all_ops.values()):
        raise RuntimeError("the trace's device planes hold no XLA op")
    name = reading.get("program_name", "")
    first = min(all_ops)
    offset = tr.clock_offset_ns(
        [s + d for nm, s, d in reading.get("host_spans", []) if nm in BARRIERS],
        [s + d for nm, s, d, _ in devices[first].get("XLA Modules", []) if name and name in nm],
        int(reading.get("sync_every", 1)),
    )
    host = [] if offset is None else [
        (nm, s - offset, d) for nm, s, d in reading["host_spans"]
    ]
    syncs = sorted(s + d for nm, s, d in host if nm in BARRIERS)
    if len(syncs) >= 2:
        lo, hi = syncs[0], syncs[-1]
    else:
        lo = min(s for ops in all_ops.values() for _, s, _, _ in ops)
        hi = max(s + d for ops in all_ops.values() for _, s, d, _ in ops)
    busy = {n: tr.busy_ns(ops, lo, hi) for n, ops in all_ops.items()}
    busiest = max(busy, key=busy.get)
    # 1 us of slack: a barrier's end set on the device's clock may sit on its step's end
    inside = lambda rows: [r for r in rows if r[1] >= lo - 1e3 and r[1] + r[2] <= hi + 1e3]
    ops = inside(all_ops[busiest])
    modules = inside(devices[busiest].get("XLA Modules", []))
    runs = [m for m in modules if name and name in m[0]]
    reading.update(
        ops=ops, modules=modules, lo=lo, hi=hi, window_s=(hi - lo) / 1e9,
        busy_s=sum(busy.values()) / len(busy) / 1e9, busiest_busy_s=busy[busiest] / 1e9,
        steps_traced=len(runs), host=host,
    )
    reading["breakdown"] = {
        "device_ops": tr.top_ops(
            ops, lambda nm, sc: tr.scope_group(sc) or re.sub(r"[.\d]+$", "", nm)
        ),
        "idle_gaps": tr.attribute_gaps(tr.gaps(ops, lo, hi), host),
    }


def named_ms_per_step(reading: dict, pattern: str, exclude: str = ""):
    """Device milliseconds per step of the ops whose own name matches
    ``pattern`` (and not ``exclude``): a kernel found by the name it runs as."""
    rx = re.compile(pattern)
    ex = re.compile(exclude) if exclude else None
    iv = [(s, d) for nm, s, d, _ in reading["ops"] if rx.search(nm) and not (ex and ex.search(nm))]
    if not iv or not reading["steps_traced"]:
        return None
    return tr.union_ns(iv) / 1e6 / reading["steps_traced"]


def scoped_ms_per_step(reading: dict, scope: str, wrapped=None):
    """Device milliseconds per step of the ops under ``scope`` (union of their
    intervals, so a loop op and the ops inside it count once).  ``wrapped``:
    None = any; True = only under ``transpose(`` (backward); False = not."""
    rx = scope_re(scope)
    iv = []
    for _, s, d, sc in reading["ops"]:
        if not rx.search(sc):
            continue
        back = "transpose(" in sc
        if wrapped is None or wrapped == back:
            iv.append((s, d))
    if not iv or not reading["steps_traced"]:
        return None
    return tr.union_ns(iv) / 1e6 / reading["steps_traced"]


def roofline_share(reading: dict, need: dict, kernel_ms_per_step):
    """% of the roofline's least time in the kernel's measured time."""
    from perfbench.flops import least_seconds

    if not kernel_ms_per_step:
        return None
    least, _bound = least_seconds(need, reading["peak"])
    return 100.0 * least / (kernel_ms_per_step / 1e3)
