"""A traced step by part, pass and owner: ``classify(reading)`` gives every
device op event of the analysed window ONE row, and the six readers of
``metrics/`` (``mixer_proj_ms``, ``mixer_glue_ms``, ``mixer_proj_roofline``,
``remat_ms``, ``xla_copy_ms``, ``unowned_share``) and the operator's table
(``tools/obs_report.py --profile-dir``) all read those rows.  Nothing here needs
a new name in the program: the scope paths, the opcodes and the operand graph
come from the optimized HLO the profiler stores in the trace file
(``hlo_module.py`` reads names and calls; ``parse_graph`` below adds
``HloInstructionProto.id`` 35, ``operand_ids`` 36, ``custom_call_target`` 28 and
``tuple_index`` 13 with the same wire reader).

The rules, in the order they apply (PERF.md section 3 has them in prose):

- *leaves only*: the event of a ``while``, ``conditional`` or ``call`` inside
  which other traced ops run is dropped in favour of those ops, so the rows
  partition the busy time; what of such an op no inner op covers (the loop's own
  overhead) stays as rows under the loop's name (``core`` under a scan, else
  ``glue``: never ``dense``, whatever the body holds).
- *owner*: the resolved scope path (``hlo_module.names_of_reading``); an op
  that has none adopts the nearest PRODUCER with one (operands back through
  nameless instructions, breadth first), else the nearest CONSUMER with one;
  a parameter, a constant and a loop's tuple element end a walk.
- *layer*, *kind*: the ``l<k>`` component and the component after it; outside
  the layers the first component that is not a flax ``Module.method``
  (``backbone`` gives way to ``patchify`` / ``neck`` under it).
- *part*: ``copy`` (no scope of its own after ``resolve``: XLA's op, adopted or
  not), ``kernel`` (a Pallas custom call), ``core`` (under ``scan`` or ``attn``,
  or ``moe/experts``), ``dense`` (the op or a computation it calls holds a
  ``dot`` or a ``convolution``), ``glue`` (the rest).
- *pass_*: ``fwd`` (no ``transpose(`` in the path), ``remat``
  (``rematted_computation`` BEFORE the ``l<k>`` component: the recomputed
  forward of the block's ``jax.checkpoint``), ``bwd`` (the rest; a
  ``rematted_computation`` after ``l<k>`` is a glue function's own and is part
  of the backward).
"""

from __future__ import annotations

import bisect
import collections
import re
from typing import NamedTuple

from perfbench import hlo_module as hm
from perfbench import trace_reduce as tr

KEY = "step_parts"                       # the reading's key this module caches under
CONTAINERS = ("while", "conditional", "call")
SOURCES = ("parameter", "constant", "iota")     # a walk ends there, named or not: CSE shares a constant
MATMULS = ("dot", "convolution")
PALLAS_TARGET = "tpu_custom_call"
SCAN_MIXERS = ("kda", "ssm", "mamba")
LAYER = re.compile(r"^l\d+$")
REMAT = "rematted_computation"
PASSES = ("fwd", "remat", "bwd")


class Unreadable(Exception):
    """The trace's HLO cannot carry the classification: no ``Hlo Proto``, an
    operand id no instruction has, an event no instruction is named as."""


class Row(NamedTuple):
    name: str
    start: float
    dur: float
    owner: str      # scope path, own or adopted; "" = nobody's
    layer: str      # "l6", "" outside the layers
    kind: str       # "kda", "ffn", ... / "optimizer", "proposals", ... / ""
    part: str       # "copy", "kernel", "core", "dense" or "glue"
    pass_: str      # one of PASSES
    adopted: str    # "", "producer" or "consumer"


# -- the operand graph, from the same Hlo Proto -------------------------------------


def parse_graph(hlo_proto) -> dict:
    """HloProto bytes -> {"by_name": {instruction name: node}, "nodes":
    {computation id: {instruction id: node}}, "users": {computation id:
    {instruction id: [node]}}}; a node is {"name", "opcode", "id", "operands":
    [ids], "calls": [computation ids], "comp", "target" (a custom call's),
    "index" (a get-tuple-element's)}."""
    module = next((v for no, _, v in hm.fields(hlo_proto) if no == 1), None)
    if module is None:
        raise Unreadable("the Hlo Proto holds no module")
    by_name, nodes = {}, {}
    for no, _, v in hm.fields(module):
        if no != 3:
            continue
        cid, instrs = 0, []             # proto3 leaves a zero id out
        for cno, _, cv in hm.fields(v):
            if cno == 5:
                cid = cv
            elif cno == 2:
                node = {"name": "", "opcode": "", "id": 0, "operands": [], "calls": [],
                        "target": "", "index": 0}      # proto3 leaves a zero tuple_index out
                for ino, iwt, iv in hm.fields(cv):
                    if ino == 1:
                        node["name"] = hm._text(iv)
                    elif ino == 2:
                        node["opcode"] = hm._text(iv)
                    elif ino == 13:
                        node["index"] = iv
                    elif ino == 28:
                        node["target"] = hm._text(iv)
                    elif ino == 35:
                        node["id"] = iv
                    elif ino == 36:
                        node["operands"].extend(hm._ints(iwt, iv))
                    elif ino == 38:
                        node["calls"].extend(hm._ints(iwt, iv))
                instrs.append(node)
        nodes[cid] = {}
        for node in instrs:
            node["comp"] = cid
            nodes[cid][node["id"]] = node
            by_name[node["name"]] = node
    users = {cid: collections.defaultdict(list) for cid in nodes}
    for cid, comp in nodes.items():
        for node in comp.values():
            for op in node["operands"]:
                if op not in comp:
                    raise Unreadable(f"{node['name']}: no instruction has operand id {op}")
                users[cid][op].append(node)
    return {"by_name": by_name, "nodes": nodes, "users": users, "_dots": {}}


def holds_matmul(graph: dict, node: dict) -> bool:
    """The op is a ``dot`` or a ``convolution``, or a computation it calls
    (a fusion's, followed through nested calls) holds one."""
    if node["opcode"] in MATMULS:
        return True
    memo = graph["_dots"]

    def inside(cid, seen):
        if cid not in memo:
            memo[cid] = any(
                n["opcode"] in MATMULS or any(inside(c, seen | {c}) for c in n["calls"] if c not in seen)
                for n in graph["nodes"].get(cid, {}).values()
            )
        return memo[cid]

    return any(inside(c, frozenset([c])) for c in node["calls"])


def adopt(graph: dict, names: dict, node: dict) -> tuple[str, str]:
    """(owner, "producer" | "consumer") of an instruction without a scope:
    the nearest producer with one, operands back through nameless
    instructions; where every such walk ends in a parameter, a constant or a
    loop's tuple element, the nearest consumer with one.  ("", "") where
    neither walk meets a name."""
    comp, users = graph["nodes"][node["comp"]], graph["users"][node["comp"]]

    def back(n):
        if n["opcode"] == "get-tuple-element":
            src = comp[n["operands"][0]]
            if src["opcode"] == "tuple" and n["index"] < len(src["operands"]):
                return [comp[src["operands"][n["index"]]]]
            return [] if src["opcode"] in SOURCES + CONTAINERS else [src]
        return [comp[i] for i in n["operands"]]

    for step, how in ((back, "producer"), (lambda n: users.get(n["id"], []), "consumer")):
        seen, frontier = {node["id"]}, [node]
        while frontier:
            reached = []
            for n in frontier:
                for m in step(n):
                    if m["id"] in seen:
                        continue
                    seen.add(m["id"])
                    if m["opcode"] in SOURCES:
                        continue
                    name = names.get(m["name"], "")
                    if hm.has_scope(name):
                        return name, how
                    if m["opcode"] not in CONTAINERS:
                        reached.append(m)
            frontier = reached
    return "", ""


# -- a path's layer, kind and pass ---------------------------------------------------


def _first(owner: str) -> str:
    """XLA joins the paths of ops it merged with ``;``: the first stands."""
    return owner.split(";", 1)[0]


def place(owner: str) -> tuple[str, str]:
    """(layer, kind) of a scope path."""
    parts = [p for p in hm.scope_parts(_first(owner))
             if "." not in p and p not in ("checkpoint", REMAT)]
    for i, p in enumerate(parts):
        if LAYER.match(p):
            return p, parts[i + 1] if i + 1 < len(parts) else "block"
    while len(parts) > 1 and parts[0] == "backbone":
        parts = parts[1:]
    return "", parts[0] if parts else ""


def pass_of(owner: str) -> str:
    parts = _first(owner).split("/")
    if not any("transpose(" in p for p in parts):
        return "fwd"
    for p in parts:
        if LAYER.match(p):
            return "bwd"
        if p == REMAT:
            # the block's recomputed forward, if a layer follows at all
            return "remat" if any(LAYER.match(q) for q in parts) else "bwd"
    return "bwd"


def part_of(graph: dict, node: dict, own: str) -> str:
    if not hm.has_scope(own):
        return "copy"
    if node["opcode"] == "custom-call" and node["target"] == PALLAS_TARGET:
        return "kernel"
    parts = hm.scope_parts(own)
    if "scan" in parts or "attn" in parts or any(
            a == "moe" and b == "experts" for a, b in zip(parts, parts[1:])):
        return "core"
    return "dense" if holds_matmul(graph, node) else "glue"


# -- the rows ------------------------------------------------------------------------


def _proto_of_reading(reading: dict):
    """The step program's Hlo Proto bytes from the reading's trace file, as
    ``hlo_module.module_of_reading`` chose it."""
    if hm.module_of_reading(reading) is None or "xplane_path" not in reading:
        raise Unreadable("the trace holds no Hlo Proto of the step program")
    ran = {m[0] for m in reading.get("modules", []) if reading.get("program_name", "") in m[0]}
    for pno, _, entry in hm._plane(reading["xplane_path"], "/host:metadata"):
        if pno != 4:
            continue
        ename, proto = "", None
        for mno, _, mv in hm.fields(hm._map_value(entry)):
            if mno == 2:
                ename = hm._text(mv)
            elif mno == 5:
                proto = next((sv for sno, _, sv in hm.fields(mv) if sno == 6), proto)
        if proto is not None and ename in ran:
            return proto
    raise Unreadable("no Hlo Proto under the name the step ran as")


def split_leaves(ops, graph: dict):
    """(leaf events, loop-overhead events): a ``while``, ``conditional`` or
    ``call`` inside which other events start is dropped for those; the
    stretches of it that no leaf covers come back as events under its name,
    each given to the innermost such op round it."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    starts = [e[1] for e in ops]
    leaves, containers = [], []
    for i, e in enumerate(ops):
        node = graph["by_name"].get(e[0])
        if node is None:
            raise Unreadable(f"no instruction of the step's HLO is named {e[0]!r}")
        inner = bisect.bisect_left(starts, e[1] + e[2], i + 1) - (i + 1)
        (containers if node["opcode"] in CONTAINERS and inner else leaves).append(e)
    if not containers:
        return leaves, []
    lo, hi = ops[0][1], max(e[1] + e[2] for e in ops)
    cuts = sorted({c[1] for c in containers} | {c[1] + c[2] for c in containers})
    pieces = []                                   # idle stretches of the leaves, cut at every container's ends
    for gs, gd in tr.gaps(leaves, lo, hi):
        at = gs
        for cut in cuts[bisect.bisect_right(cuts, gs):bisect.bisect_left(cuts, gs + gd)]:
            pieces.append((at, cut - at))
            at = cut
        pieces.append((at, gs + gd - at))
    piece_starts = [s for s, _ in pieces]
    taken = [False] * len(pieces)
    overhead = []
    for c in sorted(containers, key=lambda e: -e[1]):            # innermost first
        a = bisect.bisect_left(piece_starts, c[1])
        b = bisect.bisect_left(piece_starts, c[1] + c[2])
        for k in range(a, b):
            if not taken[k] and pieces[k][1] > 0:
                taken[k] = True
                overhead.append((c[0], pieces[k][0], pieces[k][1], c[3]))
    return leaves, overhead


def _classify(reading: dict) -> dict:
    graph = parse_graph(_proto_of_reading(reading))
    names = hm.names_of_reading(reading)
    memo: dict = {}

    def row(event, loop=False):
        nm, s, d, _ = event
        if nm not in memo:
            node = graph["by_name"][nm]
            own = names.get(nm, "")
            part = part_of(graph, node, own)
            owner, how = (own, "") if part != "copy" else adopt(graph, names, node)
            memo[nm] = (owner, *place(owner), part, pass_of(owner), how)
        owner, layer, kind, part, pass_, how = memo[nm]
        if loop and part == "dense":
            part = "glue"               # a loop's own overhead is no matmul, whatever its body holds
        return Row(nm, s, d, owner, layer, kind, part, pass_, how)

    leaves, overhead = split_leaves(reading["ops"], graph)
    rows = [row(e) for e in leaves] + [row(e, loop=True) for e in overhead]
    rows.sort(key=lambda r: r.start)
    busy = tr.union_ns([(r.start, r.dur) for r in rows])
    return {
        "rows": rows,
        "busy_ns": busy,
        # what rows overlap one another: an async pair's ``-done`` beside compute
        "overlap_ns": sum(r.dur for r in rows) - busy,
        "loop_overhead_ns": sum(e[2] for e in overhead),
    }


def classify(reading: dict):
    """The rows of the reading's analysed window (``reading["ops"]``), cached
    under ``reading["step_parts"]``; None where the trace cannot carry them
    (no ``Hlo Proto``, an operand id or an event's name the HLO lacks: the
    cache's ``error`` says which)."""
    if KEY not in reading:
        try:
            reading[KEY] = _classify(reading)
        except (Unreadable, ValueError, IndexError) as e:   # the last two: bytes `fields` cannot read
            reading[KEY] = {"rows": None, "error": str(e)}
    return reading[KEY]["rows"]


def union_ms_per_step(reading: dict, pick):
    """Device ms a step of the rows ``pick(row)`` takes (the union of their
    intervals); None where there are no rows, none is picked, or no step."""
    rows = classify(reading)
    if not rows or not reading.get("steps_traced"):
        return None
    iv = [(r.start, r.dur) for r in rows if pick(r)]
    if not iv:
        return None
    return tr.union_ns(iv) / 1e6 / reading["steps_traced"]


def is_mixer_proj(r: Row) -> bool:
    """A scan mixer's op outside its scan and outside XLA's copies."""
    return r.kind in SCAN_MIXERS and r.part in ("dense", "glue")


# The rows each ``<name>.train`` metric takes: ONE definition for the benchmark's
# readers (``metrics/``) and the operator's totals (``table``).
PICKS = {
    "mixer_proj_ms": is_mixer_proj,
    "mixer_glue_ms": lambda r: is_mixer_proj(r) and r.part == "glue",
    "remat_ms": lambda r: r.pass_ == "remat",
    "xla_copy_ms": lambda r: r.part == "copy",
}


def total(reading: dict, name: str):
    """The reading's value of one of ``PICKS`` (ms a step) or of
    ``unowned_share`` (% of the rows' busy time in rows without an owner);
    None where ``classify`` gives no rows, or a pick none."""
    if name != "unowned_share":
        return union_ms_per_step(reading, PICKS[name])
    rows = classify(reading)
    if not rows:
        return None
    nobody = tr.union_ns([(r.start, r.dur) for r in rows if not r.owner])
    return 100.0 * nobody / reading[KEY]["busy_ns"]


def metric(reading: dict, name: str):
    """What ``metrics/<name>.train.py`` reports: ``total`` on a run whose
    configuration has a decoder, None on any other (the first cell)."""
    if "decoder" not in reading["config"].get("reference", {}):
        return None
    return total(reading, name)


def table(reading):
    """The operator's and PERF.md's table of one reading, ms a step: by pass,
    by part, by kind x part x pass, the same by layer (``l6/kda glue bwd``),
    the ``copy`` rows by (owner, how adopted, pass), the metrics' ``totals``
    and the sums that hold it all to the trace.  None where ``classify``
    gives none."""
    rows = classify(reading) if reading else None
    steps = reading.get("steps_traced") if reading else None
    if not rows or not steps:
        return None
    passes, parts, kinds, layers, copies = (collections.defaultdict(list) for _ in range(5))
    for r in rows:
        at = (r.start, r.dur)
        where = "/".join(p for p in (r.layer, r.kind) if p) or "(nobody)"
        passes[r.pass_].append(at)
        parts[r.part].append(at)
        kinds[(r.kind or "(nobody)", r.part, r.pass_)].append(at)
        layers[(where, r.part, r.pass_)].append(at)
        if r.part == "copy":
            copies[(where, r.adopted or "none", r.pass_)].append(at)
    ms = lambda iv: tr.union_ns(iv) / 1e6 / steps
    ranked = lambda groups: sorted(([*k, ms(iv)] for k, iv in groups.items()), key=lambda g: -g[-1])
    info = reading[KEY]
    return {
        "steps": steps,
        "busy_ms": info["busy_ns"] / 1e6 / steps,
        "overlap_ms": info["overlap_ns"] / 1e6 / steps,
        "loop_overhead_ms": info["loop_overhead_ns"] / 1e6 / steps,
        "by_pass": {k: ms(iv) for k, iv in sorted(passes.items())},
        "by_part": {k: ms(iv) for k, iv in sorted(parts.items())},
        "by_kind_part_pass": ranked(kinds),
        "by_layer_part_pass": ranked(layers),
        "copy_by_owner": ranked(copies),
        "totals": {name: total(reading, name) for name in (*PICKS, "unowned_share")},
    }


def reading_of_xplane(path: str, program: str = "") -> dict:
    """A reading of a trace file alone, for an operator's profile (no harness,
    no barriers): the window is the whole runs of ``program`` the file holds
    (by default the program that took most device time) on its busiest chip,
    with the keys ``readers.prepare`` would give and the file's own HLO.  None
    where the file holds no chip's plane or no run of the program (a profile
    taken on the CPU)."""
    trace = tr.load(path)
    total = collections.Counter()
    for dev in trace["devices"].values():
        for nm, _, d, _ in dev.get("XLA Modules", []):
            total[nm.split("(", 1)[0]] += d
    program = program or (total.most_common(1)[0][0] if total else "")
    best = None
    for dev in trace["devices"].values():
        runs = [m for m in dev.get("XLA Modules", []) if program and program in m[0]]
        if not runs:
            continue
        lo, hi = min(m[1] for m in runs), max(m[1] + m[2] for m in runs)
        ops = [e for e in dev.get("XLA Ops", []) if e[1] >= lo and e[1] + e[2] <= hi]
        busy = tr.busy_ns(ops)
        if best is None or busy > best["busiest_busy_s"] * 1e9:
            best = dict(ops=ops, modules=runs, lo=lo, hi=hi, window_s=(hi - lo) / 1e9,
                        busy_s=busy / 1e9, busiest_busy_s=busy / 1e9, steps_traced=len(runs))
    if best is None:
        return None
    mods = hm.modules_of_xplane(path)
    ran = {m[0] for m in best["modules"]}
    return dict(best, trace=trace, program_name=program, counters={}, config={},
                chips=len(trace["devices"]), xplane_path=path,
                hlo_module=next((mods[n] for n in mods if n in ran), None))
