"""The program that ran, read from the trace it left: the profiler stores the
optimized HLO of every module it saw in the ``/host:metadata`` plane of the
``.xplane.pb`` (one ``Hlo Proto`` stat per module, named as the module's
events are: ``jit_step(<id>)``).  From it: every instruction's ``op_name``
(the ``jax.named_scope`` path the trace's events lack), the computation each
instruction sits in, and the computations it calls - enough to give a fusion
that XLA made without metadata the name of what it holds, and to tell one
loop's body from another's.

Protobuf is read on the wire (field numbers of xplane.proto and hlo.proto),
with nothing but the standard library: the harness imports JAX and nothing
else.  Looked at by hand first (PERF.md section 3, PR 25).
"""

from __future__ import annotations

import collections
import glob
import os
import re

WRAPPERS = re.compile(r"^(?:transpose\(|jvp\(|vmap\(|jit\()+|\)+$")


def fields(buf):
    """(field number, wire type, value) of one message's bytes: varints as
    ints, length-delimited fields as memoryviews."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        no, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield no, wt, v


def _varint(buf, i):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def _ints(wt, v):
    """A repeated integer field's values, packed or not."""
    if wt == 0:
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def parse_module(hlo_proto) -> dict:
    """HloProto bytes -> {"name", "computations": {computation name:
    [instruction]}}; an instruction is {"name", "opcode", "op_name",
    "calls": [computation names]}."""
    module = next((v for no, _, v in fields(hlo_proto) if no == 1), None)
    if module is None:
        return {"name": "", "computations": {}}
    name, comps, by_id = "", {}, {}
    for no, _, v in fields(module):
        if no == 1:
            name = _text(v)
        elif no == 3:
            cname, cid, instrs = "", None, []
            for cno, _, cv in fields(v):
                if cno == 1:
                    cname = _text(cv)
                elif cno == 5:
                    cid = cv
                elif cno == 2:
                    ins = {"name": "", "opcode": "", "op_name": "", "calls": []}
                    for ino, iwt, iv in fields(cv):
                        if ino == 1:
                            ins["name"] = _text(iv)
                        elif ino == 2:
                            ins["opcode"] = _text(iv)
                        elif ino == 7:
                            ins["op_name"] = next(
                                (_text(mv) for mno, _, mv in fields(iv) if mno == 2), ""
                            )
                        elif ino == 38:
                            ins["calls"].extend(_ints(iwt, iv))
                    instrs.append(ins)
            comps[cname] = instrs
            by_id[cid] = cname
    for instrs in comps.values():
        for ins in instrs:
            ins["calls"] = [by_id[c] for c in ins["calls"] if c in by_id]
    return {"name": name, "computations": comps}


def _plane(path: str, name: str) -> list:
    """The fields of the plane called ``name`` in a trace file; [] where the
    file has none."""
    with open(path, "rb") as f:
        space = f.read()
    for no, _, plane in fields(space):
        if no == 1:
            parts = list(fields(plane))
            if any(pno == 2 and _text(pv) == name for pno, _, pv in parts):
                return parts
    return []


def _map_value(entry):
    """The value of a protobuf map entry (key = 1, value = 2)."""
    return next((v for no, _, v in fields(entry) if no == 2), b"")


def modules_of_xplane(path: str) -> dict:
    """{module event name: parsed module} of a trace file; {} where the
    trace holds no ``/host:metadata`` plane."""
    out = {}
    for pno, _, entry in _plane(path, "/host:metadata"):
        if pno != 4:  # event_metadata: map<int64, XEventMetadata>
            continue
        ename, proto = "", None
        for mno, _, mv in fields(_map_value(entry)):
            if mno == 2:
                ename = _text(mv)
            elif mno == 5:  # stats: the one bytes_value is the HloProto
                proto = next((sv for sno, _, sv in fields(mv) if sno == 6), proto)
        if proto is not None:
            out[ename] = parse_module(proto)
    return out


def profile_start_ns(path: str):
    """``profile_start_time`` of a trace file (Unix nanoseconds, from its
    ``Task Environment`` plane: when the profiler session began); None where
    the file does not say.  The device planes count from about there."""
    parts = _plane(path, "Task Environment")
    ids = {}
    for pno, _, entry in parts:
        if pno == 5:  # stat_metadata: map<int64, XStatMetadata>
            meta = {mno: mv for mno, _, mv in fields(_map_value(entry))}
            ids[meta.get(1)] = _text(meta.get(2, b""))
    for pno, _, stat in parts:
        if pno == 6:
            st = {sno: sv for sno, _, sv in fields(stat)}
            if ids.get(st.get(1)) == "profile_start_time":
                return st.get(3, st.get(4))
    return None


def scope_parts(op_name: str) -> list:
    """The components of an op's path that name where it is, outermost
    first: the last component left out where it is the primitive itself (no
    parenthesis in it), ``jit(...)`` wrappers dropped, and the wrappers of a
    transformation (``jvp(..)``, ``transpose(jvp(..))``, ``vmap(..)``)
    peeled (``jvp()`` holds nothing)."""
    raw = [p for p in op_name.split("/") if p]
    if raw and "(" not in raw[-1]:
        raw = raw[:-1]
    parts = []
    for p in raw:
        if p.startswith("jit("):
            continue
        inner = WRAPPERS.sub("", p)
        if inner:
            parts.append(inner)
    return parts


def has_scope(op_name: str) -> bool:
    """An op has a name when its path holds any component above its own
    primitive: a ``jax.named_scope``, a flax module, or the function jax
    names a control-flow body by."""
    return bool(scope_parts(op_name))


def resolve(module: dict) -> dict:
    """{instruction name: op_name} over every computation of a module.  An
    instruction with a scope of its own keeps it; one without (a fusion XLA
    merged or made, a layout copy it wrapped) takes the ``op_name`` that
    most of the instructions of the computations it calls carry, followed
    through nested calls."""
    comps = module["computations"]
    memo: dict = {}

    def inside(cname, seen):
        """Counter of the scoped op_names under a computation, by scope."""
        if cname in memo:
            return memo[cname]
        votes = collections.Counter()
        for ins in comps.get(cname, []):
            if has_scope(ins["op_name"]):
                votes[ins["op_name"]] += 1
            for c in ins["calls"]:
                if c not in seen:
                    votes.update(inside(c, seen | {c}))
        memo[cname] = votes
        return votes

    out = {}
    for instrs in comps.values():
        for ins in instrs:
            own = ins["op_name"]
            if not has_scope(own) and ins["calls"]:
                votes = collections.Counter()
                for c in ins["calls"]:
                    votes.update(inside(c, frozenset([c])))
                if votes:
                    by_scope = collections.Counter()
                    for name, n in votes.items():
                        by_scope["/".join(scope_parts(name))] += n
                    best = by_scope.most_common(1)[0][0]
                    own = next(n for n in sorted(votes) if "/".join(scope_parts(n)) == best)
            out[ins["name"]] = own
    return out


def computation_of(module: dict) -> dict:
    """{instruction name: name of the computation it sits in}."""
    return {
        ins["name"]: cname
        for cname, instrs in module["computations"].items() for ins in instrs
    }


def loop_bodies(module: dict) -> set:
    """Names of the computations some ``while`` runs as its body (hlo.proto
    lists a while's body first, its condition second)."""
    return {
        ins["calls"][0]
        for instrs in module["computations"].values() for ins in instrs
        if ins["opcode"] == "while" and ins["calls"]
    }


def module_of_reading(reading: dict):
    """The parsed module of the step program of a traced run, from the newest
    trace file under ``.perfbench_trace/`` that names it; None where no file
    does (the readers then fall back on the scopes the harness joined).
    Cached in the reading."""
    if "hlo_module" in reading:
        return reading["hlo_module"]
    from perfbench.spec import REPO_ROOT

    ran = {m[0] for m in reading.get("modules", []) if reading.get("program_name", "") in m[0]}
    root = reading.get("trace_root", os.path.join(REPO_ROOT, ".perfbench_trace"))
    files = glob.glob(os.path.join(root, "*", "plugins", "profile", "*", "*.xplane.pb"))
    found = None
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        mods = modules_of_xplane(path)
        name = next((n for n in mods if n in ran), None)
        if name is not None:
            found = mods[name]
            reading["xplane_path"] = path
            break
    reading["hlo_module"] = found
    return found


def names_of_reading(reading: dict) -> dict:
    """{instruction name: resolved op_name} for a traced run: the trace's own
    HLO where it has it, else the harness's join (``reading["scopes"]``)."""
    if "op_names" not in reading:
        module = module_of_reading(reading)
        names = dict(reading.get("scopes") or {})
        if module is not None:
            names.update({k: v for k, v in resolve(module).items() if v})
        reading["op_names"] = names
    return reading["op_names"]
