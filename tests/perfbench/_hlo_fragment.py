"""A protobuf writer for hand-made trace files: an ``Hlo Proto`` whose
instructions carry ids and operand ids (what ``perfbench/step_parts.py`` reads
beside ``hlo_module.py``), and an ``.xplane.pb`` that holds it in its
``/host:metadata`` plane and, when asked, a chip's plane with ``XLA Modules``
and ``XLA Ops`` lines that JAX's own reader loads."""

from __future__ import annotations

import os


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(no: int, value) -> bytes:
    """One field: ints as varints, bytes/str length-delimited."""
    if isinstance(value, int):
        return varint(no << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(no << 3 | 2) + varint(len(value)) + value


def instr(name, opcode, id, operands=(), op_name="", calls=(), target="", index=None) -> bytes:
    """An HloInstructionProto: name 1, opcode 2, metadata.op_name 7.2,
    tuple_index 13, custom_call_target 28, id 35, operand_ids 36 (packed),
    called_computation_ids 38."""
    body = field(1, name) + field(2, opcode)
    if op_name:
        body += field(7, field(2, op_name))
    if index:
        body += field(13, index)          # proto3: a zero index is left out
    if target:
        body += field(28, target)
    body += field(35, id)
    if operands:
        body += field(36, b"".join(varint(o) for o in operands))
    return body + b"".join(field(38, c) for c in calls)


def module(name: str, computations) -> bytes:
    """An HloProto; computations: [(id, name, [instruction bytes])]."""
    body = field(1, name)
    for cid, cname, instrs in computations:
        body += field(3, field(1, cname) + b"".join(field(2, i) for i in instrs) + field(5, cid))
    return field(1, body)


def _device_plane(modules, ops) -> bytes:
    """``/device:TPU:0`` with two lines of (name, start_ns, dur_ns) events."""
    ids: dict = {}
    for name, *_ in list(modules) + list(ops):
        ids.setdefault(name, len(ids) + 1)
    plane = field(1, 2) + field(2, "/device:TPU:0")
    for line_id, (line_name, events) in enumerate((("XLA Modules", modules), ("XLA Ops", ops)), 1):
        line = field(1, line_id) + field(2, line_name) + field(3, 0)
        for name, start, dur in events:
            line += field(4, field(1, ids[name]) + field(2, int(start * 1000)) + field(3, int(dur * 1000)))
        plane += field(3, line)
    for name, mid in ids.items():
        plane += field(4, field(1, mid) + field(2, field(1, mid) + field(2, name)))
    return plane


def xplane(tmp: str, hlo_proto=None, module_event="jit_step(7)", modules=(), ops=(),
           sub=("cell", "plugins", "profile", "run")) -> str:
    """Writes ``<tmp>/<sub>/host.xplane.pb``: the metadata plane with the
    program (left out when ``hlo_proto`` is None) and, where ``ops`` are given,
    a chip's plane.  -> the file's path."""
    space = b""
    if hlo_proto is not None:
        meta = field(2, "/host:metadata") + field(4, field(1, 1) + field(2, (
            field(1, 1) + field(2, module_event) + field(5, field(1, 1) + field(6, hlo_proto)))))
        space += field(1, meta)
    if ops:
        space += field(1, _device_plane(modules, ops))
    d = os.path.join(tmp, *sub)
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, "host.xplane.pb")
    with open(path, "wb") as f:
        f.write(space)
    return path
