"""Seconds spent tracing functions to jaxprs and lowering them to modules
before the window opened and outside ``build_all``'s four phases: the union
of the program's ``jit.trace`` and ``jit.lower`` spans
(``utils/compile_cache.py``'s listener; a lowering may trace, so the two
overlap) that ended in none of the ``setup.*`` spans - above all the step
itself, which no cache spares the process.  Disjoint from
``setup_init_s.train`` and ``setup_compile_s.train`` by construction."""

from perfbench import program_spans
from perfbench import trace_reduce as tr


def read(reading):
    rows = (program_spans.spans(subsystem="jit", prefix="jit.trace") or []) + (
        program_spans.spans(subsystem="jit", prefix="jit.lower") or []
    )
    if not rows:
        return None
    phases = program_spans.spans(subsystem="train", prefix="setup.")
    mine = program_spans.before_window(reading, rows, apart_from=phases)
    return None if mine is None else tr.union_ns(mine) / 1e9
