"""Seconds inside backend compiles before the window opened and outside
``build_all``'s four phases: the program's ``jit.compile`` spans (one per
program built or loaded from the persistent cache, whose retrieval the
seconds include; ``utils/compile_cache.py``'s listener) that ended in none of
the ``setup.*`` spans - the step program itself, the benchmark's weights and
followed steps.  What compiled inside a phase is in ``setup_init_s.train``
already (the span's ``compile_s`` field says how much), so the two add up to
no more than ``setup_s``, cold or warm."""

from perfbench import program_spans


def read(reading):
    rows = program_spans.spans(subsystem="jit", prefix="jit.compile")
    if not rows:
        return None
    phases = program_spans.spans(subsystem="train", prefix="setup.")
    mine = program_spans.before_window(reading, rows, apart_from=phases)
    return None if mine is None else sum(d for _, d in mine) / 1e9
