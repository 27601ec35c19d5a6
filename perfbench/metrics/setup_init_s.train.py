"""Seconds inside ``train/loop.py::build_all``'s four phases before the
window opened: the ``setup.init_state``, ``setup.optimizer``, ``setup.plan``
and ``setup.step`` spans of the program's own timeline (host clock).  Their
``programs`` / ``compile_s`` fields say how much of it was compiling."""

from perfbench import program_spans


def read(reading):
    rows = program_spans.spans(subsystem="train", prefix="setup.")
    if not rows:
        return None
    mine = program_spans.before_window(reading, rows)
    return None if mine is None else sum(d for _, d in mine) / 1e9
