"""Seconds from the start of the process to the compile cache placed: the
program's ``setup.import`` span (the kernel's record of the process start to
the first ``configure_cache``: the interpreter, ``import jax`` and what the
entry point did first - the harness starts the backend there) and
``setup.backend`` (the backend's start inside ``configure_cache``, where
nobody had touched it).  The package's own import chain (flax, optax, orbax)
comes later in every entry point and is not in it."""

from perfbench import program_spans


def read(reading):
    rows = program_spans.spans(subsystem="process", prefix="setup.")
    if not rows:
        return None
    mine = program_spans.before_window(reading, rows)
    return None if mine is None else sum(d for _, d in mine) / 1e9
