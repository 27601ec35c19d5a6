"""Milliseconds per step inside all-reduce (or any collective) ops during
which no other op runs on that chip (device trace, busiest chip)."""

from perfbench import trace_reduce as tr


def read(reading):
    if not any(tr.COLLECTIVE.search(n) for n, *_ in reading["ops"]):
        return None
    if not reading["steps_traced"]:
        return None
    return tr.exposed_collective_ns(reading["ops"]) / 1e6 / reading["steps_traced"]
