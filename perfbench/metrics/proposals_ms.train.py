"""Device milliseconds per step under the graph's ``proposals`` scope (top-k
and NMS), from the trace's op metadata."""

from perfbench.readers import scoped_ms_per_step


def read(reading):
    return scoped_ms_per_step(reading, "proposals")
