"""Milliseconds per step the consumer's thread spent inside the feed's
``device_put`` of a later batch (``feed.put`` spans of
``parallel/prefetch.py::device_prefetch``), over the traced window: host
time between two dispatches that the prefetch does not hide."""

from perfbench import program_spans


def read(reading):
    rows = program_spans.spans(subsystem="train", prefix="feed.put")
    if not rows or not reading["steps_traced"]:
        return None
    inside = program_spans.in_window(reading, rows)
    if not inside:
        return None
    return sum(d for _, _, d, _ in inside) / 1e6 / reading["steps_traced"]
