"""Device ms a step of the window layers' attention (``swa/attn``: both maps
of the differential attention; forward, recomputed forward and backward)."""

from perfbench.ling_readers import scoped_ms


def read(reading):
    return scoped_ms(reading, "swa", "attn")
