"""Device ms a step of the blocked causal attention (``mla/attn``; forward,
recomputed forward and backward)."""

from perfbench.ling_readers import scoped_ms


def read(reading):
    return scoped_ms(reading, "mla", "attn")
