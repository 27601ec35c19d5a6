"""Device ms a step of the blocked grouped-query attention (``gqa/attn``;
forward, recomputed forward and backward)."""

from perfbench.ling_readers import scoped_ms


def read(reading):
    return scoped_ms(reading, "gqa", "attn")
