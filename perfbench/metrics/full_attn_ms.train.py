"""Device ms a step of the attention over the whole causal prefix: the
full-attention layer's and the cross layers' (``full/attn``, ``xattn/attn``:
both maps; forward, recomputed forward and backward)."""

from perfbench.sambay_readers import either_ms


def read(reading):
    return either_ms(reading, ("full", "attn"), ("xattn", "attn"))
