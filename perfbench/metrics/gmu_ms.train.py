"""Device ms a step under the Gated Memory Units' scope (``backbone/l<k>/gmu``:
two projections and the gate against the memory; forward, recomputed forward
and backward)."""

from perfbench.ling_readers import scoped_ms


def read(reading):
    return scoped_ms(reading, "gmu")
