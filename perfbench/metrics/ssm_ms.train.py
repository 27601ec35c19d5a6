"""Device ms a step under the state-space mixers' scope (``backbone/l<k>/ssm``:
projections, the short conv, the chunked scan, the gated norm; forward,
recomputed forward and backward)."""

from perfbench.ling_readers import scoped_ms


def read(reading):
    return scoped_ms(reading, "ssm")
