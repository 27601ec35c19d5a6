"""Device ms a step under the KDA mixers' scope (``backbone/l<k>/kda``:
projections, short convs, the chunked scan; forward, recomputed forward and
backward)."""

from perfbench.ling_readers import scoped_ms


def read(reading):
    return scoped_ms(reading, "kda")
