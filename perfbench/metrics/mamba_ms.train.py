"""Device ms a step under the Mamba-1 mixers' scope (``backbone/l<k>/mamba``:
projections, the short conv, the selective scan, the gate; forward, recomputed
forward and backward)."""

from perfbench.ling_readers import scoped_ms


def read(reading):
    return scoped_ms(reading, "mamba")
