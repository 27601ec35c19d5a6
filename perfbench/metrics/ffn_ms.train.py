"""Device ms a step under the dense SwiGLU of every ``phi4_mini_flash`` layer
(``backbone/l<k>/ffn``; forward, recomputed forward and backward).  Another
family's dense layer runs under the same scope: None there."""

from perfbench.ling_readers import scoped_ms
from perfbench.sambay_readers import has_sambay


def read(reading):
    return scoped_ms(reading, "ffn") if has_sambay(reading) else None
