"""Device ms a step under the expert layers' scope of the ``nemotron_twotower``
backbone (``backbone/l<k>/moe``: router, dispatch, the held two-matrix
experts, combine and the shared expert; forward, recomputed forward and
backward).  ``moe_ms.train`` is the same reading and lists the other decoder
cell."""

from perfbench.ling_readers import scoped_ms


def read(reading):
    return scoped_ms(reading, "moe")
