"""Device ms a step under the expert layers' scope (``backbone/l<k>/moe``:
router, dispatch, the held experts, combine and the shared expert; forward,
recomputed forward and backward)."""

from perfbench.ling_readers import scoped_ms


def read(reading):
    return scoped_ms(reading, "moe")
