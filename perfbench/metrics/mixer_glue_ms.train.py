"""Of ``mixer_proj_ms.train``, the ops that hold no matmul: short
convolutions, gates, norms, casts (part ``glue`` of the scan mixers' rows)."""

from perfbench.step_parts import metric


def read(reading):
    return metric(reading, "mixer_glue_ms")
