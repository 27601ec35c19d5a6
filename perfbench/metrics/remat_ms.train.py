"""Device ms a step in the recomputed forward of the blocks'
``jax.checkpoint`` (the rows of ``step_parts.classify`` with pass ``remat``):
the most a checkpoint policy could buy back."""

from perfbench.step_parts import metric


def read(reading):
    return metric(reading, "remat_ms")
