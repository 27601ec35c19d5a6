"""Device ms a step of the scan mixers (``kda``, ``ssm``, ``mamba``) OUTSIDE
their scans: the rows of ``step_parts.classify`` of those kinds with part
``dense`` or ``glue``, every pass (the copies XLA adds round them are
``xla_copy_ms.train``'s)."""

from perfbench.step_parts import metric


def read(reading):
    return metric(reading, "mixer_proj_ms")
