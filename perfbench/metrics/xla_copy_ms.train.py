"""Device ms a step in ops that carry no scope of their own after
``hlo_module.resolve`` (part ``copy`` of ``step_parts.classify``): XLA's layout
copies, async copy and slice pairs, zero broadcasts and the fusions it made of
them, whoever they were adopted by, or nobody."""

from perfbench.step_parts import metric


def read(reading):
    return metric(reading, "xla_copy_ms")
