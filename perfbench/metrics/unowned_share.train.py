"""% of the device's busy time in rows of ``step_parts.classify`` that have no
owner after adoption: no scope of their own, no producer and no consumer with
one."""

from perfbench.step_parts import metric


def read(reading):
    return metric(reading, "unowned_share")
