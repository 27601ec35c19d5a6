"""The state-space scan's share of its roofline: the least time the chip could
take for the recurrence's own work (ssm_need.py::ssm_scan_need, forward and
backward) over the device time under ``ssm/scan``."""

from perfbench.ling_readers import images_per_chip, share
from perfbench.ssm_need import ssm_scan_need
from perfbench.ssm_readers import has_ssm


def read(reading):
    if not has_ssm(reading):
        return None
    need = ssm_scan_need(reading["config"]["reference"], images_per_chip(reading))
    return share(reading, need, "ssm", "scan")
