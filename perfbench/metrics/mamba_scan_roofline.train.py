"""The selective scan's share of its roofline: the least time the chip could
take for the recurrence's own work (sambay_need.py::mamba_scan_need, forward
and backward) over the device time under ``mamba/scan``."""

from perfbench.ling_readers import images_per_chip, share
from perfbench.sambay_need import mamba_scan_need
from perfbench.sambay_readers import has_sambay


def read(reading):
    if not has_sambay(reading):
        return None
    need = mamba_scan_need(reading["config"]["reference"], images_per_chip(reading))
    return share(reading, need, "mamba", "scan")
