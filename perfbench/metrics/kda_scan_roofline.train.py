"""The KDA scan's share of its roofline: the least time the chip could take
for the recurrence's own work (ling_need.py::kda_scan_need, forward and
backward) over the device time under ``kda/scan``."""

from perfbench.ling_need import kda_scan_need
from perfbench.ling_readers import has_decoder, images_per_chip, share


def read(reading):
    if not has_decoder(reading):
        return None
    need = kda_scan_need(reading["config"]["reference"], images_per_chip(reading))
    return share(reading, need, "kda", "scan")
