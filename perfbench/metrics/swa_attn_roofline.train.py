"""The window attention's share of its roofline: the least time for the band's
pairs of both maps (sambay_need.py::swa_attn_need, forward and backward) over
the device time under ``swa/attn``."""

from perfbench.ling_readers import images_per_chip, share
from perfbench.sambay_need import swa_attn_need
from perfbench.sambay_readers import has_sambay


def read(reading):
    if not has_sambay(reading):
        return None
    need = swa_attn_need(reading["config"]["reference"], images_per_chip(reading))
    return share(reading, need, "swa", "attn")
