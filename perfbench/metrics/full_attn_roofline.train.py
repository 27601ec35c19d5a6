"""The full and cross attention's share of their roofline: the least time for
the causal triangle of both maps in those layers, k and v moved once
(sambay_need.py::full_attn_need, forward and backward) over the device time
under ``full/attn`` and ``xattn/attn``."""

from perfbench.ling_readers import images_per_chip
from perfbench.readers import roofline_share
from perfbench.sambay_need import full_attn_need
from perfbench.sambay_readers import either_ms, has_sambay


def read(reading):
    if not has_sambay(reading):
        return None
    need = full_attn_need(reading["config"]["reference"], images_per_chip(reading))
    return roofline_share(reading, need, either_ms(reading, ("full", "attn"), ("xattn", "attn")))
