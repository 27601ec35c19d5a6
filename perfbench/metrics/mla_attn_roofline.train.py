"""The causal attention's share of its roofline: the least time for the
causal half of the scores (ling_need.py::mla_attn_need, forward and backward)
over the device time under ``mla/attn``."""

from perfbench.ling_need import mla_attn_need
from perfbench.ling_readers import has_decoder, images_per_chip, share


def read(reading):
    if not has_decoder(reading):
        return None
    need = mla_attn_need(reading["config"]["reference"], images_per_chip(reading))
    return share(reading, need, "mla", "attn")
