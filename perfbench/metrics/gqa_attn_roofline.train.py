"""The grouped-query attention's share of its roofline: the least time for the
causal half of the scores with K and V moved once a key head
(ssm_need.py::gqa_attn_need, forward and backward) over the device time under
``gqa/attn``."""

from perfbench.ling_readers import images_per_chip, share
from perfbench.ssm_need import gqa_attn_need
from perfbench.ssm_readers import has_ssm


def read(reading):
    if not has_ssm(reading):
        return None
    need = gqa_attn_need(reading["config"]["reference"], images_per_chip(reading))
    return share(reading, need, "gqa", "attn")
