"""The scan mixers' projections' share of their roofline: the least time the
chip could take for the projection matmuls (mixer_need.py::mixer_proj_need,
forward and backward, nothing recomputed) over ``mixer_proj_ms.train``'s device
time.  Cannot pass 75 % while a block recomputes its forward."""

from perfbench.ling_readers import images_per_chip
from perfbench.mixer_need import mixer_proj_need
from perfbench.readers import roofline_share
from perfbench.step_parts import metric


def read(reading):
    ms = metric(reading, "mixer_proj_ms")
    if ms is None:
        return None
    need = mixer_proj_need(reading["config"]["reference"], images_per_chip(reading))
    return roofline_share(reading, need, ms)
