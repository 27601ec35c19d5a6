"""ROIAlign backward's share of its roofline (see the forward's reader): the
ops under ``transpose(...roi_align...)``."""

from perfbench.readers import named_ms_per_step, roofline_share, scoped_ms_per_step
from perfbench.roi_need import need_of


def read(reading):
    # The Pallas kernel runs under its own name; an XLA backward only has the scope.
    ms = named_ms_per_step(reading, r"roi_align.*bwd") or scoped_ms_per_step(
        reading, "roi_align", wrapped=True
    )
    return roofline_share(reading, need_of(reading, backward=True), ms)
