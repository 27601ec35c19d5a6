"""ROIAlign forward's share of its roofline: the least time the chip could
take for what the algorithm needs (perfbench/flops.py::roi_align_need) over
the kernel's device time under the ``roi_align`` scope (forward ops)."""

from perfbench.readers import named_ms_per_step, roofline_share, scoped_ms_per_step
from perfbench.roi_need import need_of


def read(reading):
    # The Pallas kernel runs under its own name; an XLA forward only has the scope.
    ms = named_ms_per_step(reading, r"roi_align", exclude=r"bwd") or scoped_ms_per_step(
        reading, "roi_align", wrapped=False
    )
    return roofline_share(reading, need_of(reading, backward=False), ms)
