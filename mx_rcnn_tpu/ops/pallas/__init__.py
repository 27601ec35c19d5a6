"""Pallas TPU kernels — the performance path for the detection hot ops.

Each kernel has a pure-XLA reference implementation in :mod:`mx_rcnn_tpu.ops`
(the correctness oracle, SURVEY.md §5: Pallas kernels validated vs XLA
reference impls in tests).  Kernels run in interpret mode on CPU, so the
same tests cover both backends.
"""

from mx_rcnn_tpu.ops.pallas.roi_align import (
    multilevel_roi_align_fast,
    multilevel_roi_align_pallas,
)

__all__ = [
    "multilevel_roi_align_fast",
    "multilevel_roi_align_pallas",
]
