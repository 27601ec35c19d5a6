"""Pallas TPU kernels — the performance path for the detection hot ops.

Each kernel has a pure-XLA reference implementation in :mod:`mx_rcnn_tpu.ops`
(the correctness oracle, SURVEY.md §5: Pallas kernels validated vs XLA
reference impls in tests).  Kernels run in interpret mode on CPU, so the
same tests cover both backends.

- :mod:`.roi_align` — multilevel ROIAlign, forward and backward (the FPN
  presets; oracle ``ops/roi_align.py``).
- :mod:`.kda` — the chunk-local part of the KDA scan, ``kda_intra_fwd`` /
  ``kda_intra_bwd`` (taken by ``ops/kda.py::kda_chunked`` on a TPU; oracle
  ``ops/kda.py::_intra``).
- :mod:`.attention` — causal softmax attention with its scores in VMEM,
  ``flash_attention_fwd`` / ``flash_attention_bwd`` (taken by
  ``ops/attention.py::causal_attention`` on a TPU; oracles
  ``causal_attention_dense`` and the blocked XLA form).
- :mod:`.selective_scan` — the Mamba-1 selective scan with its state in VMEM,
  ``selective_scan_fwd`` / ``selective_scan_bwd`` (taken by
  ``ops/selective_scan.py::selective_scan_chunked`` on a TPU; oracles
  ``selective_scan_recurrent``, token by token, and the chunked XLA form,
  itself held to that recurrence).
- :mod:`.ssd` — the Mamba-2 chunked scan with its decay matrices and state in
  VMEM, ``ssd_fwd`` / ``ssd_bwd`` (taken by ``ops/ssd.py::ssd_chunked`` on a
  TPU; oracles ``ssd_recurrent``, token by token, and the chunked XLA form).
"""

from mx_rcnn_tpu.ops.pallas.roi_align import (
    multilevel_roi_align_fast,
    multilevel_roi_align_pallas,
)

__all__ = [
    "multilevel_roi_align_fast",
    "multilevel_roi_align_pallas",
]
