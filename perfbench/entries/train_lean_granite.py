"""``entries/train_lean.py``'s run for the ``granite4_h_micro`` backbone: the
same window, feed, followed steps and ``compare.py`` numbers, with what that
file hard-codes for the first decoder family swapped, as
``train_lean_ssm.py`` does for the second:

- the plain backbone (``reference/backbone_granite4_h_micro.py``) in the
  faults, two of them this family's own: ``no_carry`` (the recurrence started
  from zero at every chunk: ``train_lean_ssm.py``'s fault, in the Mamba-2
  mixer this backbone borrows from that family) and ``no_residual_multiplier``
  (every sub-layer's output added at 1.0, not at the muP
  ``residual_multiplier``);
- the step's needed FLOPs (``granite_need.py::step_flops``);
- the weights (:func:`granite_ranges`): ``A_log`` and ``dt_bias`` mapped onto
  the Mamba-2 ranges as ``train_lean_ssm.py::ssm_ranges`` does, and the
  patchify kernel scaled by the published ``initializer_range``, before the
  program or the reference sees them (``weights.py`` gains no kind);
- which leaves weight decay skips: ``train_lean_ssm.py::decayed`` (the
  program's ``train/optim.py::NO_DECAY`` for this family's leaves);
- where the reference sums a batch's gradients: on the host
  (:class:`HostSumReference`), so that its per-image program fits the chip.

``train_lean.py`` and ``detector.py`` are accepted files that name their own
classes, backbone and rule, so their ``run``, ``main``, ``side_reading``,
``LeanReference`` and ``decayed`` carry this module's names while this entry
runs and no longer (:func:`_as_this_family`).  Nothing here is routed: the merged-experts
numbers read what ``grad1`` and ``change`` read.

Run as a script it takes the readings the cell's limits are set from:

    python3 perfbench/entries/train_lean_granite.py --workload <cell> --seeds 1,2 \\
        [--sides fp8,no_carry,no_residual_multiplier] [--seconds 2]
"""

from __future__ import annotations

import contextlib
import os
import sys
from unittest import mock

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

import jax
import numpy as np

from perfbench.entries import train_lean as L
from perfbench.entries.train_lean_ssm import _no_carry, decayed, ssm_ranges
from perfbench.reference import backbone_granite4_h_micro as B
from perfbench.reference import backbone_nemotron_twotower as mamba2
from perfbench.reference import detector as D
from perfbench.reference.train_lean import LeanReference


def granite_ranges(dc: dict, weights: dict) -> dict:
    """``ssm_ranges`` (every ``A_log`` and ``dt_bias`` from its uniform draw
    onto the family's ranges), and the patchify kernel's lecun-normal draw
    times ``initializer_range``: the published embedding's std, so that the
    patch tokens after ``embedding_multiplier`` start near the published
    model's scale."""
    out = ssm_ranges(dc, weights)
    for path, value in weights.items():
        if path.endswith("/patchify/kernel"):
            out[path] = (np.asarray(value) * dc["initializer_range"]).astype(np.float32)
    return out


class GraniteTrainCell(L.LeanTrainCell):
    def __init__(self, ctx):
        """``LeanTrainCell.__init__`` with the weights mapped (:func:`granite_ranges`)
        as they are made, before the program or the reference sees them."""
        make, dc = L.W.make_weights, ctx.config["reference"]["decoder"]
        mapped = lambda seed, specs: granite_ranges(dc, jax.device_get(make(seed, specs)))
        with mock.patch.object(L.W, "make_weights", mapped):
            super().__init__(ctx)

    def step_flops(self, counters=None) -> float:
        from perfbench.granite_need import step_flops

        return step_flops(self.ref_run, self.global_batch)


class HostSumReference(LeanReference):
    """``LeanReference`` with the batch's gradient summed on the HOST.  Its
    per-image gradient program needs 7.9 GB of temporaries at this cell's size
    (compiled for a v5e: XLA keeps the nine Mamba-2 layers' recomputed
    ``in_proj`` results alive together in the backward), and beside the
    weights, that image's gradient and a device-side sum (3.1 GB each) it
    does not fit 16 GB.  Each image's gradient goes to the host as it is
    made and is added there in float32, the same sums in the same order."""

    def __init__(self, ref, matmul=None, devices=None):
        super().__init__(ref, matmul=matmul, devices=devices)
        grad = self._grad

        def to_host(*args):
            g, s = grad(*args)
            return jax.device_get(g), s

        self._grad = to_host
        self._add = lambda acc, g: {p: acc[p] + g[p] for p in acc}


_lean_side_reading = L.side_reading     # before :func:`_as_this_family` rebinds the name


def side_reading(cell, kind: str, ref_res: dict) -> dict:
    """``train_lean.side_reading`` with this family's backbone in the two
    faults that reach into it."""
    if kind == "no_carry":                    # the scan's carry between chunks left out
        patch = mock.patch.object(mamba2, "recurrence", _no_carry(cell.ref["decoder"]["chunk_size"]))
    elif kind == "no_residual_multiplier":    # every branch added at 1.0
        patch = mock.patch.object(B, "branch_scale", lambda dc: 1.0)
    else:
        return _lean_side_reading(cell, kind, ref_res)
    with patch:
        return L.numbers_of(cell.reference(), ref_res)


@contextlib.contextmanager
def _as_this_family():
    with mock.patch.multiple(L, LeanTrainCell=GraniteTrainCell, side_reading=side_reading,
                             LeanReference=HostSumReference), \
            mock.patch.object(D, "decayed", decayed):
        yield


def run(ctx) -> dict:
    """One benchmark run of the cell.  -> result fields (run.py)."""
    with _as_this_family():
        return L.run(ctx)


def main(argv=None) -> int:
    with _as_this_family():
        return L.main(argv)


if __name__ == "__main__":
    sys.exit(main())
