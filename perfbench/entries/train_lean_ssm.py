"""``entries/train_lean.py``'s run for the ``nemotron_twotower`` backbone: the
same window, feed, followed steps, ``compare.py`` and merged-experts numbers,
with what that file hard-codes for the other decoder family swapped:

- the plain backbone (``reference/backbone_nemotron_twotower.py``) in
  ``reference_slots`` and in the faults, and a fault of this family's own,
  ``no_carry``: the recurrence started from zero at every chunk (what a chunked
  scan computes when the state's carry between chunks is left out);
- the step's needed FLOPs (``ssm_need.py::step_flops``);
- the weights: ``A_log`` and ``dt_bias`` are drawn uniform (``weights.py``
  gains no kind) and mapped here onto the family's ranges (:func:`ssm_ranges`)
  before the program or the reference sees them;
- which leaves weight decay skips: ``reference/detector.py::decayed`` knows
  ``bias`` and ``scale``; this family's recipe (and the program's optimizer,
  ``train/optim.py::NO_DECAY``) skips ``A_log``, ``dt_bias`` and ``D`` too.

``train_lean.py`` and ``detector.py`` are accepted files that name their own
class, backbone and rule, so their ``run``, ``main``, ``side_reading`` and
``decayed`` carry this module's names while this entry runs and no longer
(:func:`_as_this_family`; the weights' mapping while the cell is built).  A
``benchmark`` PR can give them parameters instead (PERF.md section 7).

Run as a script it takes the readings the cell's limits are set from:

    python3 perfbench/entries/train_lean_ssm.py --workload <cell> --seeds 1,2 \\
        [--sides fp8,half_batch,unchanged,no_experts,no_carry] [--seconds 2]
"""

from __future__ import annotations

import contextlib
import math
import os
import sys
from unittest import mock

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.entries import train_lean as L
from perfbench.reference import backbone_nemotron_twotower as B
from perfbench.reference import detector as D

NO_DECAY = ("bias", "scale", "A_log", "dt_bias", "D")


def decayed(path: str) -> bool:
    return path.rsplit("/", 1)[1] not in NO_DECAY


def ssm_ranges(dc: dict, weights: dict) -> dict:
    """The host's weights with every ``A_log`` and ``dt_bias`` mapped from its
    uniform draw u in 0.7..1 onto the family's initial ranges: A = exp(A_log)
    uniform in ``A_range`` (1-16), dt = softplus(dt_bias) log-uniform in
    ``time_step_min``-``time_step_max``."""
    (a_lo, a_hi), lo, hi = dc["A_range"], math.log(dc["time_step_min"]), math.log(dc["time_step_max"])
    out = dict(weights)
    unit = lambda value: (np.asarray(value, np.float64) - 0.7) / 0.3
    for path, value in weights.items():
        if path.endswith("/A_log"):
            out[path] = np.log(a_lo + (a_hi - a_lo) * unit(value)).astype(np.float32)
        elif path.endswith("/dt_bias"):
            dt = np.exp(lo + (hi - lo) * unit(value))
            out[path] = (dt + np.log(-np.expm1(-dt))).astype(np.float32)   # softplus^-1
    return out


class SsmTrainCell(L.LeanTrainCell):
    def __init__(self, ctx):
        """``LeanTrainCell.__init__`` with the weights mapped (:func:`ssm_ranges`)
        as they are made, before the program or the reference sees them."""
        make, dc = L.W.make_weights, ctx.config["reference"]["decoder"]
        mapped = lambda seed, specs: ssm_ranges(dc, jax.device_get(make(seed, specs)))
        with mock.patch.object(L.W, "make_weights", mapped):
            super().__init__(ctx)

    def reference_slots(self) -> float:
        w = jax.device_put(self.w0)
        count = jax.jit(lambda w, image: B.slots_here(self.ref, w, D.normalize(self.ref, image[None])))
        return sum(float(count(w, jnp.asarray(image))) for image in self.followed[0]["images"])

    def step_flops(self, counters=None) -> float:
        from perfbench.ssm_need import step_flops

        return step_flops(self.ref_run, self.global_batch, (counters or {}).get("moe_slots_here"))


def _no_carry(chunk: int):
    """``B.recurrence`` with the state started from zero at every chunk, through
    its own inputs: at a chunk's first token dt is made so large that the decay
    ``exp(dt A)`` is exactly 0 (A <= -1 here), and x is scaled down by the same
    factor, so what the token adds, ``dt x B``, stays what it was."""
    real, forget = B.recurrence, 1.0e4

    def chunk_by_chunk(x, dt, a, b, c):
        first = (jnp.arange(x.shape[0]) % chunk == 0)[:, None]
        reset = jnp.where(first, forget, dt)
        return real(x * (dt / reset)[..., None], reset, a, b, c)

    return chunk_by_chunk


_lean_side_reading = L.side_reading     # before :func:`_as_this_family` rebinds the name


def side_reading(cell, kind: str, ref_res: dict) -> dict:
    """``train_lean.side_reading`` with this family's backbone in the two
    faults that reach into it."""
    if kind == "no_experts":      # the held experts' part left out of the layer
        patch = mock.patch.object(B, "held", lambda dc: range(0))
    elif kind == "no_carry":      # the scan's carry between chunks left out
        patch = mock.patch.object(B, "recurrence", _no_carry(cell.ref["decoder"]["chunk_size"]))
    else:
        return _lean_side_reading(cell, kind, ref_res)
    with patch:
        return L.numbers_of(cell.reference(), ref_res)


@contextlib.contextmanager
def _as_this_family():
    with mock.patch.multiple(L, LeanTrainCell=SsmTrainCell, side_reading=side_reading), \
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
