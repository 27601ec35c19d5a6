"""``entries/train_lean.py``'s run for the ``phi4_mini_flash`` backbone: the
same window, feed, followed steps and ``compare.py`` numbers, with what that
file hard-codes for the first decoder family swapped, as
``train_lean_ssm.py`` does for the second:

- the plain backbone (``reference/backbone_phi4_mini_flash.py``) in the faults,
  four of them this family's own: ``no_carry`` (the selective scan started
  from zero at every chunk), ``no_window`` (the window layers see the whole
  prefix), ``no_diff`` (lambda a_2 left out of the differential attention) and
  ``stale_share`` (the Gated Memory Unit and the cross layers read zeros in
  place of the memory, keys and values handed on to them);
- the step's needed FLOPs (``sambay_need.py::step_flops``);
- the weights: ``A_log``, ``dt_bias`` and ``lambda`` are drawn as a scale or a
  bias (``weights.py`` gains no kind) and mapped here onto the family's ranges
  (:func:`sambay_ranges`) before the program or the reference sees them;
- which leaves weight decay skips: ``reference/detector.py::decayed`` knows
  ``bias`` and ``scale``; this family's recipe (and the program's optimizer,
  ``train/optim.py::NO_DECAY``) skips ``A_log``, ``dt_bias``, ``D`` and
  ``lambda`` too.

- what ``grad1`` and ``change`` judge: every leaf but a ``lambda`` leaf whose
  gradient the float32 reference itself finds to be the remainder of a
  cancellation in THIS run (:class:`SambayReference`, :func:`numbers_of`:
  a rule on a number measured each run, not on the leaf's name); what the rule
  measured and both numbers with every leaf in ride beside, under no limit.

``train_lean.py`` and ``detector.py`` are accepted files that name their own
class, backbone and rule, so their ``run``, ``main``, ``side_reading``,
``numbers_of`` and ``decayed`` carry this module's names while this entry runs
and no longer (:func:`_as_this_family`).  Nothing here is routed: the
merged-experts numbers read what ``grad1`` and ``change`` read.

Run as a script it takes the readings the cell's limits are set from:

    python3 perfbench/entries/train_lean_sambay.py --workload <cell> --seeds 1,2 \\
        [--sides fp8,half_batch,unchanged,no_carry,no_window,no_diff,stale_share] [--seconds 2]
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
import jax.numpy as jnp
import numpy as np

from perfbench.entries import train_lean as L
from perfbench.entries.train_lean_ssm import ssm_ranges
from perfbench.reference import backbone_phi4_mini_flash as B
from perfbench.entries.train import reference_side
from perfbench.reference import detector as D
from perfbench.reference.train_lean import LeanReference

NO_DECAY = ("bias", "scale", "A_log", "dt_bias", "D", "lambda")


def decayed(path: str) -> bool:
    return path.rsplit("/", 1)[1] not in NO_DECAY


def sambay_ranges(dc: dict, weights: dict) -> dict:
    """``train_lean_ssm.py::ssm_ranges`` (every ``A_log`` and ``dt_bias`` from
    its uniform draw onto the family's ranges: A = exp(A_log) uniform in
    ``A_range``, 1-16; dt = softplus(dt_bias) log-uniform in ``time_step_min``-
    ``time_step_max``), and every ``lambda`` scaled from a bias's normal 0.02 to
    normal ``lambda_std``."""
    out = ssm_ranges(dc, weights)
    for path, value in weights.items():
        if path.endswith("/lambda"):
            out[path] = (np.asarray(value, np.float64) * (dc["lambda_std"] / 0.02)).astype(np.float32)
    return out


class SambayTrainCell(L.LeanTrainCell):
    def __init__(self, ctx):
        """``LeanTrainCell.__init__`` with the weights mapped (:func:`sambay_ranges`)
        as they are made, before the program or the reference sees them."""
        make, dc = L.W.make_weights, ctx.config["reference"]["decoder"]
        mapped = lambda seed, specs: sambay_ranges(dc, jax.device_get(make(seed, specs)))
        with mock.patch.object(L.W, "make_weights", mapped):
            super().__init__(ctx)

    def reference(self, matmul=None, batches=None, unchanged=False) -> dict:
        """``LeanTrainCell.reference`` through :class:`SambayReference`, whose
        result also says how well conditioned each ``lambda`` leaf's gradient is."""
        runner = SambayReference(self.ref_run, matmul=matmul, devices=jax.devices()[: self.cell["chips"]])
        out = runner.run(self.w0, self.followed if batches is None else batches, self.rng,
                         self.follow_steps, steady=self.steady, unchanged=unchanged)
        return dict(reference_side(out), lambda_kept=runner.lambda_kept)

    def step_flops(self, counters=None) -> float:
        from perfbench.sambay_need import step_flops

        return step_flops(self.ref_run, self.global_batch)


def _no_carry(chunk: int):
    """``B.recurrence`` with the state started from zero at every chunk, through
    its own inputs: at a chunk's first token dt is made so large that the decay
    ``exp(dt A)`` is exactly 0 (A <= -1 here), and x is scaled down by the same
    factor, so what the token adds, ``dt x B``, stays what it was."""
    real, forget = B.recurrence, 1.0e4

    def chunk_by_chunk(x, dt, a, b, c):
        first = (jnp.arange(x.shape[0]) % chunk == 0)[:, None]
        reset = jnp.where(first, forget, dt)
        return real(x * (dt / reset), reset, a, b, c)

    return chunk_by_chunk


# A ``lambda`` leaf is judged where at least this share of the sum behind its
# gradient is left once the sum's terms have cancelled (PERF.md section 2 has
# the readings the share was set from).
KEPT_MIN = 0.05


FIELD = "lambda_field"   # beside a layer's ``lambda`` among the weights, while a gradient is taken


@contextlib.contextmanager
def _lambda_fields():
    """``B.difference`` with a layer's scalar lambda widened to lambda + a FIELD
    of zeros, one number per position, query pair and value column, read from
    the weights ``B.diff_attention`` was handed: the loss's gradient by the
    field is the term each of them adds to d loss / d lambda.  Whatever
    ``B.difference`` is at the time (a fault may have replaced it) stays."""
    real_attention, real_difference, here = B.diff_attention, B.difference, {}

    def diff_attention(dc, w, p, *args, **kw):
        here["field"] = w.get(f"{p}/{FIELD}")
        return real_attention(dc, w, p, *args, **kw)

    def difference(a1, a2, lam):
        return real_difference(a1, a2, lam if here["field"] is None else lam + here["field"])

    with mock.patch.multiple(B, diff_attention=diff_attention, difference=difference):
        yield


class SambayReference(LeanReference):
    """``LeanReference`` that also measures, on its first batch at the first
    weights, how much of d loss / d lambda is left after its terms cancel.

    A layer's lambda is ONE scalar, so its leaf's gradient is d loss / d lambda
    times fixed vectors, and d loss / d lambda = - sum over every position,
    query pair and value column of g * a_2, with g the cotangent of
    ``a_1 - lambda a_2``.  That cotangent comes through an RMSNorm, which forgets
    scale, so g is orthogonal to ``a_1 - lambda a_2``; where both maps average
    many values that look alike, a_1 ~ a_2, g is all but orthogonal to a_2 as
    well, and the sum is a small remainder of large terms: rounding in the
    terms (bfloat16 operands, as the configuration states) then moves the
    remainder by as much as it is.  The terms are the gradient by a field of
    zeros added to lambda (:func:`_lambda_fields`), which rides among the
    trainable weights into the ONE jitted per-image gradient at every step and
    is taken out again before the optimizer sees it: no second program, no
    second backward.  ``lambda_kept``: {leaf: {"kept": |sum| / sum of |terms|
    (a term: one position, pair and column, over the batch's images),
    "kept_by_position": |sum| / sum over positions and pairs of |a position's
    terms summed|, "sum": d loss / d lambda of the batch, unclipped}}."""

    lambda_kept = None

    def batch_grads(self, wt, wf, batch, rng, step):
        dc = self.ref["decoder"]
        height, width = batch["images"].shape[1:3]
        zeros = jnp.zeros(((height // dc["patch"]) * (width // dc["patch"]),
                           dc["num_attention_heads"] // 2, 2 * dc["head_dim"]), jnp.float32)
        fields = {p[: -len("lambda")] + FIELD: zeros for p in wt if p.endswith("/lambda")}
        with _lambda_fields():
            grads, report = super().batch_grads({**wt, **fields}, wf, batch, rng, step)
        terms = {p: grads.pop(p) for p in fields}
        if self.lambda_kept is None:
            self.lambda_kept = {}
            for p, t in terms.items():
                total, by_position, each = (float(v) for v in jax.device_get((
                    jnp.sum(t), jnp.sum(jnp.abs(jnp.sum(t, axis=-1))), jnp.sum(jnp.abs(t)))))
                self.lambda_kept[p[: -len(FIELD)] + "lambda"] = {
                    "kept": abs(total) / max(each, 1e-30),
                    "kept_by_position": abs(total) / max(by_position, 1e-30), "sum": total}
        return grads, report


_lean_side_reading = L.side_reading     # before :func:`_as_this_family` rebinds the names
_lean_numbers_of = L.numbers_of


def numbers_of(prog: dict, ref_res: dict) -> dict:
    """``train_lean.numbers_of`` over every leaf but the ``lambda`` leaves that
    the reference finds ill-conditioned in this run: those where under
    ``KEPT_MIN`` of the sum behind d loss / d lambda is left after its terms
    cancel (:class:`SambayReference`, measured on ``ref_res``'s side in
    float32).  There the program's bfloat16 terms move the remainder by as much
    as it is, and the leaf's norm says nothing of the program, sound or not;
    above it the leaf is judged like any other.  The measure, which leaves
    were left out, and ``grad1`` and ``change`` with every leaf in ride beside,
    under no limit."""
    kept = ref_res.get("lambda_kept") or {}
    out_of = sorted(leaf for leaf, k in kept.items() if k["kept"] < KEPT_MIN)
    without = lambda norms: {p: v for p, v in norms.items() if p not in out_of}
    cut = lambda side: dict(side, grad1=without(side["grad1"]), change=without(side["change"]))
    out = _lean_numbers_of(cut(prog), cut(ref_res))
    whole = L.compare.train_numbers(prog, ref_res)
    for k in ("grad1", "grad1_leaf", "change", "change_leaf"):
        out[f"{k}_with_lambda"] = whole[k]
    out["lambda_kept"] = {leaf: [k["kept"], k["kept_by_position"]] for leaf, k in kept.items()}
    out["lambda_left_out"] = out_of
    return out


def side_reading(cell, kind: str, ref_res: dict) -> dict:
    """``train_lean.side_reading`` with this family's backbone in the four
    faults that reach into it."""
    if kind == "no_carry":        # the scan's carry between chunks left out
        patch = mock.patch.object(B, "recurrence", _no_carry(cell.ref["decoder"]["chunk_size"]))
    elif kind == "no_window":     # the window layers see the whole prefix
        patch = mock.patch.object(B, "window", lambda dc, kind: None)
    elif kind == "no_diff":       # lambda a_2 left out
        patch = mock.patch.object(B, "difference", lambda a1, a2, lam: a1)
    elif kind == "stale_share":   # GMU and cross layers read zeros
        patch = mock.patch.object(B, "handed_on", jnp.zeros_like)
    else:
        return _lean_side_reading(cell, kind, ref_res)
    with patch:
        return L.numbers_of(cell.reference(), ref_res)


@contextlib.contextmanager
def _as_this_family():
    with mock.patch.multiple(L, LeanTrainCell=SambayTrainCell, side_reading=side_reading,
                             numbers_of=numbers_of), mock.patch.object(D, "decayed", decayed):
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
