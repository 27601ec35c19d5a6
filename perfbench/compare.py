"""The comparison that decides ``correct``: the timed path's own numbers
against the plain reference's, each under a limit of its own (the limits sit
in the cell's file with the readings they were set from in PERF.md).

Training: each followed step's loss, the first gradient as the optimizer
applied it, and the parameters' change after the followed steps - the last
two by the worst leaf: the gap between the program's norm and the reference's
(not the norm of their difference), against the reference's norm of that leaf
or of the median leaf, whichever is larger.

Beside them one number that is steady from seed to seed (PERF.md section 2
has the look that led to it): the direction of the first gradient and of the
change over the cell's ``steady`` leaves - the RPN head, whose gradient does
not pass through the proposal picks, which flip on bfloat16 rounding and
redraw the whole roi sample.  Each side's leaves are scaled by that side's
norm over all of them, which takes out the optimizer's global-norm clip (it
couples every leaf to the noisiest); then the worst leaf's norm of the
difference, against the reference's share of that leaf or of the median leaf.
"""

from __future__ import annotations

import statistics


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def worst_leaf(prog: dict, ref: dict, skip=()) -> tuple[float, str]:
    """(gap, leaf) of the worst leaf; both sides must hold the same leaves."""
    if set(prog) != set(ref):
        only = sorted(set(prog) ^ set(ref))
        raise ValueError(f"leaves differ between program and reference: {only[:4]}")
    med = statistics.median(ref.values())
    worst, where = 0.0, ""
    for leaf, r in ref.items():
        if leaf in skip:
            continue
        gap = abs(prog[leaf] - r) / max(r, med, 1e-30)
        if gap > worst:
            worst, where = gap, leaf
    return worst, where


def direction_gap(prog: dict, ref: dict) -> tuple[float, str]:
    """``prog``/``ref``: {leaf: array} over the same leaves -> (gap, leaf) of
    the worst leaf, each side scaled to unit norm over all its leaves."""
    import numpy as np

    if set(prog) != set(ref) or not ref:
        raise ValueError(f"steady leaves differ: {sorted(set(prog) ^ set(ref))[:4]}")
    def total(side):
        squares = sum(np.sum(np.square(v, dtype=np.float64)) for v in side.values())
        return max(float(np.sqrt(squares)), 1e-30)

    tp, tr = total(prog), total(ref)
    share = {p: float(np.linalg.norm(v)) / tr for p, v in ref.items()}
    med = statistics.median(share.values())
    worst, where = 0.0, ""
    for leaf, r in ref.items():
        diff = float(np.linalg.norm(
            np.asarray(prog[leaf], np.float64) / tp - np.asarray(r, np.float64) / tr
        ))
        gap = diff / max(share[leaf], med, 1e-30)
        if not gap <= worst:  # a NaN is the worst
            worst, where = gap, leaf
    return worst, where


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"steps": [{"loss", "rpn", "rcnn"}...], "grad1":
    {leaf: norm}, "change": {leaf: norm}, "steady_grad1"/"steady_change":
    {leaf: array} (may be empty)} -> {number name: value}."""
    out = {}
    for i, (p, r) in enumerate(zip(prog["steps"], ref["steps"]), start=1):
        out[f"loss{i}"] = rel_gap(p["loss"], r["loss"])
        out[f"rpn{i}"] = rel_gap(p["rpn"], r["rpn"])
        out[f"rcnn{i}"] = rel_gap(p["rcnn"], r["rcnn"])
    out["grad1"], out["grad1_leaf"] = worst_leaf(prog["grad1"], ref["grad1"])
    # A leaf whose reference gradient is nought to rounding moves by
    # round-off alone: out of the change, by the rule on the gradient.
    med = statistics.median(ref["grad1"].values())
    still = {leaf for leaf, g in ref["grad1"].items() if g < 1e-3 * med}
    out["change"], out["change_leaf"] = worst_leaf(prog["change"], ref["change"], skip=still)
    if ref.get("steady_grad1"):
        out["dir1"], out["dir1_leaf"] = direction_gap(prog["steady_grad1"], ref["steady_grad1"])
        out["dirc"], out["dirc_leaf"] = direction_gap(prog["steady_change"], ref["steady_change"])
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every limit's number at or under it.  -> (correct, {name: [value, limit]})."""
    rows, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        rows[name] = [value, limit]
        if value is None or not (value <= limit):
            ok = False
    return ok, rows
