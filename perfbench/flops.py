"""Operation and byte counts kept with the benchmark, so that no later PR can
move the yardstick: the matmul+conv FLOPs of a traced function (a copy of the
program's ``utils/flops.py::count_matmul_flops`` jaxpr walk - the original is
listed in PERF.md's Open questions for a later PR to delete) and the shape
functions of ROIAlign, which count what the ALGORITHM needs whatever
implements it.
"""

from __future__ import annotations

import math

import jax


def _conv_flops(eqn) -> float:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    out = eqn.outvars[0].aval
    dn = eqn.params["dimension_numbers"]
    groups = eqn.params.get("feature_group_count", 1)
    out_spatial = [out.shape[d] for d in dn.out_spec[2:]]
    kernel_spatial = [rhs.shape[d] for d in dn.rhs_spec[2:]]
    batch = out.shape[dn.out_spec[0]]
    c_out = out.shape[dn.out_spec[1]]
    c_in = lhs.shape[dn.lhs_spec[1]]
    return 2.0 * batch * math.prod(out_spatial) * c_out * (c_in / groups) * math.prod(kernel_spatial)


def _dot_flops(eqn) -> float:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = math.prod(lhs.shape[d] for d in lb)
    k = math.prod(lhs.shape[d] for d in lc)
    m = math.prod(lhs.shape[d] for d in range(lhs.ndim) if d not in tuple(lc) + tuple(lb))
    n = math.prod(rhs.shape[d] for d in range(rhs.ndim) if d not in tuple(rc) + tuple(rb))
    return 2.0 * batch * m * n * k


def _jaxpr_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif prim == "dot_general":
            total += _dot_flops(eqn)
        elif prim == "scan":
            total += eqn.params["length"] * _jaxpr_flops(eqn.params["jaxpr"].jaxpr)
        elif prim == "while":
            # Data-dependent trip count: one iteration, a stated lower bound.
            total += _jaxpr_flops(eqn.params["body_jaxpr"].jaxpr)
        elif prim == "cond":
            total += max(_jaxpr_flops(b.jaxpr) for b in eqn.params["branches"])
        else:
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                sub = eqn.params.get(key)
                if sub is not None:
                    total += _jaxpr_flops(sub.jaxpr if hasattr(sub, "jaxpr") else sub)
                    break
    return total


def count_matmul_flops(fn, *args, **kwargs) -> float:
    """Matmul+conv FLOPs of one call of ``fn(*args)`` (abstract trace only)."""
    return _jaxpr_flops(jax.make_jaxpr(fn, **kwargs)(*args).jaxpr)


def roi_align_need(rois: int, size: int, ratio: int, channels: int, level_cells: int,
                   itemsize: int, backward: bool = False) -> dict:
    """What ROIAlign needs for ``rois`` boxes pooled to size x size bins of
    ratio x ratio bilinear samples over ``channels``, from a pyramid of
    ``level_cells`` cells in total (all images):

    - FLOPs = rois x bins x samples x 4 taps x 2 x channels (forward; the
      backward spreads the same taps, the same count);
    - bytes = the pooled output written (backward: the cotangent read), the
      boxes read, and the features read (backward: the feature gradient
      written) - taken as the SMALLER of every pyramid cell once and every
      tap on its own, the least any implementation could move, so that no
      faster kernel reads over 100 %.
    """
    bins = size * size
    flops = rois * bins * ratio * ratio * 4 * 2 * channels
    pooled = rois * bins * channels * itemsize
    taps = rois * bins * ratio * ratio * 4 * channels * itemsize
    feature = min(level_cells * channels * itemsize, taps)
    if backward:
        # The feature gradient is accumulated in float32 whatever the
        # features' type.
        feature = min(level_cells * channels * 4, taps)
    return {"flops": float(flops), "bytes": float(pooled + rois * 16 + feature)}


def least_seconds(need: dict, peak: dict) -> tuple[float, str]:
    """The roofline's least time and which bound holds."""
    t_f = need["flops"] / peak["bf16_flops"]
    t_b = need["bytes"] / peak["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
