"""Analytic FLOP counting by walking a jaxpr.

XLA's ``compiled.cost_analysis()`` counts a ``lax.scan`` body once (no
trip-count multiply), so it can't report the K-step train program's true
cost; this counter walks the traced program itself — every
``conv_general_dilated`` and ``dot_general`` in the jaxpr (recursing into
pjit/scan/while/cond/remat sub-jaxprs, scaling by scan trip counts) —
and cross-checks against cost_analysis's per-body figure (they agree to
~1% on the detector step).

Elementwise/reduction work is ignored — on a TPU the MXU ops are where
>95% of a convnet's FLOPs live, and MFU is conventionally defined on
matmul FLOPs (the scaling-book convention).
"""

from __future__ import annotations

import math

import jax

# Published peak dense-matmul rate per chip in bf16, keyed by the
# ``device_kind`` string jax reports (``jax.devices()[0].device_kind``).
# The one table every MFU figure in the repo divides by; a kind that is
# not here is an error, never a default.
#   "TPU v5 lite": TPU v5e, 197 TFLOP/s — Google Cloud documentation,
#   "TPU v5e"; the key is the string the chip reported in PR 21's runs.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_bf16_flops(device_kind: str) -> float:
    """Peak bf16 FLOP/s of one chip of ``device_kind``; raises on a kind
    the table does not list (a CPU included — no MFU exists there)."""
    if device_kind not in PEAK_BF16_FLOPS:
        raise ValueError(
            f"no published bf16 peak for device_kind {device_kind!r}; "
            f"known: {sorted(PEAK_BF16_FLOPS)} — add it to "
            "utils/flops.py::PEAK_BF16_FLOPS with its source"
        )
    return PEAK_BF16_FLOPS[device_kind]


def _conv_flops(eqn) -> float:
    """2 * batch * out_spatial * Cout * (Cin/groups) * kernel_spatial."""
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    out = eqn.outvars[0].aval
    dn = eqn.params["dimension_numbers"]
    groups = eqn.params.get("feature_group_count", 1)
    out_spatial = [out.shape[d] for d in dn.out_spec[2:]]
    kernel_spatial = [rhs.shape[d] for d in dn.rhs_spec[2:]]
    batch = out.shape[dn.out_spec[0]]
    c_out = out.shape[dn.out_spec[1]]
    c_in = lhs.shape[dn.lhs_spec[1]]
    return (
        2.0
        * batch
        * math.prod(out_spatial)
        * c_out
        * (c_in / groups)
        * math.prod(kernel_spatial)
    )


def _dot_flops(eqn) -> float:
    """2 * batch_dims * M * N * K."""
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = math.prod(lhs.shape[d] for d in lb)
    k = math.prod(lhs.shape[d] for d in lc)
    m = math.prod(
        lhs.shape[d] for d in range(lhs.ndim) if d not in tuple(lc) + tuple(lb)
    )
    n = math.prod(
        rhs.shape[d] for d in range(rhs.ndim) if d not in tuple(rc) + tuple(rb)
    )
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
            total += eqn.params["length"] * _jaxpr_flops(
                eqn.params["jaxpr"].jaxpr
            )
        elif prim == "while":
            # Trip count is data-dependent; count one iteration (documented
            # lower bound — the NMS fixed point converges in a few sweeps).
            total += _jaxpr_flops(eqn.params["body_jaxpr"].jaxpr)
        elif prim == "cond":
            total += max(
                _jaxpr_flops(b.jaxpr) for b in eqn.params["branches"]
            )
        else:
            # Generic containers: pjit/remat/custom_vjp/closed_call all
            # carry their body under a jaxpr-valued param.
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                sub = eqn.params.get(key)
                if sub is not None:
                    total += _jaxpr_flops(
                        sub.jaxpr if hasattr(sub, "jaxpr") else sub
                    )
                    break
    return total


def count_matmul_flops(fn, *args, **kwargs) -> float:
    """Matmul+conv FLOPs of one call of ``fn(*args)`` (abstract trace; no
    execution, no device)."""
    jaxpr = jax.make_jaxpr(fn, **kwargs)(*args)
    return _jaxpr_flops(jaxpr.jaxpr)
