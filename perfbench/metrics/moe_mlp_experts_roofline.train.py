"""The held two-matrix experts' share of their roofline: the least time for
two matmuls over the token-slots the step routed here (its own counter) and
the held weights moved once (ssm_need.py::moe_mlp_experts_need, forward and
backward) over the device time under ``moe/experts``.  The program computes
every held expert over every token (no dispatch, PR 32), so the share reads
what that costs against what the routed slots need: a dispatch that costs
what its slots need would raise it, a collapsed routing lowers the need."""

from perfbench.ling_readers import share
from perfbench.ssm_need import moe_mlp_experts_need
from perfbench.ssm_readers import has_ssm


def read(reading):
    slots = reading["counters"].get("moe_slots_here")
    if slots is None or not has_ssm(reading):
        return None
    need = moe_mlp_experts_need(reading["config"]["reference"], slots / reading["chips"])
    return share(reading, need, "moe", "experts")
