"""The held experts' share of their roofline: the least time for three
matmuls over the token-slots the step routed here (its own counter) and the
held weights moved once (ling_need.py::moe_experts_need, forward and
backward) over the device time under ``moe/experts`` together with that of
the grouped matmuls themselves, found by name: XLA turns ``lax.ragged_dot``
into a custom call ``ragged-dot-none`` that keeps no scope path (read from
the scope alone the share came out at 77 %, of the glue's time)."""

from perfbench.ling_need import moe_experts_need
from perfbench.ling_readers import has_decoder, share


def read(reading):
    slots = reading["counters"].get("moe_slots_here")
    if slots is None or not has_decoder(reading):
        return None
    need = moe_experts_need(reading["config"]["reference"], slots / reading["chips"])
    return share(reading, need, "moe", "experts", named=r"^ragged-dot")
