"""What the ``ling3_flash_vl`` readers under ``metrics/`` share: device time
under a path of scopes, and a kernel's share of its roofline from a need in
``ling_need.py``.  Each returns None where the trace holds no such scope or
the run no such counter (a program without the backbone), never 0."""

from __future__ import annotations

import re

from perfbench import trace_reduce as tr
from perfbench.readers import roofline_share, scope_re


def scoped_ms(reading, *scopes, named=None):
    """Device ms a step of the ops whose path holds EVERY one of ``scopes``
    as a component (forward, recomputed forward and backward alike), and of
    the ops whose own name matches ``named``: a custom call that XLA makes
    from an op (``ragged-dot-none``) carries no scope path at all."""
    rxs = [scope_re(s) for s in scopes]
    by_name = re.compile(named) if named else None
    iv = [(s, d) for nm, s, d, sc in reading["ops"]
          if all(rx.search(sc) for rx in rxs) or (by_name and by_name.search(nm))]
    if not iv or not reading["steps_traced"]:
        return None
    return tr.union_ns(iv) / 1e6 / reading["steps_traced"]


def images_per_chip(reading) -> int:
    return reading["counters"]["global_batch"] // reading["chips"]


def has_decoder(reading) -> bool:
    return "decoder" in reading["config"].get("reference", {})


def share(reading, need, *scopes, named=None):
    ms = scoped_ms(reading, *scopes, named=named)
    if ms is None:
        return None
    return roofline_share(reading, need, ms)
