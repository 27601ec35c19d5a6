"""What the ``phi4_mini_flash`` readers under ``metrics/`` share beside
``ling_readers.py``'s ``scoped_ms`` and ``share``: whether the run's
configuration is of this family at all, and device time under EITHER of two
scope paths (the full-attention layer's and the cross layers' attention).  A
reader returns None, never 0, where the family is not the run's (a program
without the backbone, the parent commit)."""

from __future__ import annotations

from perfbench import trace_reduce as tr
from perfbench.readers import scope_re


def has_sambay(reading) -> bool:
    decoder = reading["config"].get("reference", {}).get("decoder", {})
    return "mb_per_layer" in decoder


def either_ms(reading, *paths):
    """Device ms a step of the ops whose scope path holds every component of
    ANY one of ``paths`` (each a tuple of scope names)."""
    rxs = [[scope_re(s) for s in path] for path in paths]
    iv = [(s, d) for _, s, d, sc in reading["ops"]
          if any(all(rx.search(sc) for rx in path) for path in rxs)]
    if not iv or not reading["steps_traced"]:
        return None
    return tr.union_ns(iv) / 1e6 / reading["steps_traced"]
