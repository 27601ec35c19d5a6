"""% of the device's busy time in which no op with a name runs: an op has a
name when its ``op_name`` path - its own, or for a fusion XLA made without
one that of what it holds (``hlo_module.resolve``) - has a component above
the primitive.  A loop that has a name covers the ops inside it."""

from perfbench import trace_reduce as tr
from perfbench.hlo_module import has_scope, names_of_reading


def read(reading):
    ops = reading["ops"]
    if not ops:
        return None
    names = names_of_reading(reading)
    busy = tr.union_ns([(s, d) for _, s, d, _ in ops])
    named = tr.union_ns([(s, d) for nm, s, d, sc in ops if has_scope(names.get(nm, sc))])
    return 100.0 * (busy - named) / busy
