"""Device milliseconds per step of the NMS inside ``proposals``: the IoU
matrix, the fixed-point loop and the ranking of what it kept - the ops under
the jitted ``nms_indices``, which is all the ``nms`` scope holds.  The
function's name is in the path whether or not the executable knows the scope:
the persistent cache's key leaves names out, so a warm cache hands out one
compiled before the scope existed."""

from perfbench import trace_reduce as tr
from perfbench.hlo_module import names_of_reading
from perfbench.readers import scope_re

OUTER = scope_re("proposals")
INNER = scope_re("nms_indices")


def read(reading):
    names = names_of_reading(reading)
    iv = []
    for nm, s, d, sc in reading["ops"]:
        path = names.get(nm, sc)
        if OUTER.search(path) and INNER.search(path):
            iv.append((s, d))
    if not iv or not reading["steps_traced"]:
        return None
    return tr.union_ns(iv) / 1e6 / reading["steps_traced"]
