"""Sweeps of the fixed-point NMS per step.  The trace shows the ops of a
``while`` body once per iteration, so the runs of the loop body's ops are
counted: the ops under ``nms_indices/while/body`` (the ``nms_sweep`` scope
sits below that, in an executable compiled since it exists; the path above it
is in every one).  Per body computation the most any one of its instructions
ran, summed over the bodies; an op XLA peeled out of the loop (it sits in no
``while`` body) is no sweep.  Where the trace does not hold the program, all
count as one body."""

import collections
import re

from perfbench.hlo_module import computation_of, loop_bodies, module_of_reading, names_of_reading

BODY = re.compile(r"nms_indices\)*/while/body")


def read(reading):
    names = names_of_reading(reading)
    module = module_of_reading(reading)
    where = computation_of(module) if module else {}
    bodies = loop_bodies(module) if module else {""}
    runs = collections.Counter()
    for nm, _, _, sc in reading["ops"]:
        if BODY.search(names.get(nm, sc)) and where.get(nm, "") in bodies:
            runs[nm] += 1
    if not runs or not reading["steps_traced"]:
        return None
    per_body = {}
    for nm, n in runs.items():
        body = where.get(nm, "")
        per_body[body] = max(per_body.get(body, 0), n)
    return sum(per_body.values()) / reading["steps_traced"]
