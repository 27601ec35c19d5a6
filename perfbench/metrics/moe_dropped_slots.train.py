"""Token-slots routed to a held expert that found no room in the dispatch
buffer, the worst step of the window (the step's own counter; must read 0)."""


def read(reading):
    return reading["counters"].get("moe_dropped_slots")
