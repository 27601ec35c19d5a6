"""The fullest held expert's token-slots over the mean, in the worst expert
layer of the worst step between two barriers (the step's own counter)."""


def read(reading):
    return reading["counters"].get("moe_load_max_over_mean")
