"""The fullest held expert's token-slots over the mean, in the worst expert
layer of the worst step between two barriers (the step's own counter);
``moe_load_max_over_mean.train`` is the same reading and lists the other
decoder cell."""


def read(reading):
    return reading["counters"].get("moe_load_max_over_mean")
