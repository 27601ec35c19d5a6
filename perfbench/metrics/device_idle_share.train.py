"""1 - (union of op intervals / traced window) on the busiest chip, in %."""


def read(reading):
    return 100.0 * (1.0 - reading["busiest_busy_s"] / reading["window_s"])
