"""Host milliseconds per step the consumer waited for the loader past the
prefetch buffer (the loop's own ``PrefetchStats``, host clock)."""


def read(reading):
    c = reading["counters"]
    return 1000.0 * c["data_stall_s"] / c["steps"]
