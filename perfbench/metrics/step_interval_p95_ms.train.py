"""95th percentile of the completion-to-completion interval of consecutive
step programs on the busiest chip, over the traced window (device trace)."""

from perfbench import trace_reduce as tr


def read(reading):
    iv = tr.step_intervals_ns(reading["modules"], reading["program_name"])
    if len(iv) < 2:
        return None
    reading["step_interval_samples"] = len(iv)
    return tr.percentile(iv, 95) / 1e6
