"""The whole step's share of the chip's bf16 peak: the matmul+conv FLOPs a
step needs (the plain reference's forward and backward, nothing recomputed),
times the steps completed in the traced window, over window, chips and peak."""


def read(reading):
    if not reading["steps_traced"] or reading["step_flops"] is None:
        return None
    flops = reading["step_flops"]() * reading["steps_traced"]
    return 100.0 * flops / reading["window_s"] / reading["chips"] / reading["peak"]["bf16_flops"]
