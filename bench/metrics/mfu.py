"""Model FLOPs of the window's steps (bench/flops.py) over the traced
window's length times the chip's bf16 peak."""


def read(ctx):
    if ctx.trace["window_s"] <= 0:
        return None
    flops = ctx.flops_per_step * ctx.steps
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peak["bf16_flops_per_s"])
