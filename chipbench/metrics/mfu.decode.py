"""A decode step's share of the chip's roofline: the larger of its required
operations over the peak bf16 rate and its required bytes over the peak HBM
rate (counts/<family>.py), over the traced window's time per step (step
program layer). At these sizes the bytes bound it."""


def read(ctx):
    if ctx.kind != "decode" or ctx.trace is None or not ctx.steps:
        return None
    step_s = ctx.trace.window_s / ctx.steps
    least = max(ctx.counts["flops_per_step"] / ctx.peak["bf16_flops_per_s"],
                ctx.counts["bytes_per_step"] / ctx.peak["hbm_bytes_per_s"]) / ctx.chips
    return 100.0 * least / step_s
