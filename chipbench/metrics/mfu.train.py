"""The operations a training step requires (counts/<family>.py), over chips
times the peak bf16 rate times the traced window's time per step (step
program layer)."""


def read(ctx):
    if ctx.kind != "train" or ctx.trace is None or not ctx.steps:
        return None
    step_s = ctx.trace.window_s / ctx.steps
    return 100.0 * ctx.counts["flops_per_step"] / (
        ctx.chips * ctx.peak["bf16_flops_per_s"] * step_s)
