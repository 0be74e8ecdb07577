"""Mean time per training step that the loop waits on the program's data
stream for the next batch, on the benchmark's host clock (data layer)."""


def read(ctx):
    if ctx.kind != "train" or not ctx.steps or "chipbench.next_batch" not in ctx.host:
        return None
    return 1e3 * ctx.host["chipbench.next_batch"] / ctx.steps
