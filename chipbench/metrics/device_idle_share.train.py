"""Share of the traced training window in which no operation ran on a chip,
as the mean over the cell's chips (device layer)."""


def read(ctx):
    if ctx.kind != "train" or ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share()
