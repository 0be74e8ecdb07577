"""Share of the traced decode window in which no operation ran on a chip,
as the mean over the cell's chips (device layer)."""


def read(ctx):
    if ctx.kind != "decode" or ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share()
