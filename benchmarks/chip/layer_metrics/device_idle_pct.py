"""Share of the trace slice in which no operation ran on the device,
mean over the cell's chips."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
