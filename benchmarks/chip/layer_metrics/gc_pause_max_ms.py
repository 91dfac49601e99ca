"""The longest single collector pause of the window (``gc_pause``'s
``max_s``): what one request behind it waits for."""


def read(ctx):
    row = ctx.stage("gc_pause")
    return None if row is None else 1e3 * float(row["max_s"])
