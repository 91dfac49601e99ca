"""``retraces.compiles`` after the window minus before it.  Must read 0;
``correct`` is false otherwise."""


def read(ctx):
    return float(ctx.compiles_in_window)
