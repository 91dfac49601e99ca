"""Process start to window start: loading, making the weights, warming
up and, in a run that compiles, compilation."""


def read(ctx):
    return ctx.setup_s
