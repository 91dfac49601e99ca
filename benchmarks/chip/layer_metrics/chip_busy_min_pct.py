"""Busy share of the least busy chip in the trace slice: with fan-out
over the data axis every chip should be as busy as the mean."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * min(c["busy_s"] for c in ctx.trace["chips"]) \
        / ctx.trace["window_s"]
