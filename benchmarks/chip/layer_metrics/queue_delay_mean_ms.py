"""Mean queueing delay as a user feels it: the mean time to image over
the window, minus the time to image of a request that found the server
empty (the median over the requests sent when every earlier one was
already on /history).  From the benchmark's own records, not from the
program's ``pipeline.stages.queue_wait``: the executor takes a request as
soon as it arrives, and the wait for the device then happens inside
``compute`` and ``d2h`` (that span read 0.24 s where this reads 0.62 s)."""

from lib.stats import median


def read(ctx):
    alone, busy_until = [], float("-inf")
    for r in sorted(ctx.completed(), key=lambda r: r["due"]):
        if busy_until <= r["due"]:
            alone.append(r["done"] - r["due"])
        busy_until = max(busy_until, r["done"])
    if not alone:
        return None
    lat = ctx.latencies()
    return 1e3 * (sum(lat) / len(lat) - median(alone))
