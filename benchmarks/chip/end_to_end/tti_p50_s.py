"""Median time to image: from when a request was due to be sent to its
id on /history, over every attempted request that completed."""

from lib.stats import median


def read(ctx):
    lat = ctx.latencies()
    return median(lat) if lat else None
