"""Median time to image below the knee: what ``tti_p50_s`` is at
saturation, as a per-layer metric of the queue.  With arrivals at fixed
instants the 68 latencies lie 5-40 ms apart around their middle, so the
median is one or two requests' own reading, each good to the poller's
tick (10 ms): the driver read spreads of 0.3% and 0.8% where the
saturated cells stay under 0.05%.  ISSUE 22's rule for that case: the
cell reports it here, without a bound, so that one cell does not loosen
the bound of the other three."""

from lib.stats import median


def read(ctx):
    lat = ctx.latencies()
    return median(lat) if lat else None
