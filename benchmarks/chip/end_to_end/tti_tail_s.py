"""Time to image in the tail: the mean over the requests beyond the
percentile the traffic file fixes (``tail_percentile``: the highest of
80, 90, 95 that leaves ten samples beyond it at the cell's rate; at p80
the slowest 13 of 68).

The mean beyond the percentile and not the percentile itself: with
arrivals at fixed instants the latencies are fixed too, some tens of
milliseconds apart in the tail, and each is read to the poller's tick,
so the percentile is one or two requests' own reading and its spread is
theirs (the median, read the same way, spread 0.8% in one of the
driver's sets).  The mean beyond it is a tail a user feels as well, and
averages those readings (PERF.md section 6)."""

import math


def read(ctx):
    lat = sorted(ctx.latencies())
    if not lat:
        return None
    first = math.ceil(len(lat) * float(ctx.mix["tail_percentile"]) / 100.0)
    beyond = lat[min(first, len(lat) - 1):]
    return sum(beyond) / len(beyond)
