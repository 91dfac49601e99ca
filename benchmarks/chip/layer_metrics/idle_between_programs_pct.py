"""Share of the trace slice in which the device idled BETWEEN two
executions or at the slice's edges (``idle_between_s`` of the program's
own summary: the whole the owner table's six rows add up to), mean over
chips.  ``device_idle_pct`` less the gaps inside programs."""

from lib.host_idle import rows
from lib.profile import summary


def read(ctx):
    if rows(ctx) is None:
        return None
    prof = summary(ctx)
    return 100.0 * prof["idle_between_s"] / prof["window_s"]
