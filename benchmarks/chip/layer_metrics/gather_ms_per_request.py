"""Host milliseconds DistributedCollector spends bringing the replicas'
images together (the ``gather`` stage), per request completed."""

from lib.profile import stage_total_s


def read(ctx):
    total = stage_total_s(ctx.metrics_window, "gather")
    if total is None or not ctx.completed():
        return None
    return 1e3 * total / len(ctx.completed())
