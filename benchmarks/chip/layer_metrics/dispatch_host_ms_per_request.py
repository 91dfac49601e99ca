"""The executor's own host milliseconds to dispatch one request: the
``dispatch`` stage (pop to the return of the last enqueue, device waits
taken out) over the window, per request completed."""

from lib.profile import stage_total_s


def read(ctx):
    total = stage_total_s(ctx.metrics_window, "dispatch")
    if total is None or not ctx.completed():
        return None
    return 1e3 * total / len(ctx.completed())
