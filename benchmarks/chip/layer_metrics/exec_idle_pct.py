"""Share of the window the executor thread spent waiting for a request
(the ``exec_idle`` stage).  At saturation it must be near 0; where it is
not, the callers do not saturate the chips."""

from lib.profile import stage_total_s


def read(ctx):
    total = stage_total_s(ctx.metrics_window, "exec_idle")
    if total is None:
        return None
    return 100.0 * total / ctx.seconds
