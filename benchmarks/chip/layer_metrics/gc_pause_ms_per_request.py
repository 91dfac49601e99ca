"""Milliseconds the collector stopped every thread (the ``gc_pause``
stage: ``gc.callbacks`` start to stop) over the window, per request
completed."""

from lib.host_idle import stage_ms_per_request


def read(ctx):
    return stage_ms_per_request(ctx, "gc_pause")
