"""Seconds of set-up spent making or reading the weights and placing them
on the device: the ``load_weights`` stage at window start."""

from lib.profile import stage_total_s


def read(ctx):
    return stage_total_s(ctx.metrics_setup, "load_weights")
