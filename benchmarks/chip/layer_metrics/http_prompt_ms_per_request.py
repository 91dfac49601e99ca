"""Milliseconds of the ``POST /prompt`` handler, from the body's read to
the response (the ``http_prompt`` stage, on the event loop's thread),
over the window, per request completed."""

from lib.host_idle import stage_ms_per_request


def read(ctx):
    return stage_ms_per_request(ctx, "http_prompt")
