"""Device milliseconds of the text-encoder programs per request: their
total in the trace slice over the denoise executions in it (one per
request)."""


def read(ctx):
    clip, denoise = ctx.program("text_encode"), ctx.program("denoise")
    if clip is None or denoise is None:
        return None
    return 1e3 * clip["total_s"] / denoise["count"]
