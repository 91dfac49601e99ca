"""Host milliseconds to encode and write one PNG:
``pipeline.stages.encode`` over the window, per image completed.  The
``d2h`` stage before it is left out: dispatch is asynchronous, so d2h is
where the finaliser waits for the device (2.6 s of it a request on four
chips), not a host cost."""


def read(ctx):
    enc = ctx.stage("encode")
    images = len(ctx.completed()) * ctx.images_per_request
    if not enc or not images:
        return None
    return 1e3 * enc["total_s"] / images
