"""Mean milliseconds from enqueue to the instant a request's own denoise
could start (the later of its dispatch and the previous request's
device_ready): the program's ``queue_to_device`` stage, the queue delay
measured where it happens."""


def read(ctx):
    row = ctx.stage("queue_to_device")
    if not row or not row["count"]:
        return None
    return 1e3 * row["total_s"] / row["count"]
