"""Host milliseconds of a request's life that no device wait is charged
to: ``pipeline.stages.job_e2e`` minus ``queue_wait``, ``compute`` and
``d2h``, per request.  What is left is the finaliser's own work: PNG
encoding, the history write, bookkeeping.  ``compute`` and ``d2h`` are
left out because dispatch is asynchronous: whichever of them first blocks
waits for the device to finish the request (0.54 s + 0.61 s of a 0.61 s
denoise cycle), so a kernel moves them and a dispatch change cannot."""


def read(ctx):
    e2e, wait, comp, d2h = (ctx.stage(k) for k in
                            ("job_e2e", "queue_wait", "compute", "d2h"))
    if not e2e or not e2e["count"] or not comp or not d2h:
        return None
    waited = wait["total_s"] if wait else 0.0
    return 1e3 * (e2e["total_s"] - waited - comp["total_s"]
                  - d2h["total_s"]) / e2e["count"]
