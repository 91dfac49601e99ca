"""Peak device memory on the fullest chip after the window, from the
allocator's ``memory_stats`` (never host RSS)."""


def read(ctx):
    res = ctx.resource
    if res.get("source") != "memory_stats" or not res["per_device_bytes"]:
        return None
    return max(d[1] for d in res["per_device_bytes"]) / 1e9
