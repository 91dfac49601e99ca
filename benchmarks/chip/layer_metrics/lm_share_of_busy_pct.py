"""The language model's share of the device seconds a request takes: its
program's mean execution over that plus every other program's seconds
per request (their totals in the trace slice over the denoise executions
in it, one per request).  Per request, and not per slice: a slice's edge
cuts a two-second execution out whole, which would move a share of the
slice by a quarter."""

import re

from lib.lm_bytes import program_s


def read(ctx):
    lm_s = program_s(ctx)
    denoise = ctx.program("denoise") if lm_s is not None else None
    if lm_s is None or denoise is None:
        return None
    own = re.compile(ctx.config["programs"]["lm_generate"])
    chips = ctx.trace["chips"]
    others = sum(m["total_s"] for c in chips
                 for name, m in c["modules"].items()
                 if not own.search(name)) / len(chips)
    return 100.0 * lm_s / (lm_s + others / denoise["count"])
