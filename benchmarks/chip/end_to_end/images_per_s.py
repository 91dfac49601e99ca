"""Images per second at the pace the loop holds: the images of one
request times the mix's ``clients``, over the median seconds from one
completion to the one ``clients`` later (in a closed loop: from a
caller's image to that caller's next), over the requests completed
inside the window.

A median over the whole window and not the count over the window's
length: one pause of the host (80-180 ms, once in some runs and not in
others) moved that quotient by 0.2-0.4% and the driver's sets of six by
up to 0.5%, where the pace between pauses repeats within 0.03% (PERF.md
section 6).  What the pauses cost is on an earlier line and in run.json
(``completed_per_s_whole_window``).  An open loop has no callers: its
step is one completion."""

from lib.stats import median


def read(ctx):
    step = int(ctx.mix.get("clients", 1))
    done = sorted(r["done"] for r in ctx.completed()
                  if r["done"] <= ctx.seconds)
    cycles = [later - earlier for earlier, later in zip(done, done[step:])]
    if not cycles:
        return None
    return step * ctx.images_per_request / median(cycles)
