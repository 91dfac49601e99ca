"""Share of the trace slice in which the device idled between programs
under NO interval of the executor's: what the program's own timing
cannot answer for.  Near 0, or the owner table has a hole."""

from lib.host_idle import class_pct


def read(ctx):
    return class_pct(ctx, "unowned")
