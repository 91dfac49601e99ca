"""Share of the trace slice in which the device idled between programs
while the executor thread ran its own code (``dispatch``, a node's span,
``detokenize``: any interval of its that is no wait)."""

from lib.host_idle import class_pct


def read(ctx):
    return class_pct(ctx, "host")
