"""Share of the trace slice in which the device idled between programs
while the executor waited for a request (``exec_idle``): the callers did
not keep the queue fed."""

from lib.host_idle import class_pct


def read(ctx):
    return class_pct(ctx, "wait_request")
