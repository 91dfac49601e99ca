"""Share of the trace slice in which the device idled between programs
while the executor was dispatching: idle seconds under a ``dtpu/dispatch``
host span (at any depth; the gaps inside a program's execution are the
program's and are left out) over the traced window, mean over chips."""

from lib.profile import idle_under, summary


def read(ctx):
    idle = idle_under(ctx, "dispatch")
    if idle is None:
        return None
    return 100.0 * idle / summary(ctx)["window_s"]
