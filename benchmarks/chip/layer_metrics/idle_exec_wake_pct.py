"""Share of the trace slice in which the device idled between programs
while a hand-over from another thread was on its way to the executor
(``wake_drain``, ``wake_queue``: the notifier's stamp to the waiter's
return)."""

from lib.host_idle import class_pct


def read(ctx):
    return class_pct(ctx, "wake")
