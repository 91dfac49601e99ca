"""Share of the trace slice in which the device idled between programs
while the executor blocked for the device (``device_wait``,
``lm_drain_wait``): a result on its way back, not work withheld."""

from lib.host_idle import class_pct


def read(ctx):
    return class_pct(ctx, "wait_device")
