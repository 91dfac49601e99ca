"""Device seconds of the zero-compute experts' work per request: the
identity experts' one scaled add of the expert layer's input (class
``lm_zero``: which of a token's choices are zero experts, the sum of
their weights, times the input; no product, no gather) in one execution
of the generate program (the program's own trace summary), over the
requests the execution served (``lm.rows`` over ``lm.executions``): what
"zero-compute" costs the device.  Nothing where the summary has no second
in such a class (every family but this one, and the parent)."""

from lib.lm_bytes import class_s, per_request


def read(ctx):
    return per_request(ctx, "lm_zero_device_s_per_request",
                       class_s(ctx, "lm_zero") or None)
