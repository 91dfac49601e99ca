"""Device seconds of the cross layers' attention per request: a ``cross``
layer's two maps a head pair over the cache ANOTHER layer wrote, its read
of that cache with it (``lm_cross``) in one execution of the generate
program (the program's own trace summary), over the requests the
execution served (``lm.rows`` over ``lm.executions``).  The layer's query
and output projections are ``lm_proj``'s; the layer that writes the cache
attends to it under ``lm_attn``.  Nothing where the summary has no second
in such a class (every family but this one)."""

from lib.lm_bytes import class_s, per_request


def read(ctx):
    return per_request(ctx, "lm_cross_device_s_per_request",
                       class_s(ctx, "lm_cross") or None)
