"""Device seconds of the gated memory units' own work per request: a
``gmu`` layer's gate with the memory another layer's scan made
(``lm_gmu``: ``m * silu(.)``, nothing else) in one execution of the
generate program (the program's own trace summary), over the requests
the execution served (``lm.rows`` over ``lm.executions``).  The unit's
two projections are ``lm_proj``'s, with q / k / v / o.  Nothing where the
summary has no second in such a class (every family but this one)."""

from lib.lm_bytes import class_s, per_request


def read(ctx):
    return per_request(ctx, "lm_gmu_device_s_per_request",
                       class_s(ctx, "lm_gmu") or None)
