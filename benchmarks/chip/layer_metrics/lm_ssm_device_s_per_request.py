"""Device seconds of the state-space sub-layers' own work per request:
everything in a Mamba mixer that is no product with a weight (``lm_ssm``:
the convolution, the discretisation, the chunked scan or the state's
step, the ``D`` skip, the gated norm) and the recurrent state's and the
convolution tail's read and write (``lm_state``) in one execution of the
generate program (the program's own trace summary), over the requests
the execution served (``lm.rows`` over ``lm.executions``).  The mixer's
two projections are ``lm_proj``'s, with q / k / v / o.  Nothing where the
summary has no such class (every family but this one)."""

from lib.lm_bytes import class_s, per_request


def read(ctx):
    return per_request(ctx, "lm_ssm_device_s_per_request",
                       class_s(ctx, "lm_ssm", "lm_state") or None)
