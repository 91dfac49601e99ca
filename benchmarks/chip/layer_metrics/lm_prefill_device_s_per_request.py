"""Device seconds of the language model's PREFILL per request: the
seconds of the generate program's operations under its ``prefill`` scope
in one execution (the program's own trace summary, ``phases``) over the
requests the execution served (``lm.rows`` over ``lm.executions``).
Nothing where the program's scopes carry no phase."""

from lib.lm_bytes import per_request
from lib.lm_swa_moe_bytes import phase_s


def read(ctx):
    return per_request(ctx, "lm_prefill_device_s_per_request",
                       phase_s(ctx, "prefill"))
