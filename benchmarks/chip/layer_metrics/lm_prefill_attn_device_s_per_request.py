"""Device seconds of the attention sub-layers of the language model's
PREFILL per request: ``lm_proj`` + ``lm_attn`` + ``lm_cache`` of
``account.by_phase.prefill`` of the generate program (exclusive seconds,
one execution) over the requests the execution served (``lm.rows`` over
``lm.executions``).  ``lm_attn_device_s_per_request`` holds the decode's
too.  Nothing where the program's scopes carry no phase or the summary
has no account."""

from lib.account import phase_class_s
from lib.lm_bytes import per_request


def read(ctx):
    return per_request(
        ctx, "lm_prefill_attn_device_s_per_request",
        phase_class_s(ctx, "prefill", "lm_proj", "lm_attn", "lm_cache"))
