"""Device seconds of what routing adds to the language model's PREFILL
per request: ``lm_experts`` of ``account.by_phase.prefill`` of the
generate program (exclusive seconds, one execution) over the requests the
execution served.  ``lm_experts_device_s_per_request`` holds the decode's
too.  Nothing where the program's scopes carry no phase, the summary has
no account, or the prefill no second in such a class (a model without
experts)."""

from lib.account import phase_class_s
from lib.lm_bytes import per_request


def read(ctx):
    return per_request(ctx, "lm_prefill_experts_device_s_per_request",
                       phase_class_s(ctx, "prefill", "lm_experts"))
