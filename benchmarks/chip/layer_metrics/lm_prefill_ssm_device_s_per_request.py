"""Device seconds of the CHUNKED SCAN per request: ``lm_ssm`` +
``lm_state`` of ``account.by_phase.prefill`` of the generate program
(exclusive seconds, one execution: the convolution over the prompt, the
chunk-local decay matrices and their products, the state carried between
chunks, the gated norm; the state and the tail written once a layer) over
the requests the execution served (``lm.rows`` over ``lm.executions``).
``lm_ssm_device_s_per_request`` holds the decode steps' too.  Nothing
where the program's scopes carry no phase, the summary no account, or the
phase no such class."""

from lib.account import phase_class_s
from lib.lm_bytes import per_request


def read(ctx):
    return per_request(
        ctx, "lm_prefill_ssm_device_s_per_request",
        phase_class_s(ctx, "prefill", "lm_ssm", "lm_state"))
