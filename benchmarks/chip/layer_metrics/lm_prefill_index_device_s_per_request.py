"""Device seconds of the PREFILL's key selection per request:
``lm_index`` of ``account.by_phase.prefill`` of the generate program
(exclusive seconds, one execution: the index projections over the
prompt, a chunk's scores against the index keys up to its end, the
32 + 14 counting passes that find each query's ``topk``-th score and its
ties, the packed record) over the requests the execution served
(``lm.rows`` over ``lm.executions``).  ``lm_index_device_s_per_request``
holds the decode steps' too (their scores, top-k and gather).  Nothing
where the program's scopes carry no phase, the summary no account, or
the phase no such class."""

from lib.account import phase_class_s
from lib.lm_bytes import per_request


def read(ctx):
    return per_request(ctx, "lm_prefill_index_device_s_per_request",
                       phase_class_s(ctx, "prefill", "lm_index"))
