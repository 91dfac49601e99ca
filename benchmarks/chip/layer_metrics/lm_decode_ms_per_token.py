"""Device milliseconds per decoded token: the generate program's mean
execution over the tokens one execution decodes (the program's counter
``lm.tokens_decoded`` over its executions in the window).  The prefill
is inside it: one program, one number."""

from lib.lm_bytes import per_request, program_s


def read(ctx):
    seconds, tokens = program_s(ctx), per_request(ctx, "lm.tokens_decoded")
    if seconds is None or not tokens:
        return None
    return 1e3 * seconds / tokens
