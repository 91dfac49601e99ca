"""Wall milliseconds of one decode STEP of the language model, all its
rows, without the prefill: every row of ``account.by_phase.decode`` of the
generate program (its operations' exclusive seconds and the idle
stretches that end in one of them: they add up to the phase's wall
seconds) over the steps an execution takes (``lm.tokens_decoded ÷
lm.rows``).  ``lm_decode_ms_per_token`` divides the whole execution, the
prefill with it.  Nothing where the program's scopes carry no phase or
the summary has no account."""

from lib.account import phase_rows
from lib.lm_bytes import say, served


def read(ctx):
    rows, serves = phase_rows(ctx, "decode"), served(ctx)
    if rows is None or serves is None or not serves["steps"]:
        return None
    decode_s = sum(rows.values())
    value = 1e3 * decode_s / serves["steps"]
    say("lm_decode_step_ms",
        f"{value:.4f} ms a step of {decode_s:.6f} s under `decode` "
        f"({rows.get('idle', 0.0):.6f} idle) in {serves['steps']:.1f} "
        f"steps", serves)
    return value
