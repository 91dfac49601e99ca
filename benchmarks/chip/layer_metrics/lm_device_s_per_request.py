"""Device seconds of the language model's one program per request: the
mean over its whole executions in the trace slice (prefill of the padded
prompt, then every decode step)."""

from lib.lm_bytes import program_s


def read(ctx):
    return program_s(ctx)
