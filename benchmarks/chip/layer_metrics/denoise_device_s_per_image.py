"""Device seconds of the denoise program per image: its whole executions
in the trace slice, their mean duration over the images one execution
denoises on a chip (the request's batch_size)."""


def read(ctx):
    return ctx.program_s_per_image("denoise")
