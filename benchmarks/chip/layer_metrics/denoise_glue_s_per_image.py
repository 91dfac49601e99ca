"""Seconds of one denoise execution in what it runs that is no attention,
matmul, convolution or norm: ``sampler`` (the solver's and the CFG's own
operations), ``embed`` (the timestep and label embeddings) and every class
named since beside the seven the four accepted readers sum
(``account.by_class`` of the program's own trace summary, exclusive), per
image.  Nothing where the summary has no account."""

from lib.account import denoise_s_per_image, glue_s


def read(ctx):
    return denoise_s_per_image(ctx, "denoise_glue_s_per_image", glue_s)
