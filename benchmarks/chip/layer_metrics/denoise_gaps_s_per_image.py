"""Seconds of one denoise execution in which NO operation ran on the
device (``account.by_class.idle`` of the program's own trace summary: the
execution's seconds less the union of its operations), per image.  Not
the accepted classes' ``gaps``, which also holds the operations
``classes`` leaves out.  Nothing where the summary has no account."""

from lib.account import IDLE, denoise_s_per_image


def read(ctx):
    return denoise_s_per_image(ctx, "denoise_gaps_s_per_image",
                               lambda by_class: by_class[IDLE])
