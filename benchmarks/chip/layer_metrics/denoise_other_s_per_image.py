"""Seconds of one denoise execution in operations of NO kernel class
(``account.by_class.other`` of the program's own trace summary, exclusive:
an instant has one owner; ``top_other`` names them), per image.  Nothing
where the summary has no account."""

from lib.account import OTHER, denoise_s_per_image


def read(ctx):
    return denoise_s_per_image(ctx, "denoise_other_s_per_image",
                               lambda by_class: by_class.get(OTHER, 0.0))
