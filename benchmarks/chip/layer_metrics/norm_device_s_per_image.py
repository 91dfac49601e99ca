"""Device seconds of GroupNorm and LayerNorm (the ``norm`` class) per
whole denoise execution, per image."""

from lib.profile import class_s_per_image


def read(ctx):
    return class_s_per_image(ctx, "norm")
