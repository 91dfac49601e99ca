"""Device seconds of the convolutions (the ``resblock`` and ``resample``
classes) per whole denoise execution, per image."""

from lib.profile import class_s_per_image


def read(ctx):
    return class_s_per_image(ctx, "resblock", "resample")
