"""Device seconds of attention proper (QK^T, softmax, PV: the ``attn_self``
and ``attn_cross`` classes) per whole denoise execution, per image."""

from lib.profile import class_s_per_image


def read(ctx):
    return class_s_per_image(ctx, "attn_self", "attn_cross")
