"""Device seconds of the transformer's matmuls outside attention proper
(the ``attn_proj`` and ``ff`` classes) per whole denoise execution, per
image."""

from lib.profile import class_s_per_image


def read(ctx):
    return class_s_per_image(ctx, "attn_proj", "ff")
