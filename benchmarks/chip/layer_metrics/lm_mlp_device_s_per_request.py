"""Device seconds of the language model's gated MLPs (the ``lm_mlp``
class: gate, up and down projections and the activation between) per
execution of its program."""

from lib.lm_bytes import class_s


def read(ctx):
    return class_s(ctx, "lm_mlp")
