"""Device seconds of the language model's attention per execution of its
program: the q/k/v/o projections (``lm_proj``), attention proper with the
rotary embedding (``lm_attn``) and the cache's update and read
(``lm_cache``)."""

from lib.lm_bytes import class_s


def read(ctx):
    return class_s(ctx, "lm_proj", "lm_attn", "lm_cache")
