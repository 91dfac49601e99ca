"""Device seconds of the VAE decode program per image."""


def read(ctx):
    return ctx.program_s_per_image("vae_decode")
