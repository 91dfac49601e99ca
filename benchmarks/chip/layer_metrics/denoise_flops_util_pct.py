"""Achieved FLOP/s of the whole denoise program against the chip's bf16
peak: UNet operations from shapes (lib/flops.py) over the program's
device time.  It is a program's utilisation, not a kernel's roofline
share."""

from lib.flops import denoise_flops_per_image


def read(ctx):
    per_image_s = ctx.program_s_per_image("denoise")
    if per_image_s is None or ctx.peaks is None:
        return None
    return 100.0 * denoise_flops_per_image(ctx.config) / per_image_s \
        / ctx.peaks["bf16_flops_per_s"]
