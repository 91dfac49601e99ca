"""Attention's share of its roofline: the least seconds QK^T, softmax and
PV of every attention call of one denoise can take on the chip (the
larger of operations over the bf16 peak and least bytes over the HBM
peak, from shapes: ``lib/kernels.py``) over the device seconds the
``attn_self`` and ``attn_cross`` classes took.  Prints which bound
applies."""

from lib.kernels import attention_bound
from lib.profile import class_s_per_image


def read(ctx):
    seconds = class_s_per_image(ctx, "attn_self", "attn_cross")
    if seconds is None or ctx.peaks is None:
        return None
    bound = attention_bound(ctx.config, ctx.peaks)
    print(f"[chipbench] attn_roofline_pct: {bound['bound']}-bound "
          f"({bound['ops'] / 1e12:.3f} TFLOP -> {bound['compute_s']:.4f} s, "
          f"{bound['bytes'] / 1e9:.3f} GB -> {bound['memory_s']:.4f} s) "
          f"against {seconds:.4f} s measured", flush=True)
    return 100.0 * bound["seconds"] / seconds
