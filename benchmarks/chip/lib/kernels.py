"""Operations and least bytes of single kernels, computed from shapes:
what the algorithm requires, not what a compiler emitted.

``attention_calls`` walks the latent-diffusion UNet by its published
widths, as ``flops.unet_forward_flops`` does, and lists every attention
call of one forward pass.  ``attention_cost`` counts for one call what
the attention kernels compute (QK^T, softmax, PV; the q/k/v and output
projections are ``attn_proj``'s):

* operations: two per multiply-accumulate of QK^T and of PV, and
  SOFTMAX_OPS per score (subtract the row maximum, exponentiate, add to
  the row sum, divide, scale);
* least bytes: q, k and v read once and the output written once, in the
  compute precision (bf16); scores never leave the chip.
"""

from __future__ import annotations

from .flops import SAMPLER_EVALS, request_shape

SOFTMAX_OPS = 5
BYTES_PER_VALUE = 2         # bf16


def attention_calls(unet: dict, latent_h: int, latent_w: int,
                    ctx_len: int = 77) -> list[dict]:
    """One entry per attention call of a forward pass for one sample:
    ``kind`` (self / cross), query and key tokens, heads, head dim."""
    ch = int(unet["model_channels"])
    mult = [int(m) for m in unet["channel_mult"]]
    depth = [int(d) for d in unet["transformer_depth"]]
    n_res = int(unet["num_res_blocks"])
    mid = unet.get("transformer_depth_middle")
    mid_depth = int(mid) if mid is not None else max(depth[-1], 1)

    def heads(c):
        if unet.get("num_heads"):
            return int(unet["num_heads"]), c // int(unet["num_heads"])
        d = int(unet["num_head_channels"])
        return max(c // d, 1), d

    calls = []

    def transformer(tokens, c, blocks):
        h, d = heads(c)
        for _ in range(blocks):
            calls.append({"kind": "self", "q": tokens, "kv": tokens,
                          "heads": h, "head_dim": d})
            calls.append({"kind": "cross", "q": tokens, "kv": ctx_len,
                          "heads": h, "head_dim": d})

    h, w = latent_h, latent_w
    for level, m in enumerate(mult):
        for _ in range(n_res):
            transformer(h * w, ch * m, depth[level])
        if level != len(mult) - 1:
            h, w = h // 2, w // 2
    transformer(h * w, ch * mult[-1], mid_depth)
    for level in reversed(range(len(mult))):
        for _ in range(n_res + 1):
            transformer(h * w, ch * mult[level], depth[level])
        if level != 0:
            h, w = h * 2, w * 2
    return calls


def attention_cost(call: dict, rows: int = 1) -> tuple[int, int]:
    """(operations, least bytes) of one attention call over ``rows``
    samples."""
    scores = rows * call["heads"] * call["q"] * call["kv"]
    ops = 4 * scores * call["head_dim"] + SOFTMAX_OPS * scores
    values = rows * call["heads"] * call["head_dim"] \
        * (2 * call["q"] + 2 * call["kv"])
    return ops, BYTES_PER_VALUE * values


def denoise_attention_per_image(config: dict) -> dict:
    """Attention operations and least bytes to denoise one image of the
    configuration's request (forward passes x CFG rows x steps), and the
    seconds each needs at a chip's peaks: see ``attention_bound``."""
    shape = request_shape(config["graph"])
    rows = 1 if shape["cfg"] == 1.0 else 2
    passes = shape["steps"] * SAMPLER_EVALS[shape["sampler_name"]]
    ops = nbytes = 0
    for call in attention_calls(config["unet"], shape["height"] // 8,
                                shape["width"] // 8):
        o, b = attention_cost(call, rows)
        ops += o
        nbytes += b
    return {"ops": ops * passes, "bytes": nbytes * passes}


def attention_bound(config: dict, peaks: dict) -> dict:
    """The least seconds one image's attention can take on a chip with
    these peaks, and which peak sets it (``compute`` or ``memory``)."""
    cost = denoise_attention_per_image(config)
    compute_s = cost["ops"] / peaks["bf16_flops_per_s"]
    memory_s = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {**cost, "compute_s": compute_s, "memory_s": memory_s,
            "bound": "compute" if compute_s >= memory_s else "memory",
            "seconds": max(compute_s, memory_s)}
