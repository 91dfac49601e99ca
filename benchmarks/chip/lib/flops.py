"""Operations a request needs, computed from shapes: the count the
algorithm requires, not what a compiler emitted.

``unet_forward_flops`` walks the latent-diffusion UNet (Rombach et al.;
the ``UNetModel`` of CompVis/stable-diffusion and Stability-AI/
generative-models) by its published widths and counts two operations per
multiply-accumulate of every convolution, projection and attention
product.  Normalisations, activations, the softmax and the timestep MLP
are left out: they are well under 1% of the total.
"""

from __future__ import annotations

# model evaluations per sampler step
SAMPLER_EVALS = {"euler": 1, "euler_ancestral": 1, "dpmpp_2m": 1,
                 "ddim": 1, "heun": 2, "dpmpp_sde": 2}


def _conv(h, w, cin, cout, k=3):
    return 2 * h * w * cin * cout * k * k


def _resblock(h, w, cin, cout, emb):
    f = _conv(h, w, cin, cout) + _conv(h, w, cout, cout) + 2 * emb * cout
    if cin != cout:
        f += _conv(h, w, cin, cout, k=1)      # the skip's 1x1 projection
    return f


def _transformer(tokens, c, depth, ctx_len, ctx_dim):
    block = 0
    # self-attention: q, k, v, out projections; QK^T and PV
    block += 4 * 2 * tokens * c * c + 2 * 2 * tokens * tokens * c
    # cross-attention: q and out from the image, k and v from the text
    block += 2 * 2 * tokens * c * c + 2 * 2 * ctx_len * ctx_dim * c \
        + 2 * 2 * tokens * ctx_len * c
    # GEGLU feed-forward: c -> 8c, then 4c -> c
    block += 2 * tokens * c * 8 * c + 2 * tokens * 4 * c * c
    return depth * block + 2 * 2 * tokens * c * c    # proj_in, proj_out


def unet_forward_flops(unet: dict, latent_h: int, latent_w: int,
                       ctx_len: int = 77) -> int:
    """One forward pass of the UNet for one sample (one CFG row)."""
    ch = int(unet["model_channels"])
    mult = [int(m) for m in unet["channel_mult"]]
    depth = [int(d) for d in unet["transformer_depth"]]
    n_res = int(unet["num_res_blocks"])
    ctx_dim = int(unet["context_dim"])
    mid = unet.get("transformer_depth_middle")
    mid_depth = int(mid) if mid is not None else max(depth[-1], 1)
    emb = 4 * ch
    h, w = latent_h, latent_w
    total = _conv(h, w, int(unet["in_channels"]), ch)
    skips = [ch]
    c = ch
    for level, m in enumerate(mult):
        out = ch * m
        for _ in range(n_res):
            total += _resblock(h, w, c, out, emb)
            c = out
            if depth[level]:
                total += _transformer(h * w, c, depth[level], ctx_len,
                                      ctx_dim)
            skips.append(c)
        if level != len(mult) - 1:
            h, w = h // 2, w // 2
            total += _conv(h, w, c, c)         # stride-2 downsample
            skips.append(c)
    total += 2 * _resblock(h, w, c, c, emb)
    total += _transformer(h * w, c, mid_depth, ctx_len, ctx_dim)
    for level in reversed(range(len(mult))):
        out = ch * mult[level]
        for _ in range(n_res + 1):
            total += _resblock(h, w, c + skips.pop(), out, emb)
            c = out
            if depth[level]:
                total += _transformer(h * w, c, depth[level], ctx_len,
                                      ctx_dim)
        if level != 0:
            h, w = h * 2, w * 2
            total += _conv(h, w, c, c)         # nearest upsample + conv
    total += _conv(h, w, c, int(unet["out_channels"]))
    return int(total)


def request_shape(graph: dict) -> dict:
    """Width, height, batch, steps, CFG and sampler of a txt2img graph:
    read from its one EmptyLatentImage and its one KSampler."""
    def only(class_type):
        nodes = [n for n in graph.values()
                 if isinstance(n, dict) and n.get("class_type") == class_type]
        if len(nodes) != 1:
            raise ValueError(f"graph has {len(nodes)} {class_type} nodes; "
                             f"this reader wants exactly one")
        return nodes[0]["inputs"]
    lat, ks = only("EmptyLatentImage"), only("KSampler")
    return {"width": int(lat["width"]), "height": int(lat["height"]),
            "batch_size": int(lat["batch_size"]), "steps": int(ks["steps"]),
            "cfg": float(ks["cfg"]), "sampler_name": ks["sampler_name"]}


def denoise_flops_per_image(config: dict) -> int:
    """UNet operations to denoise one image of the configuration's
    request: forward passes x CFG rows x steps."""
    shape = request_shape(config["graph"])
    if shape["sampler_name"] not in SAMPLER_EVALS:
        raise ValueError(f"no evaluation count for sampler "
                         f"{shape['sampler_name']!r}")
    rows = 1 if shape["cfg"] == 1.0 else 2
    fwd = unet_forward_flops(config["unet"], shape["height"] // 8,
                             shape["width"] // 8)
    return fwd * rows * shape["steps"] * SAMPLER_EVALS[shape["sampler_name"]]
