"""Standard workflow ops: loaders, conditioning, latents, sampling, images.

Schemas mirror ComfyUI node surfaces used by the reference workflows
(``workflows/distributed-txt2img.json``, ``distributed-upscale.json``).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import threading
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.ops.base import (
    CBCapture,
    CONTROL,
    Conditioning,
    DeviceImage,
    DeviceLatent,
    DeviceTensor,
    Op,
    OpContext,
    SeedValue,
    as_device_array,
    as_device_image,
    as_image_array,
    fetch_image_array,
    register_op,
)
from comfyui_distributed_tpu.parallel import collectives as coll
from comfyui_distributed_tpu.utils.image import (
    pil_to_tensor,
    resize_image,
    tensor_to_pil,
)
from comfyui_distributed_tpu.utils import trace as trace_mod
from comfyui_distributed_tpu.utils.logging import Timer, debug_log, log


@register_op
class CheckpointLoaderSimple(Op):
    """-> (MODEL, CLIP, VAE); all three views of one DiffusionPipeline."""
    TYPE = "CheckpointLoaderSimple"
    WIDGETS = ["ckpt_name"]

    def execute(self, ctx: OpContext, ckpt_name: str):
        pipe = registry.load_pipeline(ckpt_name, models_dir=ctx.models_dir)
        return (pipe, pipe, pipe)


@register_op
class LoraLoader(Op):
    """ComfyUI's LoraLoader: merge a kohya-format LoRA into the UNet and
    text-encoder weights at the given strengths.  Returns a patched
    (MODEL, CLIP) pair; the base pipeline stays untouched and patched
    pipelines are cached so repeat runs reuse compiled executables."""
    TYPE = "LoraLoader"
    WIDGETS = ["lora_name", "strength_model", "strength_clip"]
    DEFAULTS = {"strength_model": 1.0, "strength_clip": 1.0}

    def execute(self, ctx: OpContext, model, clip, lora_name: str,
                strength_model: float = 1.0, strength_clip: float = 1.0):
        from comfyui_distributed_tpu.models.lora import apply_lora_to_pipeline
        sm, sc = float(strength_model), float(strength_clip)
        name = str(lora_name)
        if sm == 0.0 and sc == 0.0:
            return (model, clip)
        if model is clip:
            patched = apply_lora_to_pipeline(model, name, sm, sc,
                                             models_dir=ctx.models_dir)
            return (patched, patched)
        # MODEL and CLIP wired from different checkpoints: patch each
        # independently, like ComfyUI's loader
        m2 = apply_lora_to_pipeline(model, name, sm, 0.0,
                                    models_dir=ctx.models_dir) \
            if sm != 0.0 else model
        c2 = apply_lora_to_pipeline(clip, name, 0.0, sc,
                                    models_dir=ctx.models_dir) \
            if sc != 0.0 else clip
        return (m2, c2)


def _freeu_pipeline(model, version: int, b1: float, b2: float,
                    s1: float, s2: float):
    """MODEL -> derived pipeline with FreeU decoder re-weighting baked
    into the (static) UNet config; params shared with the base."""
    fam = model.family
    fam2 = dataclasses.replace(fam, unet=dataclasses.replace(
        fam.unet, freeu=(float(b1), float(b2), float(s1), float(s2)),
        freeu_version=int(version)))
    tag = f"freeu{version}:{b1}:{b2}:{s1}:{s2}"
    return registry.derive_pipeline(model, tag, family=fam2)


@register_op
class RescaleCFG(Op):
    """RescaleCFG: re-std the CFG combination toward the cond
    prediction's v-space statistics (multiplier-blended) — the standard
    fix for high-CFG over-saturation, essential on v-prediction (sd21)
    models.  Derived pipeline; the patch rides further derivations."""
    TYPE = "RescaleCFG"
    WIDGETS = ["multiplier"]
    DEFAULTS = {"multiplier": 0.7}

    def execute(self, ctx: OpContext, model, multiplier: float = 0.7):
        m = float(multiplier)
        if m == 0.0:
            return (model,)
        return (registry.derive_pipeline(model, f"rescale:{m}",
                                         cfg_rescale=m),)


def _merge_trees(t1, t2, ratio_of_key):
    """Per-leaf lerp of two structurally-equal param trees:
    ``out = a * r + b * (1 - r)`` with r from the leaf's tree path."""
    import jax

    def leaf(path, a, b):
        key = jax.tree_util.keystr(path)
        r = float(ratio_of_key(key))
        return (jnp.asarray(a, jnp.float32) * r
                + jnp.asarray(b, jnp.float32) * (1.0 - r)) \
            .astype(jnp.asarray(a).dtype)

    return jax.tree_util.tree_map_with_path(leaf, t1, t2)


@register_op
class ModelMergeSimple(Op):
    """Weight-space lerp of two same-family UNets:
    ``model1 * ratio + model2 * (1 - ratio)`` (the reference ecosystem's
    merge node)."""
    TYPE = "ModelMergeSimple"
    WIDGETS = ["ratio"]
    DEFAULTS = {"ratio": 1.0}

    def execute(self, ctx: OpContext, model1, model2,
                ratio: float = 1.0):
        if model1.family.unet != model2.family.unet:
            raise ValueError("ModelMergeSimple: UNet configs differ "
                             f"({model1.family.name} vs "
                             f"{model2.family.name})")
        tag = f"merge:{model2.cache_token}:{float(ratio)}"
        cached = registry.derived_cached(model1, tag)
        if cached is not None:      # don't redo a gigabyte-scale lerp
            return (cached,)
        merged = _merge_trees(model1.unet_params, model2.unet_params,
                              lambda _k: float(ratio))
        return (registry.derive_pipeline(model1, tag,
                                         unet_params=merged),)


def _arith_trees(t1, t2, fn):
    """Per-leaf arithmetic of two structurally-equal param trees in
    fp32, cast back to the first tree's dtype."""
    import jax

    def leaf(a, b):
        return fn(jnp.asarray(a, jnp.float32),
                  jnp.asarray(b, jnp.float32)) \
            .astype(jnp.asarray(a).dtype)

    return jax.tree_util.tree_map(leaf, t1, t2)


@register_op
class ModelMergeAdd(Op):
    """Weight-space sum ``model1 + model2`` — the "add difference"
    workflow's second half (apply a ModelMergeSubtract delta onto a
    base)."""
    TYPE = "ModelMergeAdd"

    def execute(self, ctx: OpContext, model1, model2):
        if model1.family.unet != model2.family.unet:
            raise ValueError("ModelMergeAdd: UNet configs differ "
                             f"({model1.family.name} vs "
                             f"{model2.family.name})")
        tag = f"merge_add:{model2.cache_token}"
        cached = registry.derived_cached(model1, tag)
        if cached is not None:
            return (cached,)
        merged = _arith_trees(model1.unet_params, model2.unet_params,
                              lambda a, b: a + b)
        return (registry.derive_pipeline(model1, tag,
                                         unet_params=merged),)


@register_op
class ModelMergeSubtract(Op):
    """Weight-space difference ``model1 - multiplier * model2`` — the
    "add difference" workflow's delta extraction."""
    TYPE = "ModelMergeSubtract"
    WIDGETS = ["multiplier"]
    DEFAULTS = {"multiplier": 1.0}

    def execute(self, ctx: OpContext, model1, model2,
                multiplier: float = 1.0):
        if model1.family.unet != model2.family.unet:
            raise ValueError("ModelMergeSubtract: UNet configs differ "
                             f"({model1.family.name} vs "
                             f"{model2.family.name})")
        m = float(multiplier)
        tag = f"merge_sub:{model2.cache_token}:{m}"
        cached = registry.derived_cached(model1, tag)
        if cached is not None:
            return (cached,)
        merged = _arith_trees(model1.unet_params, model2.unet_params,
                              lambda a, b: a - m * b)
        return (registry.derive_pipeline(model1, tag,
                                         unet_params=merged),)


@register_op
class ModelMergeBlocks(Op):
    """Per-section merge ratios (the reference's input/middle/out block
    split): encoder + time/label embeds use ``input``, the mid block
    ``middle``, decoder + output head ``out``."""
    TYPE = "ModelMergeBlocks"
    WIDGETS = ["input", "middle", "out"]
    DEFAULTS = {"input": 1.0, "middle": 1.0, "out": 1.0}

    def execute(self, ctx: OpContext, model1, model2, input: float = 1.0,
                middle: float = 1.0, out: float = 1.0):
        if model1.family.unet != model2.family.unet:
            raise ValueError("ModelMergeBlocks: UNet configs differ")

        def ratio_of(key: str) -> float:
            # anchor on the TOP-LEVEL tree key: ResBlocks contain an
            # inner 'out_norm' GroupNorm, so substring matching would
            # misroute encoder norms into the 'out' section
            if key.startswith("['mid_"):
                return float(middle)
            if (key.startswith("['up_") or key.startswith("['out_norm'")
                    or key.startswith("['conv_out'")):
                return float(out)
            return float(input)     # down_/conv_in/time_/label_

        tag = f"mergeb:{model2.cache_token}:{input}:{middle}:{out}"
        cached = registry.derived_cached(model1, tag)
        if cached is not None:
            return (cached,)
        merged = _merge_trees(model1.unet_params, model2.unet_params,
                              ratio_of)
        return (registry.derive_pipeline(model1, tag,
                                         unet_params=merged),)


@register_op
class CLIPMergeSimple(Op):
    TYPE = "CLIPMergeSimple"
    WIDGETS = ["ratio"]
    DEFAULTS = {"ratio": 1.0}

    def execute(self, ctx: OpContext, clip1, clip2, ratio: float = 1.0):
        if len(clip1.clip_params) != len(clip2.clip_params):
            raise ValueError("CLIPMergeSimple: tower counts differ")
        tag = f"clipmerge:{clip2.cache_token}:{float(ratio)}"
        cached = registry.derived_cached(clip1, tag)
        if cached is not None:
            return (cached,)
        merged = [_merge_trees(a, b, lambda _k: float(ratio))
                  for a, b in zip(clip1.clip_params, clip2.clip_params)]
        return (registry.derive_pipeline(clip1, tag,
                                         clip_params=merged),)


@register_op
class CLIPMergeAdd(Op):
    """Weight-space sum of two text towers (the add-difference pair's
    second half on the CLIP side)."""
    TYPE = "CLIPMergeAdd"

    def execute(self, ctx: OpContext, clip1, clip2):
        if len(clip1.clip_params) != len(clip2.clip_params):
            raise ValueError("CLIPMergeAdd: tower counts differ")
        tag = f"clipmerge_add:{clip2.cache_token}"
        cached = registry.derived_cached(clip1, tag)
        if cached is not None:
            return (cached,)
        merged = [_arith_trees(a, b, lambda x, y: x + y)
                  for a, b in zip(clip1.clip_params, clip2.clip_params)]
        return (registry.derive_pipeline(clip1, tag,
                                         clip_params=merged),)


@register_op
class CLIPMergeSubtract(Op):
    """Weight-space difference ``clip1 - multiplier * clip2``."""
    TYPE = "CLIPMergeSubtract"
    WIDGETS = ["multiplier"]
    DEFAULTS = {"multiplier": 1.0}

    def execute(self, ctx: OpContext, clip1, clip2,
                multiplier: float = 1.0):
        if len(clip1.clip_params) != len(clip2.clip_params):
            raise ValueError("CLIPMergeSubtract: tower counts differ")
        m = float(multiplier)
        tag = f"clipmerge_sub:{clip2.cache_token}:{m}"
        cached = registry.derived_cached(clip1, tag)
        if cached is not None:
            return (cached,)
        merged = [_arith_trees(a, b, lambda x, y: x - m * y)
                  for a, b in zip(clip1.clip_params, clip2.clip_params)]
        return (registry.derive_pipeline(clip1, tag,
                                         clip_params=merged),)


@register_op
class LoraLoaderModelOnly(Op):
    """LoraLoader that patches the UNet only (the CLIP stays wired to
    the base)."""
    TYPE = "LoraLoaderModelOnly"
    WIDGETS = ["lora_name", "strength_model"]
    DEFAULTS = {"strength_model": 1.0}

    def execute(self, ctx: OpContext, model, lora_name: str,
                strength_model: float = 1.0):
        from comfyui_distributed_tpu.models.lora import \
            apply_lora_to_pipeline
        sm = float(strength_model)
        if sm == 0.0:
            return (model,)
        return (apply_lora_to_pipeline(model, str(lora_name), sm, 0.0,
                                       models_dir=ctx.models_dir),)


@register_op
class VAESave(Op):
    """Export a VAE as a standalone bare-key safetensors (loads back via
    VAELoader and in the reference ecosystem)."""
    TYPE = "VAESave"
    OUTPUT_NODE = True
    WIDGETS = ["filename_prefix"]
    DEFAULTS = {"filename_prefix": "vae/save"}

    def execute(self, ctx: OpContext, vae,
                filename_prefix: str = "vae/save"):
        from comfyui_distributed_tpu.models.checkpoints import (
            _ExportMapper, _run_vae, save_state_dict)
        path = _safe_output_path(ctx.output_dir or os.getcwd(),
                                 f"{filename_prefix}.safetensors")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        sd = _run_vae(_ExportMapper(vae.vae_params, ""), vae.family.vae)
        save_state_dict(sd, path)
        debug_log(f"VAESave: wrote {path}")
        return ()


@register_op
class CLIPSave(Op):
    """Export the text encoder tower(s) with their in-checkpoint
    prefixes (round-trips through this framework's converter)."""
    TYPE = "CLIPSave"
    OUTPUT_NODE = True
    WIDGETS = ["filename_prefix"]
    DEFAULTS = {"filename_prefix": "clip/save"}

    def execute(self, ctx: OpContext, clip,
                filename_prefix: str = "clip/save"):
        from comfyui_distributed_tpu.models.checkpoints import (
            _ExportMapper, _clip_prefixes, _clip_runner, save_state_dict)
        path = _safe_output_path(ctx.output_dir or os.getcwd(),
                                 f"{filename_prefix}.safetensors")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        sd = {}
        for ccfg, tree, prefix in zip(clip.family.clips,
                                      clip.clip_params,
                                      _clip_prefixes(clip.family)):
            sd.update(_clip_runner(ccfg)(_ExportMapper(tree, prefix),
                                         ccfg))
        save_state_dict(sd, path)
        debug_log(f"CLIPSave: wrote {path}")
        return ()


@register_op
class ModelSamplingDiscrete(Op):
    """ComfyUI's ModelSamplingDiscrete: re-declare how the model's
    output parameterizes the denoised sample (eps / v_prediction / x0 —
    v-pred finetunes of eps bases) and optionally rescale the schedule
    to zero terminal SNR.  Derived pipeline; patch rides further
    derivations (LoRA/clip-skip)."""
    TYPE = "ModelSamplingDiscrete"
    WIDGETS = ["sampling", "zsnr"]
    DEFAULTS = {"sampling": "eps", "zsnr": False}

    _MAP = {"eps": "eps", "v_prediction": "v", "x0": "x0",
            "lcm": "eps"}

    def execute(self, ctx: OpContext, model, sampling: str = "eps",
                zsnr=False):
        from comfyui_distributed_tpu.models import schedules as sch
        s = str(sampling)
        if s not in self._MAP:
            raise ValueError(f"unknown sampling {s!r}; "
                             f"available: {tuple(self._MAP)}")
        if s == "lcm":
            debug_log("ModelSamplingDiscrete: 'lcm' timestep scaling is "
                      "not modeled; treating as eps (use the lcm "
                      "sampler for LCM checkpoints)")
        z = str(zsnr).lower() not in ("false", "0", "")
        schedule = sch.rescale_zero_terminal_snr(model.schedule) if z \
            else None
        return (registry.derive_pipeline(
            model, f"msd:{s}:{int(z)}",
            prediction_type=self._MAP[s], schedule=schedule),)


@register_op
class GLIGENLoader(Op):
    """-> GLIGEN (models/gligen.py position net).  Applying it to a
    model happens implicitly at GLIGENTextBoxApply time via
    gligen_attach (the fuser weights graft into the UNet tree)."""
    TYPE = "GLIGENLoader"
    WIDGETS = ["gligen_name"]

    def execute(self, ctx: OpContext, gligen_name: str):
        from comfyui_distributed_tpu.models.gligen import load_gligen
        return (load_gligen(str(gligen_name),
                            models_dir=ctx.models_dir),)


def gligen_attach(model, gligen) -> object:
    """Derived pipeline with GLIGEN fusers: the gligen-enabled UNet's
    missing parameters (the fusers) virtual-initialize and the base
    checkpoint's weights graft over every shared key — trained weights
    stay bit-exact, only grounding-specific params are synthesized."""
    from comfyui_distributed_tpu.models import unet as unet_mod
    tag = f"gligen:{gligen.name}"
    cached = registry.derived_cached(model, tag)
    if cached is not None:
        return cached
    fam = model.family
    fam2 = dataclasses.replace(fam, unet=dataclasses.replace(
        fam.unet, gligen=int(gligen.cfg.out_dim)))
    ds = fam.vae.downscale
    h = w = 8 * ds
    import jax as _jax
    mod2 = unet_mod.UNet(fam2.unet)
    # synthesize ONLY the leaves missing from the base tree (the
    # fusers): eval_shape traces without compiling, and present leaves
    # reuse the base checkpoint's arrays by reference — no
    # gigabyte-scale throwaway init for real model sizes
    shapes = _jax.eval_shape(
        mod2.init, _jax.random.PRNGKey(0),
        jnp.zeros((1, h // ds, w // ds, fam.unet.in_channels)),
        jnp.zeros((1,)),
        jnp.zeros((1, 77, fam.unet.context_dim)))["params"]
    fill = registry._virtual_leaf(registry._name_seed(tag))

    def build(path, sd):
        node = model.unet_params
        for part in path:
            k2 = getattr(part, "key", str(part))
            if isinstance(node, dict) and k2 in node:
                node = node[k2]
            else:
                return fill(("params",) + tuple(path), sd)
        return node

    merged = _jax.tree_util.tree_map_with_path(build, shapes)
    return registry.derive_pipeline(model, tag, family=fam2,
                                    unet_params=merged)


@register_op
class GLIGENTextBoxApply(Op):
    """Ground a phrase to a pixel box: the phrase's encoding + the
    normalized box become a grounding token every fuser attends.
    Entries accumulate on the conditioning (the reference's schema);
    the sampler grafts the fusers into the UNet automatically when a
    grounded conditioning arrives (_maybe_gligen_model)."""
    TYPE = "GLIGENTextBoxApply"
    WIDGETS = ["text", "width", "height", "x", "y"]

    def execute(self, ctx: OpContext, conditioning_to: Conditioning,
                clip, gligen_textbox_model, text: str, width: int,
                height: int, x: int, y: int):
        g = gligen_textbox_model
        ctx_arr, pooled = clip.encode_prompt([str(text)])
        emb = np.asarray(pooled if pooled is not None
                         else ctx_arr.mean(axis=1), np.float32)
        if emb.shape[-1] < g.cfg.text_dim:
            emb = np.pad(emb, ((0, 0),
                               (0, g.cfg.text_dim - emb.shape[-1])))
        emb = emb[:, : g.cfg.text_dim]
        box = (int(x) // 8, int(y) // 8,
               max(int(width) // 8, 1), max(int(height) // 8, 1))

        # the reference appends the phrase to EVERY entry of the
        # conditioning list — siblings bundled by ConditioningCombine
        # (regional prompting) each keep their OWN prior grounding
        # entries and gain this one (the sampler runs per-block token
        # sets, so a sibling's earlier boxes are preserved)
        def _ground(e: Conditioning) -> Conditioning:
            prev = getattr(e, "gligen", None)
            entries = (prev[1] if prev is not None else ()) + ((emb, box),)
            return dataclasses.replace(e, gligen=(g, entries))

        return (dataclasses.replace(
            _ground(conditioning_to),
            siblings=tuple(_ground(s)
                           for s in conditioning_to.siblings)),)


@register_op
class TomePatchModel(Op):
    """ToMe token merging at the HIGHEST-resolution attention level
    (the reference's max_downsample=1): level-0 self-attentions merge
    ``ratio`` of their query tokens into their most similar 2x2-cell
    destinations and unmerge after (models/tome.py) — that level is
    where the quadratic cost lives.  Deterministic destination grid
    (the reference's randomized grid is jit-hostile).  Families without
    level-0 attention (SDXL) get a loud no-op, matching the reference's
    behavior at its default max_downsample.  Derived pipeline, static
    config like FreeU."""
    TYPE = "TomePatchModel"
    WIDGETS = ["ratio"]
    DEFAULTS = {"ratio": 0.3}

    def execute(self, ctx: OpContext, model, ratio: float = 0.3):
        r = min(max(float(ratio), 0.0), 0.9)
        if r == 0.0:
            return (model,)
        fam = model.family
        if fam.unet.transformer_depth[0] == 0:
            log(f"TomePatchModel: {fam.name} has no level-0 attention "
                "(SDXL layout) — the patch is a no-op, as with the "
                "reference's default max_downsample=1")
            return (model,)
        fam2 = dataclasses.replace(fam, unet=dataclasses.replace(
            fam.unet, tome_ratio=r))
        return (registry.derive_pipeline(model, f"tome:{r}",
                                         family=fam2),)


@register_op
class HypernetworkLoader(Op):
    """A1111-format hypernetwork: residual MLPs on the cross-attention
    k/v context streams at ``strength`` (models/hypernetwork.py).
    Derived pipeline; rides further derivations; virtual-initializes
    when no file exists (same policy as checkpoints)."""
    TYPE = "HypernetworkLoader"
    WIDGETS = ["hypernetwork_name", "strength"]
    DEFAULTS = {"strength": 1.0}

    def execute(self, ctx: OpContext, model, hypernetwork_name: str,
                strength: float = 1.0):
        from comfyui_distributed_tpu.models.hypernetwork import \
            load_hypernetwork
        s = float(strength)
        if s == 0.0:
            return (model,)
        hn = load_hypernetwork(str(hypernetwork_name),
                               models_dir=ctx.models_dir)
        # chained loaders COMPOSE (reference: attn patches stack);
        # the tag is CONTENT-stable (name@dir, not id()) so a recycled
        # object id after a cache clear can't alias a stale clone
        chain = tuple(getattr(model, "hypernets", ())) + ((hn, s),)
        chain_tag = (getattr(model, "hypernet_tag", "")
                     + f"|{hypernetwork_name}@{ctx.models_dir or ''}x{s}")
        return (registry.derive_pipeline(
            model, "hypernet:" + chain_tag,
            extra_attrs={"hypernets": chain,
                         "hypernet_tag": chain_tag}),)


@register_op
class HyperTile(Op):
    """HyperTile: tile self-attention spatially (tiles ride the batch
    axis) so its cost drops from O(N^2) to O(tiles*(N/tiles)^2) — the
    reference ecosystem's speed patch for large canvases.  Static,
    deterministic tiling (largest divisor with tiles >= tile_size//8
    latent units; the reference's random divisor swap is jit-hostile,
    so ``swap_size`` is accepted and ignored with a log)."""
    TYPE = "HyperTile"
    WIDGETS = ["tile_size", "swap_size", "max_depth", "scale_depth"]
    DEFAULTS = {"tile_size": 256, "swap_size": 2, "max_depth": 0,
                "scale_depth": False}

    def execute(self, ctx: OpContext, model, tile_size: int = 256,
                swap_size: int = 2, max_depth: int = 0,
                scale_depth=False):
        if int(swap_size) != 2:
            debug_log("HyperTile: swap_size has no effect (deterministic "
                      "static tiling)")
        sd = str(scale_depth).lower() not in ("false", "0", "")
        fam = model.family
        fam2 = dataclasses.replace(fam, unet=dataclasses.replace(
            fam.unet, hypertile=(int(tile_size), int(max_depth), sd)))
        tag = f"hypertile:{tile_size}:{max_depth}:{int(sd)}"
        return (registry.derive_pipeline(model, tag, family=fam2),)


@register_op
class PatchModelAddDownscale(Op):
    """Kohya deep shrink: for the early (high-sigma) part of sampling,
    the encoder downscales its hidden at the given input block and
    upsamples back at the first skip mismatch — large canvases keep
    global composition without doubling the trained resolution's cost.
    TPU shape: a lax.cond between a shrunk-graph and a plain-graph UNet
    apply over ONE param tree (static shapes inside each branch);
    ``downscale_method``/``upscale_method`` are accepted for schema
    parity (both paths use bilinear)."""
    TYPE = "PatchModelAddDownscale"
    WIDGETS = ["block_number", "downscale_factor", "start_percent",
               "end_percent", "downscale_after_skip",
               "downscale_method", "upscale_method"]
    DEFAULTS = {"block_number": 3, "downscale_factor": 2.0,
                "start_percent": 0.0, "end_percent": 0.35,
                "downscale_after_skip": True,
                "downscale_method": "bicubic",
                "upscale_method": "bicubic"}

    def execute(self, ctx: OpContext, model, block_number: int = 3,
                downscale_factor: float = 2.0,
                start_percent: float = 0.0, end_percent: float = 0.35,
                downscale_after_skip=True,
                downscale_method: str = "bicubic",
                upscale_method: str = "bicubic"):
        ucfg = model.family.unet
        nrb = int(ucfg.num_res_blocks)
        b = max(int(block_number), 1)
        # torch input_blocks index -> our level: blocks 1..nrb are level
        # 0, the level's trailing Downsample belongs to the NEXT level
        lvl = (b - 1) // (nrb + 1)
        if (b - 1) % (nrb + 1) == nrb:
            lvl += 1
        lvl = min(lvl, ucfg.num_levels - 1)
        sched = model.schedule
        s_hi = sched.percent_to_sigma(float(start_percent))
        s_lo = sched.percent_to_sigma(float(end_percent))
        t_hi = float(np.asarray(sched.t_from_sigma(
            np.asarray([s_hi], np.float32)))[0]) + 1e-3
        t_lo = float(np.asarray(sched.t_from_sigma(
            np.asarray([s_lo], np.float32)))[0])
        tag = (f"deepshrink:{lvl}:{float(downscale_factor)}"
               f":{start_percent}:{end_percent}")
        return (registry.derive_pipeline(
            model, tag,
            extra_attrs={"deep_shrink_spec":
                         (float(lvl), float(downscale_factor),
                          t_lo, t_hi)}),)


@register_op
class SelfAttentionGuidance(Op):
    """SAG (Hong et al.): blur what the model itself attends to, denoise
    the degraded latent once more, and steer away from it — the
    reference ecosystem's SelfAttentionGuidance patch.  Derived pipeline
    with mid-block attention capture baked into the (static) UNet
    config; 3 UNet evals per step."""
    TYPE = "SelfAttentionGuidance"
    WIDGETS = ["scale", "blur_sigma"]
    DEFAULTS = {"scale": 0.5, "blur_sigma": 2.0}

    def execute(self, ctx: OpContext, model, scale: float = 0.5,
                blur_sigma: float = 2.0):
        fam = model.family
        fam2 = dataclasses.replace(fam, unet=dataclasses.replace(
            fam.unet, sag_capture=True))
        tag = f"sag:{float(scale)}:{float(blur_sigma)}"
        return (registry.derive_pipeline(
            model, tag, family=fam2,
            extra_attrs={"sag_params": (float(scale),
                                        float(blur_sigma))}),)


@register_op
class PerpNeg(Op):
    """ComfyUI's PerpNeg model patch: sampling evaluates a third, EMPTY
    conditioning and subtracts only the negative's perpendicular
    component (samplers.cfg_denoiser_perp_neg).  Derived pipeline;
    rides further derivations."""
    TYPE = "PerpNeg"
    WIDGETS = ["neg_scale"]
    DEFAULTS = {"neg_scale": 1.0}

    def execute(self, ctx: OpContext, model,
                empty_conditioning: Conditioning, neg_scale: float = 1.0):
        import zlib

        # the empty conditioning is part of the derived pipeline's
        # identity — two patches with the same scale but different empty
        # prompts must not share a cache slot
        e = empty_conditioning
        sig = zlib.crc32(np.asarray(e.context, np.float32).tobytes())
        if e.pooled is not None:
            sig = zlib.crc32(np.asarray(e.pooled, np.float32).tobytes(),
                             sig)
        return (registry.derive_pipeline(
            model, f"perpneg:{float(neg_scale)}:{sig:08x}",
            extra_attrs={"perp_neg_cond": empty_conditioning,
                         "perp_neg_scale": float(neg_scale)}),)


@register_op
class FreeU(Op):
    """FreeU (Si et al.): decoder backbone boost + skip low-pass — free
    quality lift, no weight change (reference ecosystem's FreeU node).
    Static config: each setting compiles once, cached per pipeline."""
    TYPE = "FreeU"
    WIDGETS = ["b1", "b2", "s1", "s2"]
    DEFAULTS = {"b1": 1.1, "b2": 1.2, "s1": 0.9, "s2": 0.2}

    def execute(self, ctx: OpContext, model, b1: float = 1.1,
                b2: float = 1.2, s1: float = 0.9, s2: float = 0.2):
        return (_freeu_pipeline(model, 1, b1, b2, s1, s2),)


@register_op
class FreeU_V2(Op):
    """FreeU v2: the backbone boost scales with the per-pixel normalized
    hidden mean instead of uniformly."""
    TYPE = "FreeU_V2"
    WIDGETS = ["b1", "b2", "s1", "s2"]
    DEFAULTS = {"b1": 1.3, "b2": 1.4, "s1": 0.9, "s2": 0.2}

    def execute(self, ctx: OpContext, model, b1: float = 1.3,
                b2: float = 1.4, s1: float = 0.9, s2: float = 0.2):
        return (_freeu_pipeline(model, 2, b1, b2, s1, s2),)


@register_op
class CLIPSetLastLayer(Op):
    """ComfyUI's clip-skip: re-route cross-attention conditioning to an
    earlier CLIP hidden layer (-1 = final, -2 = penultimate, ...).  The
    weights are shared; only the tower's output_layer config changes."""
    TYPE = "CLIPSetLastLayer"
    WIDGETS = ["stop_at_clip_layer"]
    DEFAULTS = {"stop_at_clip_layer": -1}

    def execute(self, ctx: OpContext, clip, stop_at_clip_layer: int = -1):
        import dataclasses
        stop = int(stop_at_clip_layer)
        fam = clip.family
        if all(c.output_layer == stop for c in fam.clips):
            return (clip,)
        fam2 = dataclasses.replace(fam, clips=tuple(
            dataclasses.replace(c, output_layer=stop) for c in fam.clips))
        return (registry.derive_pipeline(clip, f"clip{stop}",
                                         family=fam2),)


@register_op
class VAELoader(Op):
    """Standalone VAE checkpoint (e.g. vae-ft-mse-840000) replacing the
    one baked into the model checkpoint."""
    TYPE = "VAELoader"
    WIDGETS = ["vae_name"]

    def execute(self, ctx: OpContext, vae_name: str):
        return (registry.load_vae(str(vae_name),
                                  models_dir=ctx.models_dir),)


@register_op
class CLIPLoader(Op):
    """Standalone text encoder -> CLIP wire (usable by CLIPTextEncode
    and friends); ``type`` picks the tower geometry
    (registry.CLIP_TYPE_FAMILIES)."""
    TYPE = "CLIPLoader"
    WIDGETS = ["clip_name", "type"]
    DEFAULTS = {"type": "stable_diffusion"}

    def execute(self, ctx: OpContext, clip_name: str,
                type: str = "stable_diffusion"):  # noqa: A002 - schema name
        fam = registry.CLIP_TYPE_FAMILIES.get(str(type))
        if fam is None:
            raise ValueError(
                f"CLIPLoader: unknown type {type!r}; available: "
                f"{sorted(registry.CLIP_TYPE_FAMILIES)}")
        if len(registry.FAMILIES[fam].clips) != 1:
            raise ValueError(f"CLIPLoader: type {type!r} needs "
                             "DualCLIPLoader (two towers)")
        return (registry.load_clip([str(clip_name)],
                                   models_dir=ctx.models_dir,
                                   family_name=fam),)


@register_op
class DualCLIPLoader(Op):
    """Two standalone text encoders -> one dual-tower CLIP wire
    (sdxl: clip_name1 = CLIP-L, clip_name2 = OpenCLIP bigG)."""
    TYPE = "DualCLIPLoader"
    WIDGETS = ["clip_name1", "clip_name2", "type"]
    DEFAULTS = {"type": "sdxl"}

    def execute(self, ctx: OpContext, clip_name1: str, clip_name2: str,
                type: str = "sdxl"):  # noqa: A002 - schema name
        fam = registry.CLIP_TYPE_FAMILIES.get(str(type))
        if fam is None or len(registry.FAMILIES[fam].clips) != 2:
            raise ValueError(
                f"DualCLIPLoader: type {type!r} is not a two-tower "
                "family")
        return (registry.load_clip([str(clip_name1), str(clip_name2)],
                                   models_dir=ctx.models_dir,
                                   family_name=fam),)


@register_op
class UNETLoader(Op):
    """Standalone diffusion model -> MODEL wire; family detected from
    the filename.  ``weight_dtype`` accepted for schema parity (weight
    storage is governed by DTPU_BF16_WEIGHTS)."""
    TYPE = "UNETLoader"
    WIDGETS = ["unet_name", "weight_dtype"]
    DEFAULTS = {"weight_dtype": "default"}

    def execute(self, ctx: OpContext, unet_name: str,
                weight_dtype: str = "default"):
        return (registry.load_unet(str(unet_name),
                                   models_dir=ctx.models_dir),)


@register_op
class ControlNetLoader(Op):
    """-> CONTROL_NET (module, params); virtual-initializes when no file
    exists (zero-convs make a fresh virtual net an exact UNet no-op)."""
    TYPE = "ControlNetLoader"
    WIDGETS = ["control_net_name"]

    def execute(self, ctx: OpContext, control_net_name: str):
        return (registry.load_controlnet(str(control_net_name),
                                         models_dir=ctx.models_dir),)


def _control_chain(cond) -> tuple:
    """A conditioning's ControlNet specs as a tuple (the chain).  A
    single legacy 4/5-tuple spec (first element is the net module, not
    another tuple) normalizes to a 1-chain; None to empty."""
    c = getattr(cond, "control", None)
    if c is None:
        return ()
    if isinstance(c, tuple) and c and not isinstance(c[0], tuple):
        return (c,)
    return tuple(c)


@register_op
class ControlNetApply(Op):
    """Attach a ControlNet + hint image to a conditioning at the given
    strength.  ComfyUI semantics: the control steers only the entries
    that carry it — per-entry strength blocks in the stacked CFG call
    (models/denoiser.py); applied to EVERY entry of a multi-entry cond
    list (ComfyUI loops the list), so a Combine upstream keeps both
    prompts steered."""
    TYPE = "ControlNetApply"
    WIDGETS = ["strength"]
    DEFAULTS = {"strength": 1.0}

    def execute(self, ctx: OpContext, conditioning: Conditioning,
                control_net, image, strength: float = 1.0):
        if float(strength) == 0.0:
            # ComfyUI early-returns: zero strength must not pay a full
            # encoder forward per step for a guaranteed no-op
            return (conditioning,)
        module, params = control_net
        hint = np.asarray(as_image_array(image), np.float32)
        spec = (module, params, hint, float(strength))

        def _attach(c: Conditioning) -> Conditioning:
            # CHAIN, don't replace: applying a second net accumulates
            # (ComfyUI's previous_controlnet chain — residuals sum)
            return dataclasses.replace(
                c, control=_control_chain(c) + (spec,))

        out = _attach(conditioning)
        return (dataclasses.replace(
            out, siblings=tuple(_attach(s)
                                for s in conditioning.siblings)),)


@register_op
class ControlNetApplyAdvanced(Op):
    """ControlNetApply plus a sampling-percent window and separate
    positive/negative outputs: the control's residuals contribute only
    while start_percent <= progress <= end_percent (a traced sigma gate
    in the denoiser), applied to BOTH CFG sides like the ecosystem
    node."""
    TYPE = "ControlNetApplyAdvanced"
    WIDGETS = ["strength", "start_percent", "end_percent"]
    DEFAULTS = {"strength": 1.0, "start_percent": 0.0, "end_percent": 1.0}

    def execute(self, ctx: OpContext, positive: Conditioning,
                negative: Conditioning, control_net, image,
                strength: float = 1.0, start_percent: float = 0.0,
                end_percent: float = 1.0):
        if float(strength) == 0.0:
            return (positive, negative)
        module, params = control_net
        hint = np.asarray(as_image_array(image), np.float32)
        window = (float(start_percent), float(end_percent))
        spec = (module, params, hint, float(strength), window)

        def _attach(c: Conditioning) -> Conditioning:
            chained = _control_chain(c) + (spec,)
            return dataclasses.replace(
                c, control=chained,
                siblings=tuple(dataclasses.replace(
                    s, control=_control_chain(s) + (spec,))
                    for s in c.siblings))

        return (_attach(positive), _attach(negative))


@register_op
class DiffControlNetLoader(Op):
    """'Difference' ControlNet loader: the stored weights are DELTAS
    over the base model's encoder, so loading ADDS the given model's
    matching parameter leaves (same tree path and shape) onto the net's
    params — zero-convs and other net-only leaves pass through
    untouched.  Returns a normal CONTROL_NET wire."""
    TYPE = "DiffControlNetLoader"
    WIDGETS = ["control_net_name"]

    _cache: dict = {}

    def execute(self, ctx: OpContext, model, control_net_name: str):
        import jax
        key = (model.cache_token, str(control_net_name),
               ctx.models_dir or "")
        hit = self._cache.get(key)
        if hit is not None:   # don't redo a full-net add per prompt
            return (hit,)
        module, params = registry.load_controlnet(
            str(control_net_name), models_dir=ctx.models_dir,
            family_name=model.family.name)
        unet_flat = {
            jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                model.unet_params)[0]}
        matched = [0]

        def add_base(path, leaf):
            base = unet_flat.get(jax.tree_util.keystr(path))
            if base is not None and tuple(base.shape) == tuple(leaf.shape):
                matched[0] += 1
                return (jnp.asarray(leaf, jnp.float32)
                        + jnp.asarray(base, jnp.float32)
                        ).astype(jnp.asarray(leaf).dtype)
            return leaf

        summed = jax.tree_util.tree_map_with_path(add_base, params)
        log(f"DiffControlNetLoader: added base-model weights into "
            f"{matched[0]} shared leaves of {control_net_name}")
        self._cache[key] = (module, summed)
        return ((module, summed),)


def _embed_cache_get(ctx: OpContext, kind: str):
    """Sub-graph memo lookup for an encode op (runtime/reuse.py): the
    key is the executor-computed input-sub-graph content hash, so a
    retry/variant storm pays text-encode once.  Returns (key, hit);
    key None = not addressable or caching off.  A hit stamps the node's
    span ``cache_hit``/``cache_tier`` so `cli trace` shows the skip."""
    from comfyui_distributed_tpu.runtime import reuse as reuse_mod
    from comfyui_distributed_tpu.utils import trace as trace_mod
    if not reuse_mod.reuse_enabled() or not ctx.content_key:
        return None, None
    key = f"{kind}:{ctx.content_key}"
    hit = reuse_mod.get_reuse().subgraph.get(key)
    if hit is not None:
        sp = trace_mod.current_span()
        if sp is not None:
            sp.attrs["cache_hit"] = True
            sp.attrs["cache_tier"] = "embed"
    return key, hit


def _embed_cache_put(key, value, nbytes: int) -> None:
    from comfyui_distributed_tpu.runtime import reuse as reuse_mod
    if key is not None:
        reuse_mod.get_reuse().subgraph.put(key, value, nbytes)


def _cond_nbytes(cond: "Conditioning") -> int:
    from comfyui_distributed_tpu.runtime import reuse as reuse_mod
    return reuse_mod.conditioning_nbytes(cond)


@register_op
class CLIPTextEncode(Op):
    TYPE = "CLIPTextEncode"
    WIDGETS = ["text"]

    def execute(self, ctx: OpContext, clip, text: str):
        key, hit = _embed_cache_get(ctx, "embed")
        if hit is not None:
            return (hit,)
        context, pooled = clip.encode_prompt([text])
        cond = Conditioning(context=context, pooled=pooled)
        _embed_cache_put(key, cond, _cond_nbytes(cond))
        return (cond,)


@register_op
class LanguageModelLoader(Op):
    """-> LANGUAGE_MODEL: a decoder resident beside the diffusion
    checkpoints, of the family its name contains
    (``registry.LM_FAMILIES``): ``ouro`` (models/looplm.py, Ouro-2.6B, a
    dense looped decoder), ``pangu`` (models/mla_moe.py, one chip's
    share of openPangu-Ultra-MoE-718B: latent attention with a latent
    cache, routed experts), ``exaone`` (models/swa_moe.py, one chip's
    share of K-EXAONE-236B-A23B: window and full attention layers in one
    stack, routed experts), ``granite`` (models/ssm_hybrid.py,
    granite-4.0-h-micro whole: Mamba-2 state-space layers with an
    attention layer every ten, a recurrent state beside a key-value
    cache), ``keye`` (models/dsa_moe.py, one pipeline stage of
    Keye-VL-2.0-30B-A3B's language model: a learned index picks the
    keys a query attends to, routed experts) or ``phi-4-mini-flash``
    (models/sambay.py, Phi-4-mini-flash-reasoning whole: Mamba-1 and
    window differential attention in front, ONE key-value cache and ONE
    state-space memory shared by the layers behind).  A name of none is
    refused.  The model's
    safetensors and ``tokenizer.json`` from the models dir if present;
    otherwise seeded weights made on the device and the hash tokenizer
    pair."""
    TYPE = "LanguageModelLoader"
    WIDGETS = ["model_name"]
    DEFAULTS = {"model_name": "ouro-2.6b.safetensors"}

    def execute(self, ctx: OpContext,
                model_name: str = "ouro-2.6b.safetensors"):
        return (registry.load_language_model(str(model_name),
                                             models_dir=ctx.models_dir),)


@register_op
class LanguageModelGenerate(Op):
    """The prompt expander: ``text`` under the expander's template,
    behind the operator's ``instructions`` if any (few-shot examples, the
    same in every request; the whole cut to ``prompt_tokens`` ids), is
    continued by exactly ``max_new_tokens`` tokens (greedy at
    ``temperature`` 0, else sampled from ``seed``) and the continuation
    is appended to ``text``.  -> (STRING for ``CLIPTextEncode.text``,
    LM_OUTPUT: the ids and the float32 logits each was drawn from, left
    on the device)."""
    TYPE = "LanguageModelGenerate"
    WIDGETS = ["text", "seed", CONTROL, "max_new_tokens", "prompt_tokens",
               "temperature", "instructions"]
    DEFAULTS = {"seed": 0, "max_new_tokens": 64, "prompt_tokens": 64,
                "temperature": 0.0, "instructions": ""}

    def execute(self, ctx: OpContext, model, text: str, seed=0,
                max_new_tokens: int = 64, prompt_tokens: int = 64,
                temperature: float = 0.0, instructions: str = ""):
        ctx.check_interrupt()
        base = seed.base if isinstance(seed, SeedValue) else seed
        # dtpu-lint: ignore[spine-host-fetch] a widget's number, never a device value
        row = registry.LMRow(str(text), int(base), float(temperature),
                             str(instructions))
        n, p = int(max_new_tokens), int(prompt_tokens)
        if ctx.lm_handover is None:
            words, out = model.generate_rows([row], n, p)[0]
        else:
            # a server: one execution for this request and for those
            # waiting in its queue (server/lm_handover.py)
            words, out = ctx.lm_handover.generate(model, row, n, p)
        return (f"{text}, {words}", out)

    @classmethod
    def literal_call(cls, graph, node, is_worker: bool = False):
        """What `execute` will be called with, read from the graph alone:
        ``(model name, LMRow, max_new_tokens, prompt_tokens)``, or None
        where an input is not a literal.  The model is a
        ``LanguageModelLoader``'s; the seed may be a ``DistributedSeed``'s
        on a master, which passes its widget through."""
        links = node.link_inputs()

        def source(name, class_type):
            src = graph.nodes.get(str(links[name][0])) if name in links \
                else None
            literal = src is not None and src.class_type == class_type \
                and not src.hidden
            return src if literal else None

        inputs = {**cls.DEFAULTS, **node.inputs}
        loader = source("model", "LanguageModelLoader")
        if loader is None or node.hidden:
            return None
        name = {**LanguageModelLoader.DEFAULTS, **loader.inputs}["model_name"]
        seed = inputs["seed"]
        seeded = source("seed", "DistributedSeed")
        if seeded is not None and not is_worker:
            seed = seeded.inputs.get("seed")
        numbers = (seed, inputs["max_new_tokens"], inputs["prompt_tokens"],
                   inputs["temperature"])
        if not all(isinstance(x, str) for x in (
                name, inputs["text"], inputs["instructions"])) \
                or not all(isinstance(x, (int, float))
                           and not isinstance(x, bool) for x in numbers):
            return None
        # dtpu-lint: ignore[spine-host-fetch] a number of the graph's JSON, never a device value
        temperature = float(inputs["temperature"])
        return (name, registry.LMRow(inputs["text"], int(seed), temperature,
                                     inputs["instructions"]),
                int(inputs["max_new_tokens"]), int(inputs["prompt_tokens"]))


@register_op
class SaveLanguageModelOutput(Op):
    """LM_OUTPUT -> ``<filename_prefix>.npz`` in the output directory:
    ``prompt_ids``, ``tokens [N]``, ``logits [N, V]`` float32 and the
    family's other per-position arrays of the request's row (a looped
    model's ``exit_probs [N, R]``; an expert model's ``router_scores
    [N, Le, E]`` and ``expert_choices [N, Le, k]``; the state-space
    model's none), for comparison with a reference
    (benchmarks/chip/verify_lm.py, verify_lm_moe.py, verify_lm_ssm.py)."""
    TYPE = "SaveLanguageModelOutput"
    WIDGETS = ["filename_prefix"]
    DEFAULTS = {"filename_prefix": "lm_output"}
    OUTPUT_NODE = True

    def execute(self, ctx: OpContext, lm_output,
                filename_prefix: str = "lm_output"):
        import jax
        path = _safe_output_path(ctx.output_dir or ".",
                                 f"{filename_prefix}.npz")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with trace_mod.device_wait():
            # dtpu-lint: ignore[spine-host-fetch] an OUTPUT node's host edge
            tokens, logits, aux = jax.device_get(
                (lm_output.tokens, lm_output.logits, lm_output.aux))
        row = lm_output.row
        np.savez(path, prompt_ids=lm_output.prompt_ids, tokens=tokens[row],
                 logits=logits[row],
                 **{name: a[row] for name, a in aux.items()})
        return ()


@register_op
class CLIPVisionLoader(Op):
    """-> CLIP_VISION (models/clip_vision.py tower); HF safetensors
    layout from <models>/clip_vision/, virtual init otherwise."""
    TYPE = "CLIPVisionLoader"
    WIDGETS = ["clip_name"]

    def execute(self, ctx: OpContext, clip_name: str):
        return (registry.load_clip_vision(str(clip_name),
                                          models_dir=ctx.models_dir),)


@register_op
class CLIPVisionEncode(Op):
    """IMAGE -> CLIP_VISION_OUTPUT: projected class embedding,
    FINAL-layer hiddens, and the PENULTIMATE hiddens (the layer the
    reference's style-model path consumes); crop: center (reference
    default) / none."""
    TYPE = "CLIPVisionEncode"
    WIDGETS = ["crop"]
    DEFAULTS = {"crop": "center"}

    def execute(self, ctx: OpContext, clip_vision, image,
                crop: str = "center"):
        with Timer("clip_vision_encode"):
            out = clip_vision.encode(as_image_array(image),
                                     crop=str(crop))
        return (out,)


@register_op
class unCLIPConditioning(Op):
    """Attach a CLIP-vision embedding to a conditioning for unclip-ADM
    models (image variations): entries accumulate like the reference's
    unclip_conditioning list and apply to every regional sibling."""
    TYPE = "unCLIPConditioning"
    WIDGETS = ["strength", "noise_augmentation"]
    DEFAULTS = {"strength": 1.0, "noise_augmentation": 0.0}

    def execute(self, ctx: OpContext, conditioning: Conditioning,
                clip_vision_output, strength: float = 1.0,
                noise_augmentation: float = 0.0):
        entry = (np.asarray(clip_vision_output.image_embeds, np.float32),
                 float(strength), float(noise_augmentation))

        def _attach(e: Conditioning) -> Conditioning:
            return dataclasses.replace(
                e, unclip=tuple(getattr(e, "unclip", None) or ())
                + (entry,))

        out = _attach(conditioning)
        return (dataclasses.replace(
            out, siblings=tuple(_attach(s)
                                for s in getattr(conditioning,
                                                 "siblings", ()) or ())),)


@register_op
class unCLIPCheckpointLoader(Op):
    """-> (MODEL, CLIP, VAE, CLIP_VISION) for unclip checkpoints.  The
    diffusion towers load like CheckpointLoaderSimple (family detected
    as sd21_unclip); extracting the vision tower embedded in real
    unclip checkpoint files (OpenCLIP visual layout) is not implemented
    — the vision tower virtual-initializes with a LOUD log, or load one
    explicitly with CLIPVisionLoader."""
    TYPE = "unCLIPCheckpointLoader"
    WIDGETS = ["ckpt_name"]

    def execute(self, ctx: OpContext, ckpt_name: str):
        pipe = registry.load_pipeline(ckpt_name,
                                      models_dir=ctx.models_dir)
        name = str(ckpt_name)
        if ctx.models_dir and os.path.exists(
                os.path.join(ctx.models_dir, name)):
            log(f"unCLIPCheckpointLoader: extracting the embedded vision "
                f"tower from {name!r} is not supported; using a "
                "virtual tower (load one with CLIPVisionLoader instead)")
        vision = registry.load_clip_vision(
            f"{name}.vision",
            config_name="tiny" if pipe.family.name.startswith("tiny")
            else "vit_h")
        return (pipe, pipe, pipe, vision)


@register_op
class StyleModelLoader(Op):
    """-> STYLE_MODEL (models/style_model.py)."""
    TYPE = "StyleModelLoader"
    WIDGETS = ["style_model_name"]

    def execute(self, ctx: OpContext, style_model_name: str):
        from comfyui_distributed_tpu.models.style_model import \
            load_style_model
        return (load_style_model(str(style_model_name),
                                 models_dir=ctx.models_dir),)


@register_op
class StyleModelApply(Op):
    """Append the style tokens derived from a CLIP-vision output to the
    conditioning's TOKEN axis (every sibling too) — style steering via
    ordinary cross-attention."""
    TYPE = "StyleModelApply"

    def execute(self, ctx: OpContext, conditioning: Conditioning,
                style_model, clip_vision_output):
        with Timer("style_model_apply"):
            tokens = style_model.get_cond(clip_vision_output)

        def _cat(e: Conditioning) -> Conditioning:
            return dataclasses.replace(
                e, context=jnp.concatenate(
                    [jnp.asarray(e.context),
                     jnp.asarray(tokens, jnp.float32)], axis=1))

        out = _cat(conditioning)
        return (dataclasses.replace(
            out, siblings=tuple(_cat(s)
                                for s in getattr(conditioning,
                                                 "siblings", ()) or ())),)


@register_op
class CLIPTextEncodeSDXL(Op):
    """ComfyUI's SDXL dual-prompt encode: text_l feeds the CLIP-L tower,
    text_g the OpenCLIP tower (whose pooled output becomes the ADM
    vector), and the size widgets ride the conditioning as explicit ADM
    scalars (height, width, crop_h, crop_w, target_height,
    target_width) instead of being derived from the latent dims."""
    TYPE = "CLIPTextEncodeSDXL"
    WIDGETS = ["width", "height", "crop_w", "crop_h", "target_width",
               "target_height", "text_g", "text_l"]
    DEFAULTS = {"crop_w": 0, "crop_h": 0}

    def execute(self, ctx: OpContext, clip, width: int, height: int,
                crop_w: int = 0, crop_h: int = 0,
                target_width: int = 0, target_height: int = 0,
                text_g: str = "", text_l: str = ""):
        key, hit = _embed_cache_get(ctx, "embed_sdxl")
        if hit is not None:
            return (hit,)
        tw = int(target_width) or int(width)
        th = int(target_height) or int(height)
        context, pooled = clip.encode_prompt([str(text_l)],
                                             texts_alt=[str(text_g)])
        cond = Conditioning(
            context=context, pooled=pooled,
            size_cond=(int(height), int(width), int(crop_h), int(crop_w),
                       th, tw))
        _embed_cache_put(key, cond, _cond_nbytes(cond))
        return (cond,)


@register_op
class CLIPTextEncodeSDXLRefiner(Op):
    """ComfyUI's SDXL-refiner encode: single prompt, ADM scalars
    (height, width, crop_h, crop_w, aesthetic_score) — the refiner
    family's 5-scalar embedder layout."""
    TYPE = "CLIPTextEncodeSDXLRefiner"
    WIDGETS = ["ascore", "width", "height", "text"]
    DEFAULTS = {"ascore": 6.0}

    def execute(self, ctx: OpContext, clip, ascore: float, width: int,
                height: int, text: str):
        context, pooled = clip.encode_prompt([str(text)])
        return (Conditioning(
            context=context, pooled=pooled,
            size_cond=(int(height), int(width), 0, 0, float(ascore))),)


@register_op
class EmptyLatentImage(Op):
    """Zero latent batch; in a distributed run the batch expands to
    ``batch_size * fanout`` — the SPMD analog of every participant creating
    its own batch (reference: implied scaling images = (1+N) x batch,
    ``gpupanel.js:806-808``)."""
    TYPE = "EmptyLatentImage"
    WIDGETS = ["width", "height", "batch_size"]
    DEFAULTS = {"width": 512, "height": 512, "batch_size": 1}

    def execute(self, ctx: OpContext, width: int, height: int,
                batch_size: int = 1):
        # coalesced runs lay the batch out PROMPT-MAJOR: [prompt0 x b,
        # prompt1 x b, ...] — the order scheduler.split_images relies on
        total = int(batch_size) * max(ctx.fanout, 1) * max(ctx.coalesce, 1)
        lat = np.zeros((total, height // 8, width // 8, 4), np.float32)
        return ({"samples": lat, "local_batch": int(batch_size),
                 "fanout": max(ctx.fanout, 1)},)


@dataclasses.dataclass
class SamplerObject:
    """SAMPLER wire type (ComfyUI custom sampling): a named sampler
    selection carried between KSamplerSelect and SamplerCustom."""
    name: str


@register_op
class KSamplerSelect(Op):
    TYPE = "KSamplerSelect"
    WIDGETS = ["sampler_name"]

    def execute(self, ctx: OpContext, sampler_name: str):
        from comfyui_distributed_tpu.models.samplers import get_sampler
        get_sampler(str(sampler_name))    # fail at selection, not sampling
        return (SamplerObject(str(sampler_name)),)


@register_op
class BasicScheduler(Op):
    """-> SIGMAS from the model's own schedule (ComfyUI custom
    sampling); denoise < 1 truncates to the final fraction of steps."""
    TYPE = "BasicScheduler"
    WIDGETS = ["scheduler", "steps", "denoise"]
    DEFAULTS = {"denoise": 1.0}

    def execute(self, ctx: OpContext, model, scheduler: str, steps: int,
                denoise: float = 1.0):
        from comfyui_distributed_tpu.models import schedules as sch
        return (np.asarray(sch.compute_sigmas(
            model.schedule, str(scheduler), int(steps), float(denoise)),
            np.float32),)


@register_op
class KarrasScheduler(Op):
    """-> SIGMAS: the Karras rho-schedule with explicit bounds."""
    TYPE = "KarrasScheduler"
    WIDGETS = ["steps", "sigma_max", "sigma_min", "rho"]
    DEFAULTS = {"sigma_max": 14.614642, "sigma_min": 0.0291675,
                "rho": 7.0}

    def execute(self, ctx: OpContext, steps: int, sigma_max: float,
                sigma_min: float, rho: float = 7.0):
        from comfyui_distributed_tpu.models import schedules as sch
        return (sch.karras_scheduler(None, int(steps), float(rho),
                                     sigma_min=float(sigma_min),
                                     sigma_max=float(sigma_max)),)


@register_op
class ExponentialScheduler(Op):
    """-> SIGMAS: log-linear ramp with explicit bounds."""
    TYPE = "ExponentialScheduler"
    WIDGETS = ["steps", "sigma_max", "sigma_min"]
    DEFAULTS = {"sigma_max": 14.614642, "sigma_min": 0.0291675}

    def execute(self, ctx: OpContext, steps: int, sigma_max: float,
                sigma_min: float):
        from comfyui_distributed_tpu.models import schedules as sch
        return (sch.polyexponential_sigmas(int(steps), float(sigma_max),
                                           float(sigma_min), rho=1.0),)


@register_op
class PolyexponentialScheduler(Op):
    TYPE = "PolyexponentialScheduler"
    WIDGETS = ["steps", "sigma_max", "sigma_min", "rho"]
    DEFAULTS = {"sigma_max": 14.614642, "sigma_min": 0.0291675,
                "rho": 1.0}

    def execute(self, ctx: OpContext, steps: int, sigma_max: float,
                sigma_min: float, rho: float = 1.0):
        from comfyui_distributed_tpu.models import schedules as sch
        return (sch.polyexponential_sigmas(int(steps), float(sigma_max),
                                           float(sigma_min),
                                           rho=float(rho)),)


@register_op
class VPScheduler(Op):
    TYPE = "VPScheduler"
    WIDGETS = ["steps", "beta_d", "beta_min", "eps_s"]
    DEFAULTS = {"beta_d": 19.9, "beta_min": 0.1, "eps_s": 0.001}

    def execute(self, ctx: OpContext, steps: int, beta_d: float = 19.9,
                beta_min: float = 0.1, eps_s: float = 0.001):
        from comfyui_distributed_tpu.models import schedules as sch
        return (sch.vp_sigmas(int(steps), float(beta_d),
                              float(beta_min), float(eps_s)),)


@register_op
class LaplaceScheduler(Op):
    TYPE = "LaplaceScheduler"
    WIDGETS = ["steps", "sigma_max", "sigma_min", "mu", "beta"]
    DEFAULTS = {"sigma_max": 14.614642, "sigma_min": 0.0291675,
                "mu": 0.0, "beta": 0.5}

    def execute(self, ctx: OpContext, steps: int, sigma_max: float,
                sigma_min: float, mu: float = 0.0, beta: float = 0.5):
        from comfyui_distributed_tpu.models import schedules as sch
        return (sch.laplace_sigmas(int(steps), float(sigma_max),
                                   float(sigma_min), float(mu),
                                   float(beta)),)


@register_op
class BetaSamplingScheduler(Op):
    """-> SIGMAS: beta-distribution spacing over the MODEL's schedule."""
    TYPE = "BetaSamplingScheduler"
    WIDGETS = ["steps", "alpha", "beta"]
    DEFAULTS = {"alpha": 0.6, "beta": 0.6}

    def execute(self, ctx: OpContext, model, steps: int,
                alpha: float = 0.6, beta: float = 0.6):
        from comfyui_distributed_tpu.models import schedules as sch
        return (np.asarray(sch.beta_scheduler(
            model.schedule, int(steps), float(alpha), float(beta)),
            np.float32),)


@register_op
class AlignYourStepsScheduler(Op):
    """-> SIGMAS: NVIDIA Align-Your-Steps reference tables (SD1 / SDXL /
    SVD), log-linearly interpolated to the step count."""
    TYPE = "AlignYourStepsScheduler"
    WIDGETS = ["model_type", "steps", "denoise"]
    DEFAULTS = {"model_type": "SD1", "denoise": 1.0}

    def execute(self, ctx: OpContext, model_type: str, steps: int,
                denoise: float = 1.0):
        from comfyui_distributed_tpu.models import schedules as sch
        d = float(denoise)
        if d <= 0.0:
            return (np.zeros((0,), np.float32),)
        # reference semantics: interp to steps+1, keep the LAST
        # round(steps*denoise)+1 entries, force the terminal 0
        total = round(int(steps) * d) if d < 1.0 else int(steps)
        sig = sch.ays_sigmas(str(model_type), int(steps)).copy()
        sig = sig[-(total + 1):]
        sig[-1] = 0.0
        return (sig,)


@register_op
class SDTurboScheduler(Op):
    """-> SIGMAS for distilled turbo models: the last ``steps`` of the
    model schedule's 100-spaced timesteps."""
    TYPE = "SDTurboScheduler"
    WIDGETS = ["steps", "denoise"]
    DEFAULTS = {"steps": 1, "denoise": 1.0}

    def execute(self, ctx: OpContext, model, steps: int = 1,
                denoise: float = 1.0):
        from comfyui_distributed_tpu.models import schedules as sch
        return (sch.sd_turbo_sigmas(model.schedule, int(steps),
                                    float(denoise)),)


@register_op
class SplitSigmasDenoise(Op):
    """-> (high_sigmas, low_sigmas) split at the denoise fraction (the
    img2img split as explicit sigma IO)."""
    TYPE = "SplitSigmasDenoise"
    WIDGETS = ["denoise"]
    DEFAULTS = {"denoise": 1.0}

    def execute(self, ctx: OpContext, sigmas, denoise: float = 1.0):
        s = np.asarray(sigmas, np.float32)
        steps = s.shape[0] - 1
        keep = round(steps * float(denoise))   # reference rounds
        i = max(steps - keep, 0)
        return (s[:i + 1], s[i:])


@register_op
class SplitSigmas(Op):
    """-> (high_sigmas, low_sigmas) split at ``step`` — two-stage custom
    chains (the KSamplerAdvanced window as explicit sigma IO)."""
    TYPE = "SplitSigmas"
    WIDGETS = ["step"]
    DEFAULTS = {"step": 0}

    def execute(self, ctx: OpContext, sigmas, step: int = 0):
        s = np.asarray(sigmas, np.float32)
        i = min(max(int(step), 0), s.shape[0] - 1)
        return (s[:i + 1], s[i:])


@register_op
class FlipSigmas(Op):
    """-> SIGMAS reversed (unsampling chains); a leading 0 becomes a tiny
    epsilon so the first model call has a usable sigma (ComfyUI)."""
    TYPE = "FlipSigmas"

    def execute(self, ctx: OpContext, sigmas):
        s = np.asarray(sigmas, np.float32)[::-1].copy()
        if s.shape[0] and s[0] == 0.0:
            s[0] = 1e-4
        return (s,)


@register_op
class SamplerCustom(Op):
    """ComfyUI's custom-sampling entry: explicit SAMPLER + SIGMAS instead
    of the KSampler widget pair.  Only the sigma COUNT is static (scan
    trip count); the values ride in as a traced argument, so same-length
    schedules share one executable (registry.sample).  Both latent
    outputs carry the final result (the denoised preview stream is not
    separately materialized — no callback sink exists headless)."""
    TYPE = "SamplerCustom"
    # CONTROL: ComfyUI serializes seed widgets with a trailing
    # control_after_generate value in UI-format exports
    WIDGETS = ["add_noise", "noise_seed", CONTROL, "cfg"]
    DEFAULTS = {"add_noise": True, "cfg": 8.0}

    def execute(self, ctx: OpContext, model, add_noise, noise_seed, cfg,
                positive: Conditioning, negative: Conditioning,
                latent_image, sampler, sigmas):
        ctx.check_interrupt()
        model = _maybe_gligen_model(model, positive, negative)
        prep = _prepare_sample_inputs(ctx, model, noise_seed, latent_image,
                                      positive, negative)
        name = sampler.name if isinstance(sampler, SamplerObject) \
            else str(sampler)
        with Timer(f"sampler_custom[{name}x{len(sigmas) - 1}]"):
            out = model.sample(
                prep.latents, prep.context, prep.uncond, prep.seeds,
                steps=1, cfg=float(cfg), sampler_name=name,
                scheduler="normal", y=prep.y,
                add_noise=(str(add_noise).lower()
                           not in ("disable", "false", "0")),
                sample_idx=prep.sample_idx,
                noise_mask=prep.noise_mask, control=prep.control,
                sigmas_override=np.asarray(sigmas, np.float32),
                middle_context=prep.mid_context, cfg2=prep.cfg2,
                guidance=prep.guidance, c_concat=prep.c_concat,
                gligen_objs=prep.gligen_objs,
                donate_latents=prep.donate_latents, keys=prep.keys)
        out_d = {"samples": DeviceLatent(out), **_latent_meta(latent_image),
                 "local_batch": prep.local_batch, "fanout": prep.fanout}
        return (out_d, dict(out_d))


@dataclasses.dataclass
class NoiseObject:
    """NOISE wire type (ComfyUI custom sampling): the initial-noise
    policy carried between RandomNoise/DisableNoise and
    SamplerCustomAdvanced.  ``seed`` may be a SeedValue (DistributedSeed
    replica offsets ride through)."""
    seed: object = 0
    disable: bool = False


@dataclasses.dataclass
class GuiderObject:
    """GUIDER wire type (ComfyUI custom sampling): model + conditioning
    + guidance mode bundled by BasicGuider/CFGGuider/DualCFGGuider."""
    model: object
    positive: Conditioning
    negative: Optional[Conditioning] = None
    middle: Optional[Conditioning] = None
    cfg: float = 1.0
    cfg2: float = 1.0
    mode: str = "cfg"          # "basic" | "cfg" | "dual"


@register_op
class RandomNoise(Op):
    """-> NOISE seeded like KSampler's widget (ComfyUI custom sampling);
    a DistributedSeed value keeps its per-replica offsets."""
    TYPE = "RandomNoise"
    WIDGETS = ["noise_seed", CONTROL]

    def execute(self, ctx: OpContext, noise_seed):
        return (NoiseObject(seed=noise_seed),)


@register_op
class DisableNoise(Op):
    """-> NOISE that adds nothing (ComfyUI: later hires/refiner stages
    where the latent already carries its noise)."""
    TYPE = "DisableNoise"

    def execute(self, ctx: OpContext):
        return (NoiseObject(seed=0, disable=True),)


@register_op
class BasicGuider(Op):
    """-> GUIDER: conditioning-only denoising (no CFG combine — the
    cfg==1 fast path skips the uncond evaluation entirely)."""
    TYPE = "BasicGuider"

    def execute(self, ctx: OpContext, model, conditioning: Conditioning):
        return (GuiderObject(model=model, positive=conditioning,
                             mode="basic"),)


@register_op
class CFGGuider(Op):
    """-> GUIDER: the standard positive/negative CFG combine at ``cfg``
    as an explicit wire object (ComfyUI custom sampling)."""
    TYPE = "CFGGuider"
    WIDGETS = ["cfg"]
    DEFAULTS = {"cfg": 8.0}

    def execute(self, ctx: OpContext, model, positive: Conditioning,
                negative: Conditioning, cfg: float = 8.0):
        return (GuiderObject(model=model, positive=positive,
                             negative=negative, cfg=float(cfg),
                             mode="cfg"),)


@register_op
class DualCFGGuider(Op):
    """-> GUIDER with two positives (ComfyUI DualCFGGuider — the
    InstructPix2Pix combine): cond2 is CFG'd against the negative at
    ``cfg_cond2_negative``, then cond1 steers against cond2 at
    ``cfg_conds``; see samplers.cfg_denoiser_dual."""
    TYPE = "DualCFGGuider"
    WIDGETS = ["cfg_conds", "cfg_cond2_negative"]
    DEFAULTS = {"cfg_conds": 8.0, "cfg_cond2_negative": 8.0}

    def execute(self, ctx: OpContext, model, cond1: Conditioning,
                cond2: Conditioning, negative: Conditioning,
                cfg_conds: float = 8.0, cfg_cond2_negative: float = 8.0):
        return (GuiderObject(model=model, positive=cond1, middle=cond2,
                             negative=negative, cfg=float(cfg_conds),
                             cfg2=float(cfg_cond2_negative), mode="dual"),)


@register_op
class PerpNegGuider(Op):
    """-> GUIDER: Perp-Neg as an explicit custom-sampling wire (ComfyUI
    PerpNegGuider) — positive/negative/empty conditionings, CFG at
    ``cfg``, perpendicular negative at ``neg_scale``."""
    TYPE = "PerpNegGuider"
    WIDGETS = ["cfg", "neg_scale"]
    DEFAULTS = {"cfg": 8.0, "neg_scale": 1.0}

    def execute(self, ctx: OpContext, model, positive: Conditioning,
                negative: Conditioning, empty_conditioning: Conditioning,
                cfg: float = 8.0, neg_scale: float = 1.0):
        return (GuiderObject(model=model, positive=positive,
                             negative=negative,
                             middle=empty_conditioning, cfg=float(cfg),
                             cfg2=float(neg_scale), mode="perp"),)


@register_op
class SamplerCustomAdvanced(Op):
    """ComfyUI's fully-modular sampling entry: NOISE + GUIDER + SAMPLER +
    SIGMAS.  Same compiled path as SamplerCustom; the guider picks the
    denoiser combine (basic / cfg / dual-cfg / perp-neg).  Both latent
    outputs carry the final result (no separate preview stream
    headless)."""
    TYPE = "SamplerCustomAdvanced"

    @staticmethod
    def _plain(e: Conditioning) -> bool:
        return (not getattr(e, "siblings", ()) and e.area_mask is None
                and e.timestep_range is None
                and float(getattr(e, "area_strength", 1.0)) == 1.0)

    def execute(self, ctx: OpContext, noise: NoiseObject,
                guider: GuiderObject, sampler, sigmas, latent_image):
        ctx.check_interrupt()
        g = guider
        neg = g.negative if g.negative is not None else g.positive
        g = dataclasses.replace(
            g, model=_maybe_gligen_model(g.model, g.positive, neg,
                                         g.middle))
        three_row = g.mode in ("dual", "perp")
        if three_row and not all(
                self._plain(e) for e in (g.positive, g.middle, neg)):
            raise ValueError(f"{g.mode} guidance does not compose with "
                             "regional multi-entry conditionings")
        prep = _prepare_sample_inputs(
            ctx, g.model, noise.seed, latent_image, g.positive, neg,
            middle=g.middle if three_row else None)
        if three_row:
            guidance = "perp_neg" if g.mode == "perp" else "dual"
            cfg2 = float(g.cfg2)
        else:   # incl. a PerpNeg-patched model under a plain guider
            guidance, cfg2 = prep.guidance, prep.cfg2
        cfg = 1.0 if g.mode == "basic" else float(g.cfg)
        name = sampler.name if isinstance(sampler, SamplerObject) \
            else str(sampler)
        with Timer(f"sampler_custom_adv[{g.mode}:{name}"
                   f"x{len(sigmas) - 1}]"):
            out = g.model.sample(
                prep.latents, prep.context, prep.uncond, prep.seeds,
                steps=1, cfg=cfg, sampler_name=name, scheduler="normal",
                y=prep.y, add_noise=not noise.disable,
                sample_idx=prep.sample_idx, noise_mask=prep.noise_mask,
                control=prep.control,
                sigmas_override=np.asarray(sigmas, np.float32),
                middle_context=prep.mid_context, cfg2=cfg2,
                guidance=guidance, c_concat=prep.c_concat,
                gligen_objs=prep.gligen_objs,
                donate_latents=prep.donate_latents, keys=prep.keys)
        out_d = {"samples": DeviceLatent(out), **_latent_meta(latent_image),
                 "local_batch": prep.local_batch, "fanout": prep.fanout}
        return (out_d, dict(out_d))


@register_op
class KSampler(Op):
    """Denoise loop.  Seed semantics (reference ``distributed.py:1491-1514``):
    a SeedValue from DistributedSeed applies +replica offsets; a plain int
    replicates the same stream on every replica."""
    TYPE = "KSampler"
    WIDGETS = ["seed", CONTROL, "steps", "cfg", "sampler_name", "scheduler",
               "denoise"]
    DEFAULTS = {"denoise": 1.0}
    # coalesced_seeds: per-prompt seed list injected by the batch-
    # coalescing scheduler (workflow/scheduler.py) as a hidden override —
    # JSON-safe ints, so the merged graph's PNG metadata stays clean.
    # cb_latent: a finished continuous-batching slot's latent rows
    # (workflow/batch_executor.py tail run) — the sampler returns them
    # directly so the graph tail (VAE decode, save) runs unchanged.
    HIDDEN = ["coalesced_seeds", "cb_latent"]

    # model/positive/negative/latent_image default None ONLY for the
    # continuous-batching tail (cb_latent short-circuits before any of
    # them is touched; the pruned tail graph drops the encode subtree) —
    # the parameter ORDER is unchanged, so positional callers keep
    # working, and the widget defaults only matter to pruned graphs
    def execute(self, ctx: OpContext, model=None, seed=0, steps=20,
                cfg=8.0, sampler_name="euler", scheduler="normal",
                positive: Conditioning = None,
                negative: Conditioning = None,
                latent_image=None, denoise: float = 1.0,
                coalesced_seeds=None, cb_latent=None):
        ctx.check_interrupt()
        if ctx.cb_capture is not None:
            # bucket-build prefix run: hand the resolved inputs to the
            # step executor instead of sampling (it owns the loop)
            ctx.cb_capture.update(
                model=model, seed=seed, steps=steps, cfg=cfg,
                sampler_name=str(sampler_name), scheduler=str(scheduler),
                denoise=denoise, positive=positive, negative=negative,
                latent_image=latent_image)
            raise CBCapture("KSampler inputs captured")
        if cb_latent is not None:
            lat = cb_latent if isinstance(cb_latent, DeviceLatent) \
                else DeviceLatent(as_device_array(cb_latent))
            out_d = {"samples": lat, "local_batch": int(lat.shape[0]),
                     "fanout": 1}
            return (out_d,)
        if coalesced_seeds is not None and not isinstance(seed, SeedValue):
            seed = SeedValue(int(seed),
                             per_prompt=np.asarray(coalesced_seeds,
                                                   np.uint64))
        model = _maybe_gligen_model(model, positive, negative)
        prep = _prepare_sample_inputs(ctx, model, seed, latent_image,
                                      positive, negative)
        with Timer(f"ksampler[{sampler_name}x{steps}]"):
            out = model.sample(
                prep.latents, prep.context, prep.uncond, prep.seeds,
                steps=int(steps), cfg=float(cfg),
                sampler_name=str(sampler_name), scheduler=str(scheduler),
                denoise=float(denoise), y=prep.y,
                sample_idx=prep.sample_idx,
                noise_mask=prep.noise_mask, control=prep.control,
                middle_context=prep.mid_context, cfg2=prep.cfg2,
                guidance=prep.guidance, c_concat=prep.c_concat,
                gligen_objs=prep.gligen_objs,
                donate_latents=prep.donate_latents, keys=prep.keys)
        out_d = {"samples": DeviceLatent(out),
                 "local_batch": prep.local_batch,
                 "fanout": prep.fanout}
        if "noise_mask" in latent_image:   # ComfyUI keeps the mask on the
            out_d["noise_mask"] = latent_image["noise_mask"]  # latent
        return (out_d,)


@register_op
class KSamplerAdvanced(Op):
    """ComfyUI's staged sampler: run a [start_at_step, end_at_step] window
    of the schedule, optionally without adding noise (later hires stages)
    and optionally returning a still-noisy latent for the next stage."""
    TYPE = "KSamplerAdvanced"
    WIDGETS = ["add_noise", "noise_seed", CONTROL, "steps", "cfg",
               "sampler_name", "scheduler", "start_at_step", "end_at_step",
               "return_with_leftover_noise"]
    DEFAULTS = {"start_at_step": 0, "end_at_step": 10000,
                "add_noise": "enable", "return_with_leftover_noise":
                "disable"}

    def execute(self, ctx: OpContext, model, add_noise, noise_seed, steps,
                cfg, sampler_name, scheduler, positive: Conditioning,
                negative: Conditioning, latent_image,
                start_at_step: int = 0, end_at_step: int = 10000,
                return_with_leftover_noise: str = "disable"):
        ctx.check_interrupt()
        model = _maybe_gligen_model(model, positive, negative)
        prep = _prepare_sample_inputs(ctx, model, noise_seed, latent_image,
                                      positive, negative)
        with Timer(f"ksampler_adv[{sampler_name}x{steps}"
                   f"@{start_at_step}-{end_at_step}]"):
            out = model.sample(
                prep.latents, prep.context, prep.uncond, prep.seeds,
                steps=int(steps), cfg=float(cfg),
                sampler_name=str(sampler_name), scheduler=str(scheduler),
                y=prep.y, sample_idx=prep.sample_idx,
                noise_mask=prep.noise_mask, control=prep.control,
                add_noise=(str(add_noise) != "disable"),
                start_step=int(start_at_step),
                end_step=min(int(end_at_step), int(steps)),
                force_full_denoise=(
                    str(return_with_leftover_noise) == "disable"),
                middle_context=prep.mid_context, cfg2=prep.cfg2,
                guidance=prep.guidance, c_concat=prep.c_concat,
                gligen_objs=prep.gligen_objs,
                donate_latents=prep.donate_latents, keys=prep.keys)
        out_d = {"samples": DeviceLatent(out),
                 "local_batch": prep.local_batch,
                 "fanout": prep.fanout}
        if "noise_mask" in latent_image:
            out_d["noise_mask"] = latent_image["noise_mask"]
        return (out_d,)


def cond_token_align(entries) -> int:
    """Common token length for a set of conditioning entries: ComfyUI
    repeats each cond to the lcm of the lengths (77-chunk multiples in
    practice) — semantically lossless, unlike zero-pad (zero keys still
    soak up softmax mass); falls back to zero-padding at max length only
    if a pathological mix would explode the lcm.  ONE copy of the rule —
    the sampler prep and the tiled-upscale regional refine both use it."""
    lengths = {int(e.context.shape[1]) for e in entries}
    t_max = max(lengths)
    t_align = math.lcm(*lengths)
    if t_align > 8 * t_max:
        debug_log(f"conditioning token lengths {sorted(lengths)} have no "
                  f"small common multiple; zero-padding to {t_max}")
        t_align = t_max
    return t_align


def align_cond_tokens(c, t_align: int):
    """Repeat (lossless) or zero-pad one context to ``t_align`` tokens."""
    t = int(c.shape[1])
    if t == t_align:
        return c
    if t_align % t == 0:
        return jnp.tile(c, (1, t_align // t, 1))
    return jnp.pad(c, ((0, 0), (0, t_align - t), (0, 0)))


def adm_cond_source(family, e: Conditioning, positive: Conditioning):
    """Which conditioning supplies an entry's ADM vector: unclip
    families build from the entry's OWN unclip list (a negative without
    one gets ZERO ADM — the reference zero-fills — never the positive's
    image embedding); sdxl entries without a pooled fall back to the
    primary positive's."""
    if getattr(family, "adm_kind", "sdxl") == "unclip":
        return e
    return e if e.pooled is not None else positive


def entry_sigma_range(model_or_schedule, e: Conditioning):
    """timestep_range percents -> (sigma_start, sigma_end) bounds
    against THIS model's schedule (active while s_end <= sigma <=
    s_start), or None.  Accepts the model/pipeline OR a schedule and
    resolves ``.schedule`` lazily — wrapper models without one must
    keep working when no entry carries a timestep_range."""
    tr = getattr(e, "timestep_range", None)
    if tr is None:
        return None
    schedule = getattr(model_or_schedule, "schedule", model_or_schedule)
    return (schedule.percent_to_sigma(float(tr[0])),
            schedule.percent_to_sigma(float(tr[1])))


def _materialize_area_mask(cond: Conditioning, h: int, w: int, total: int):
    """A Conditioning's area spec -> latent-resolution weight mask
    [1_or_B, h, w, 1], or None.  Rect specs resolve against the ACTUAL
    latent dims here ("px" uses ComfyUI's //8 latent-unit convention;
    "pct" is resolution-independent fractions); array masks area-resize
    like noise masks."""
    am = getattr(cond, "area_mask", None)
    if am is None:
        return None
    if isinstance(am, tuple):
        kind, x, y, ww, hh = am
        m = np.zeros((1, h, w, 1), np.float32)
        if kind == "px":
            x0, y0 = int(x) // 8, int(y) // 8
            x1 = x0 + max(int(ww) // 8, 1)
            y1 = y0 + max(int(hh) // 8, 1)
        else:
            x0, y0 = int(round(x * w)), int(round(y * h))
            x1 = x0 + max(int(round(ww * w)), 1)
            y1 = y0 + max(int(round(hh * h)), 1)
        m[:, max(y0, 0):min(y1, h), max(x0, 0):min(x1, w), :] = 1.0
        return jnp.asarray(m)
    return jnp.asarray(_image_mask_to_latent(am, h, w, total))


def _image_mask_to_latent(mask, h: int, w: int, total: int) -> np.ndarray:
    """Image-res mask [H,W]/[B,H,W] -> latent-res weights
    [1_or_total, h, w, 1]: area-downsample, clip to [0,1], short batches
    cycle — the ONE copy of the convention (noise masks and area masks
    must never drift apart)."""
    m = np.asarray(mask, np.float32)
    if m.ndim == 2:
        m = m[None]
    m = np.clip(resize_image(m[..., None], w, h, "area"), 0.0, 1.0)
    if m.shape[0] != 1:  # a single mask broadcasts; others fan out
        m = _cycle_batch(m, total)
    return m


def _cycle_batch(arr: np.ndarray, n: int) -> np.ndarray:
    """One row per sample, cycling a short batch via modulo indexing — the
    ONE copy of the pairing rule: fanned batches tile whole-block, so row
    i of the cycled array pairs with batch row i exactly (and the
    denoiser's CFG doubling then pairs [a;a] with [cond;uncond] rows
    one-to-one)."""
    if arr.shape[0] == n:
        return arr
    return np.take(arr, np.arange(n) % arr.shape[0], axis=0)


def _safe_output_path(out_dir: str, rel: str) -> str:
    """Join a user-supplied filename prefix into ``out_dir``, rejecting
    '..'-style escapes (the reference ecosystem sanitizes save paths into
    the output root the same way)."""
    root = os.path.realpath(out_dir)
    path = os.path.realpath(os.path.join(root, rel))
    if os.path.commonpath([root, path]) != root:
        raise ValueError(
            f"filename prefix {rel!r} escapes the output directory "
            f"{root!r}")
    return path


@dataclasses.dataclass
class _SampleInputs:
    """Shared KSampler/KSamplerAdvanced preamble: latent unpack, replica
    seed fan-out, per-replica fold-in indices, conditioning batch repeat,
    SDXL vector cond, and mesh sharding — ONE copy, so replica-seed or
    sharding fixes can't land in one sampler and miss the other."""
    latents: object
    context: object
    uncond: object
    seeds: object
    sample_idx: object
    y: object
    local_batch: int
    fanout: int
    noise_mask: object = None
    control: object = None
    # 3-row guidance (dual-CFG / PerpNeg): the middle conditioning's
    # batch-repeated context, aligned to the same token length as
    # context/uncond; None for plain CFG.  ``guidance``/``cfg2`` are the
    # matching registry.sample kwargs (perp-neg auto-detected from the
    # pipeline patch)
    mid_context: object = None
    guidance: str = "dual"
    cfg2: float = 1.0
    # inpaint-model channels (Conditioning.concat_latent), batch-matched
    c_concat: object = None
    # GLIGEN grounding token pair (cond, null), batch-matched
    gligen_objs: object = None
    # True when ``latents`` is a buffer freshly created by the prep
    # (host->device put or a resharding copy): the jitted denoise loop may
    # then DONATE it — the graph holds no other reference, so aliasing the
    # noised carry onto it halves peak latent memory.  False when the
    # value arrived device-resident (e.g. a hires chain reusing an
    # upstream KSampler's output that other nodes may also consume).
    donate_latents: bool = False
    # the per-sample PRNG keys of ``seeds`` / ``sample_idx``, made in the
    # one program that made ``context`` / ``uncond`` / ``y``
    # (registry.sampler_inputs): the sampler node hands them to ``sample``
    keys: object = None


def _maybe_gligen_model(model, *conds):
    """A conditioning carrying GLIGEN grounding pulls the fuser-grafted
    pipeline in transparently (the reference patches the model inside
    its sampling machinery; the graph schema carries only the
    conditioning)."""
    for c in conds:
        if c is None:
            continue
        for e in (c,) + tuple(getattr(c, "siblings", ()) or ()):
            spec = getattr(e, "gligen", None)
            if spec is not None:
                if model.family.unet.gligen:
                    return model
                return gligen_attach(model, spec[0])
    return model


def _prepare_sample_inputs(ctx: OpContext, model, seed, latent_image,
                           positive: Conditioning,
                           negative: Conditioning,
                           middle: Optional[Conditioning] = None,
                           ) -> _SampleInputs:
    """``middle`` (dual-CFG / PerpNeg): a third plain conditioning
    prepared in the SAME pass — token alignment spans all three, it
    carries its OWN pooled ADM vector, and a control on any of the three
    gets a flat per-block [cond, middle, uncond] strength tuple.  A
    PerpNeg-patched pipeline injects its empty conditioning when no
    explicit middle is given."""
    guidance, cfg2 = "dual", 1.0
    if middle is None:
        pn = getattr(model, "perp_neg_cond", None)
        if pn is not None:
            middle = pn
            guidance = "perp_neg"
            cfg2 = float(getattr(model, "perp_neg_scale", 1.0))
    # device-resident tensor plane: the latent stays a jax.Array end to
    # end — only its SHAPE is consulted here.  A host array (fresh
    # EmptyLatentImage batch, a numpy-edited latent) pays one counted
    # h2d put and yields a donation-safe fresh buffer (below, behind the
    # one program that makes the other inputs).
    raw = latent_image["samples"]
    raw_arr = raw.data if isinstance(raw, DeviceTensor) else raw
    fanout = int(latent_image.get("fanout", 1))
    total, lat_h, lat_w = (int(n) for n in np.shape(raw_arr)[:3])
    local_b = int(latent_image.get("local_batch", total // max(fanout, 1)))

    if isinstance(seed, SeedValue):
        base, distributed = seed.base, seed.distributed
        per_prompt = getattr(seed, "per_prompt", None)
    else:
        base, distributed, per_prompt = int(seed), False, None
    if fanout > 1 and distributed:
        seeds = coll.replica_seeds(base, fanout, local_b)
    elif per_prompt is not None and len(per_prompt) > 0 \
            and total % len(per_prompt) == 0:
        # coalesced group: prompt-major layout, each prompt's seed
        # repeated over its own local batch — together with the tiled
        # fold index below, every sample draws EXACTLY the (seed, idx)
        # noise stream its serial run would have drawn
        seeds = np.repeat(np.asarray(per_prompt, np.uint64),
                          total // len(per_prompt))
    else:
        seeds = np.full((total,), np.uint64(base), np.uint64)
    # fold index cycles per local batch: fanout replicas, and coalesced
    # prompts, each restart at 0 (a prompt's batch is its own batch-of-b)
    reps = -(-total // max(local_b, 1))
    local_idx = np.tile(np.arange(local_b, dtype=np.uint32), reps)[:total]
    if latent_image.get("seed_fixed_batch"):
        # LatentBatchSeedBehavior 'fixed': one noise stream for the
        # whole local batch (replica offsets still apply via seeds)
        local_idx = np.zeros_like(local_idx)

    # multi-entry cond lists (regional prompting), SYMMETRIC on both CFG
    # sides: the primary plus any siblings bundled by ConditioningCombine;
    # every entry's tokens align to the longest across BOTH sides (77 ->
    # 154 repeats whole blocks, otherwise zero-pad) — the stacked CFG
    # call concatenates all of them along batch
    pos_entries = [positive] + list(getattr(positive, "siblings", ())
                                    or ())
    neg_entries = [negative] + list(getattr(negative, "siblings", ())
                                    or ())
    mid_entries = [middle] if middle is not None else []
    all_entries = pos_entries + neg_entries + mid_entries
    t_align = cond_token_align(all_entries)

    def _align_tokens(c):
        return align_cond_tokens(c, t_align)

    mesh = ctx.runtime.mesh if ctx.runtime is not None else None
    sharded = fanout > 1 and mesh is not None
    adm = model.family.unet.adm_in_channels is not None
    unclip_adm = adm and getattr(model.family, "adm_kind",
                                 "sdxl") == "unclip"

    # every entry's context at ``total`` rows, its ADM vector (each entry
    # carries its OWN pooled: regional SDXL's region B must not ride
    # region A's; source selection shared with the tile refine) and the
    # keys: ONE program, enqueued behind whatever the device is running
    vectors = [_sdxl_vector_source(
        model, adm_cond_source(model.family, e, positive),
        lat_h * 8, lat_w * 8) for e in all_entries] \
        if adm and not unclip_adm else []
    keys, ys, contexts = registry.sampler_inputs(
        model, total, seeds, local_idx, vectors,
        [_align_tokens(e.context) for e in all_entries])
    if unclip_adm:
        ys = [_unclip_vector_cond(model, e, total) for e in all_entries]

    if sharded:
        # a fan-out request places a shard per replica before its
        # denoise is enqueued: the runtime holds the host at the first
        # of those calls until the previous denoise is done (1.77 s of a
        # 2.6 s cycle on four chips), so meet the device here by name.
        # Only placements and ``core`` follow
        registry.wait_previous_denoise()
        contexts = [coll.shard_batch(ce, mesh) for ce in contexts]
        ys = [coll.shard_batch(ye, mesh) for ye in ys]
    lat = as_device_array(raw)
    lat_dev = coll.shard_batch(lat, mesh) if sharded else lat

    def _build_entries(src, at):
        out = []
        for e, ce in zip(src, contexts[at:at + len(src)]):
            am = _materialize_area_mask(e, lat_h, lat_w, total)
            if am is not None and sharded and am.shape[0] == total:
                # per-sample masks ride the data axis like the noise
                # mask; single-row masks stay replicated
                am = coll.shard_batch(np.asarray(am), mesh)
            srange = entry_sigma_range(model, e)
            out.append((ce, am,
                        float(getattr(e, "area_strength", 1.0)), srange))
        return out, ys[at:at + len(src)]

    cond_entries, y_conds = _build_entries(pos_entries, 0)
    unc_entries, y_unconds = _build_entries(neg_entries, len(pos_entries))
    mid_built, y_mids = _build_entries(
        mid_entries, len(pos_entries) + len(neg_entries))
    multi = len(cond_entries) > 1 or len(unc_entries) > 1 \
        or any(m is not None or s != 1.0 or sr is not None
               for _, m, s, sr in cond_entries + unc_entries + mid_built)
    mid_ctx = None
    if middle is not None:
        if multi:
            raise ValueError(
                f"3-row guidance ({guidance}: "
                f"{'PerpNeg patch' if guidance == 'perp_neg' else 'DualCFG'}"
                ") requires plain single-entry positive/negative "
                "conditionings")
        mid_ctx = mid_built[0][0]
    if multi:
        ctx_arr = cond_entries
        unc_arr = unc_entries
        y = (y_conds + y_unconds) if adm else None
    elif middle is not None:
        ctx_arr = cond_entries[0][0]
        unc_arr = unc_entries[0][0]
        # one ADM vector per [cond, middle, uncond] block; middle rides
        # its OWN pooled (fallback to the positive's inside
        # _build_entries).  SDXL-kind: the negative rides the positive's
        # like the plain path; unclip-kind: the negative keeps its OWN
        # (zero-filled) vector so CFG amplifies the image guidance
        if adm:
            y = [y_conds[0], y_mids[0],
                 y_unconds[0] if unclip_adm else y_conds[0]]
        else:
            y = None
    else:   # the unchanged single-entry path: plain arrays
        ctx_arr = cond_entries[0][0]
        unc_arr = unc_entries[0][0]
        if adm and unclip_adm:
            # per-block list: the uncond block gets the negative's
            # zero-filled ADM, not a replicated positive embedding
            y = [y_conds[0], y_unconds[0]]
        else:
            y = y_conds[0] if adm else None

    # controls may hang on ANY conditioning entry (ComfyUI honors all),
    # and each entry may CHAIN several nets (previous_controlnet
    # accumulation).  EVERY unique (net, params, hint) runs per step —
    # residuals sum in the denoiser — and each net's strength/window
    # becomes a per-ENTRY tuple so only the carrying entries' blocks are
    # steered (a control on the right-region sibling must not steer the
    # left region).
    nets: List[Tuple] = []   # (module, params, hint) in first-seen order
    net_max_ord: List[int] = []   # per net: max chain repeats per entry
    spec_slot: Dict[int, Tuple[int, int]] = {}  # id(spec) -> (net, ord)

    def _net_key_index(spec) -> int:
        for i, (m, p, h) in enumerate(nets):
            if spec[0] is m and spec[1] is p \
                    and (spec[2] is h or np.array_equal(spec[2], h)):
                return i
        return -1

    for e in all_entries:
        counts: Dict[int, int] = {}
        for spec in _control_chain(e):
            i = spec_slot[id(spec)][0] if id(spec) in spec_slot \
                else _net_key_index(spec)
            if i < 0:
                nets.append((spec[0], spec[1], spec[2]))
                net_max_ord.append(0)
                i = len(nets) - 1
            # the same net chained TWICE on one entry keeps both links
            # (distinct wire slots — ComfyUI runs every link and sums;
            # the common two-windows-one-net pattern needs this)
            j = counts.get(i, 0)
            counts[i] = j + 1
            spec_slot.setdefault(id(spec), (i, j))
            net_max_ord[i] = max(net_max_ord[i], j + 1)

    control = None
    if nets:
        def _entry_spec(e, slot):
            for spec in _control_chain(e):
                if spec_slot.get(id(spec)) == slot:
                    return spec
            return None

        slots = [(i, j) for i, n in enumerate(net_max_ord)
                 for j in range(n)]
        sched = getattr(model, "schedule", None)
        wire = []
        for slot in slots:
            module, params, hint = nets[slot[0]]

            def _strength(e, _s=slot):
                sp = _entry_spec(e, _s)
                return float(sp[3]) if sp is not None else 0.0

            def _window(e, _s=slot):
                sp = _entry_spec(e, _s)
                if sp is None or len(sp) <= 4 or sp[4] is None:
                    return None
                return (float(sp[4][0]), float(sp[4][1]))

            if middle is not None:
                # flat per-block [cond, middle, uncond] tuple — the dual
                # denoiser's 3-row layout (models/denoiser.py block rule)
                strengths = (_strength(pos_entries[0]),
                             _strength(mid_entries[0]),
                             _strength(neg_entries[0]))
                windows = (_window(pos_entries[0]),
                           _window(mid_entries[0]),
                           _window(neg_entries[0]))
                flat_windows = windows
            else:
                strengths = (tuple(_strength(e) for e in pos_entries),
                             tuple(_strength(e) for e in neg_entries))
                windows = (tuple(_window(e) for e in pos_entries),
                           tuple(_window(e) for e in neg_entries))
                flat_windows = windows[0] + windows[1]
            if all(w is None for w in flat_windows):
                windows = None
            # hint image -> the resolution the hint ladder expects (8x
            # the latent dims — other VAE downscales still align)
            hh, ww = lat.shape[1] * 8, lat.shape[2] * 8
            if hint.shape[1] != hh or hint.shape[2] != ww:
                hint = resize_image(hint, ww, hh, "bilinear")
            hint = _cycle_batch(hint, total)
            hint_dev = hint
            if fanout > 1 and ctx.runtime is not None:
                hint_dev = coll.shard_batch(
                    np.asarray(hint, np.float32), ctx.runtime.mesh)
            spec_w = (module, params, jnp.asarray(hint_dev), strengths)
            if windows is not None:
                if sched is None:
                    log("ControlNetApplyAdvanced: model has no schedule;"
                        " ignoring the start/end percent windows")
                else:
                    def _to_sig(w):
                        return None if w is None else (
                            sched.percent_to_sigma(float(w[0])),
                            sched.percent_to_sigma(float(w[1])))

                    if middle is not None:
                        swins = tuple(_to_sig(w) for w in windows)
                    else:
                        swins = (tuple(_to_sig(w) for w in windows[0]),
                                 tuple(_to_sig(w) for w in windows[1]))
                    spec_w = spec_w + (swins,)
            wire.append(spec_w)
        control = tuple(wire)

    mask = latent_image.get("noise_mask")
    if mask is not None:
        # image-res [B,H,W] -> latent-res [B,h,w,1]; a single mask
        # broadcasts across the whole (fanned) batch
        m = _image_mask_to_latent(mask, lat.shape[1], lat.shape[2], total)
        if fanout > 1 and mesh is not None and m.shape[0] == total:
            m = coll.shard_batch(m, mesh)
        mask = jnp.asarray(m)

    # GLIGEN grounding tokens, PER BLOCK: each conditioning entry keeps
    # its OWN grounding spec (the reference applies gligen per-cond), so
    # distinct specs become distinct token sets padded to a common
    # object count (null tokens are the natural pad); blocks without a
    # spec get the all-null set (registry.sample indexes per block)
    gligen_objs = None
    specs = []           # unique specs, first-appearance order (identity)
    for e in all_entries:
        sp = getattr(e, "gligen", None)
        if sp is not None and all(sp is not s for s in specs):
            specs.append(sp)
    if specs:
        gmodel = specs[0][0]
        if any(sp[0] is not gmodel for sp in specs):
            log("GLIGEN: conditioning entries carry DIFFERENT gligen "
                "models; grounding tokens all run through the first "
                "model's fusers")
        n_max = max(len(sp[1]) for sp in specs)
        d_text = gmodel.cfg.text_dim

        def spec_tokens(entries_g):
            embs = np.zeros((1, n_max, d_text), np.float32)
            boxes = np.zeros((1, n_max, 4), np.float32)
            alive = np.zeros((1, n_max), np.float32)
            for i, (t, b) in enumerate(entries_g):
                # clip to the first model's text width: entries applied
                # through a DIFFERENT gligen model may carry another
                # dim — degrade (warned above), don't crash
                v = np.asarray(t, np.float32).reshape(-1)
                w = min(v.shape[0], d_text)
                embs[0, i, :w] = v[:w]
                # xywh latent units -> normalized xyxy vs THIS latent
                bx = np.asarray([b[0], b[1], b[0] + b[2], b[1] + b[3]],
                                np.float32)
                bx = bx / np.asarray([lat.shape[2], lat.shape[1],
                                      lat.shape[2], lat.shape[1]],
                                     np.float32)
                boxes[0, i] = np.clip(bx, 0.0, 1.0)
                alive[0, i] = 1.0
            return gmodel.grounding_tokens(embs, boxes, alive)

        def batch_tokens(t):
            t = jnp.repeat(jnp.asarray(t), total, axis=0)
            if fanout > 1 and mesh is not None:
                t = coll.shard_batch(np.asarray(t), mesh)
            return t

        og = jnp.stack([batch_tokens(spec_tokens(sp[1]))
                        for sp in specs])          # [S, total, N, D]
        on = batch_tokens(spec_tokens(()))         # all-null set

        def spec_index(e):
            sp = getattr(e, "gligen", None)
            return next((i for i, s in enumerate(specs) if s is sp), -1)

        # per-block spec indices in the registry's block layout (conds
        # first — incl. the dual middle — then unconds); -1 = null set
        idxs = tuple(spec_index(e) for e in pos_entries)
        if middle is not None:
            idxs += (spec_index(middle),)
        idxs += tuple(spec_index(e) for e in neg_entries)
        gligen_objs = (og, on, idxs)

    # inpaint-MODEL channels: any conditioning entry may carry them
    # (ComfyUI sets them on positive AND negative); one array rides every
    # model call, cycled to the fanned batch like the control hint
    c_concat = next((getattr(e, "concat_latent", None)
                     for e in all_entries
                     if getattr(e, "concat_latent", None) is not None),
                    None)
    if c_concat is not None:
        cc = np.asarray(c_concat, np.float32)
        if cc.shape[1:3] != (lat.shape[1], lat.shape[2]):
            cc = resize_image(cc, lat.shape[2], lat.shape[1], "bilinear")
        cc = _cycle_batch(cc, total)
        if fanout > 1 and mesh is not None:
            cc = coll.shard_batch(cc, mesh)
        c_concat = jnp.asarray(cc)

    return _SampleInputs(latents=lat_dev, context=ctx_arr,
                         uncond=unc_arr, seeds=seeds, sample_idx=local_idx,
                         y=y, local_batch=local_b, fanout=fanout,
                         noise_mask=mask, control=control,
                         mid_context=mid_ctx, guidance=guidance,
                         cfg2=cfg2, c_concat=c_concat,
                         gligen_objs=gligen_objs,
                         donate_latents=lat_dev is not raw_arr, keys=keys)


def _unclip_vector_cond(pipe, cond: Conditioning, batch: int):
    """unCLIP ADM vector (documented approximation of the reference's
    CLIPEmbeddingNoiseAugmentation): each entry's CLIP-vision embed is
    q_sample-noised to ``round(999 * noise_augmentation)`` on the
    model's own schedule (deterministic noise keyed by the embed's
    content), concatenated with that level's timestep embedding, scaled
    by strength, and entries SUM (the reference's weighted merge).  The
    dataset mean/std rescale of the trained augmentor ships with real
    weights and is not modeled — noted limitation."""
    import zlib

    from comfyui_distributed_tpu.models.layers import timestep_embedding
    want = int(pipe.family.unet.adm_in_channels)
    half = want // 2
    entries = getattr(cond, "unclip", None) or ()
    if not entries:
        return jnp.zeros((batch, want))
    acc = np.zeros((1, want), np.float32)
    abar = np.asarray(pipe.schedule.alphas_cumprod, np.float32)
    for embed, strength, noise_aug in entries:
        e = np.asarray(embed, np.float32)
        if e.ndim == 1:
            e = e[None]
        if e.shape[0] > 1:
            log("unCLIP: batched vision embeds — using row 0 (encode "
                "images separately for multi-image conditioning)")
        e = e[:1]
        if e.shape[1] < half:
            e = np.pad(e, ((0, 0), (0, half - e.shape[1])))
        e = e[:, :half]
        # widget range is [0, 1]; clamp so a stray negative can't
        # negative-index into max noise and >1 can't IndexError
        level = min(max(int(round((abar.shape[0] - 1)
                                  * float(noise_aug))), 0),
                    abar.shape[0] - 1)
        rng = np.random.default_rng(zlib.crc32(e.tobytes()) + level)
        noised = (np.sqrt(abar[level]) * e
                  + np.sqrt(max(1.0 - abar[level], 0.0))
                  * rng.standard_normal(e.shape).astype(np.float32))
        lvl = np.asarray(timestep_embedding(
            jnp.asarray([level], jnp.float32), half), np.float32)
        acc = acc + np.concatenate([noised, lvl], axis=-1) \
            * float(strength)
    return jnp.repeat(jnp.asarray(acc), batch, axis=0)


def _sdxl_vector_source(pipe, cond: Conditioning, height: int,
                        width: int):
    """``(pooled, sizes)``: what an SDXL ADM vector is made from, both
    still where they are (the pooled text embedding on the device, the
    size scalars on the host), for ``registry.sampler_inputs``.
    A Conditioning carrying ``size_cond`` (CLIPTextEncodeSDXL /
    ...Refiner) supplies its own scalar tuple; otherwise the actual
    latent dims stand in as (H, W, 0, 0, H, W)."""
    sc = getattr(cond, "size_cond", None)
    if sc is None:
        # fallback scalar layout when the encode node didn't supply one:
        # base SDXL = (H, W, 0, 0, H, W); the REFINER's 5th slot is the
        # aesthetic score — filling it with the image height would sit
        # far outside the trained ~2-10 range, so emit (H, W, 0, 0, 6.0)
        # (the ecosystem's default ascore) for refiner families
        if getattr(pipe.family, "name", "").endswith("refiner"):
            sc = (height, width, 0, 0, 6.0)
        else:
            sc = (height, width, 0, 0, height, width)
    return cond.pooled, tuple(float(v) for v in sc)


def _sdxl_vector_cond(pipe, cond: Conditioning, batch: int,
                      height: int, width: int):
    """SDXL ADM vector ``[batch, adm_in_channels]``: pooled text emb +
    size conditioning embeddings, one program.  unclip-ADM families route
    to _unclip_vector_cond instead."""
    if getattr(pipe.family, "adm_kind", "sdxl") == "unclip":
        return _unclip_vector_cond(pipe, cond, batch)
    return registry.sampler_inputs(
        pipe, batch,
        vectors=[_sdxl_vector_source(pipe, cond, height, width)])[1][0]


@register_op
class VAEDecode(Op):
    TYPE = "VAEDecode"

    def execute(self, ctx: OpContext, samples, vae):
        ctx.check_interrupt()
        with Timer("vae_decode"):
            # clamp to image range at the decode boundary (ComfyUI's
            # VAEDecode does the same): everything downstream — PNG wire,
            # tile blend, preview — assumes [0,1], and unclamped floats
            # would make the HTTP paths (clipped by the uint8 wire) diverge
            # from the SPMD/local paths (unclipped)
            img = jnp.clip(
                vae.vae_decode(as_device_array(samples["samples"])),
                0.0, 1.0)
        # stays on device: the next host edge (SaveImage PNG encode, HTTP
        # wire) pays the fetch, not this op boundary
        return (DeviceImage(img, **_image_meta(samples)),)


@register_op
class VAEDecodeTiled(Op):
    """ComfyUI's VAEDecodeTiled: bounded-memory decode for large latents
    (overlapping tiles, feathered blend — registry.vae_decode_tiled)."""
    TYPE = "VAEDecodeTiled"
    WIDGETS = ["tile_size", "overlap"]
    DEFAULTS = {"tile_size": 512, "overlap": 64}

    def execute(self, ctx: OpContext, samples, vae,
                tile_size: int = 512, overlap: int = 64):
        ctx.check_interrupt()
        with Timer("vae_decode_tiled"):
            img = jnp.clip(vae.vae_decode_tiled(
                as_device_array(samples["samples"]),
                tile_size=int(tile_size), overlap=int(overlap),
                check_interrupt=ctx.check_interrupt), 0.0, 1.0)
        return (DeviceImage(img, **_image_meta(samples)),)


@register_op
class VAEEncodeTiled(Op):
    """ComfyUI's VAEEncodeTiled: bounded-memory encode for large sources
    (overlapping pixel tiles, latent-space feathered blend —
    registry.vae_encode_tiled).  Fan-out semantics identical to
    VAEEncode."""
    TYPE = "VAEEncodeTiled"
    WIDGETS = ["tile_size", "overlap"]
    DEFAULTS = {"tile_size": 512, "overlap": 64}

    def execute(self, ctx: OpContext, pixels, vae,
                tile_size: int = 512, overlap: int = 64):
        ctx.check_interrupt()
        # host array in: only per-tile slices ever need to reach the
        # device — pushing a 4K source up just to pull it back for
        # tiling would be two wasted full-array transfers
        img = np.asarray(as_image_array(pixels), np.float32)
        with Timer("vae_encode_tiled"):
            lat = vae.vae_encode_tiled(img, tile_size=int(tile_size),
                                       overlap=int(overlap),
                                       check_interrupt=ctx.check_interrupt)
        return _expand_encoded_latent(ctx, pixels, lat)


def _expand_encoded_latent(ctx: OpContext, pixels, lat):
    """Shared VAEEncode/VAEEncodeTiled fan-out: tile a fresh batch to
    ``batch * fanout``; pass an already-fanned hires-fix batch through."""
    b = int(lat.shape[0])
    in_fan = int(getattr(pixels, "fanout", 1) or 1)
    if in_fan > 1:
        # already-fanned pixels (hires-fix chain: KSampler -> VAEDecode
        # -> ... -> VAEEncode): the batch holds one slice per replica
        # — re-tiling would square the fan-out
        local_b = int(getattr(pixels, "local_batch", None)
                      or b // in_fan)
        return ({"samples": DeviceLatent(lat), "local_batch": local_b,
                 "fanout": in_fan},)
    fanout = max(ctx.fanout, 1)
    if fanout > 1:
        # duplicate ON device: KSampler now consumes the latent
        # device-resident, so a host-side tile would force a d2h+h2d
        # round trip of the whole batch for identical bytes
        lat = jnp.tile(as_device_array(lat), (fanout, 1, 1, 1))
    return ({"samples": DeviceLatent(lat), "local_batch": b,
             "fanout": fanout},)


@register_op
class VAEEncode(Op):
    """Pixels -> latent.  In a distributed run the encoded batch expands to
    ``batch * fanout`` exactly like ``EmptyLatentImage`` — the img2img
    variation sweep (every participant denoises the SAME source latent with
    its own seed offset; reference semantics: each worker runs the full
    graph on its own copy of the staged input image)."""
    TYPE = "VAEEncode"

    def execute(self, ctx: OpContext, pixels, vae):
        # sub-graph memo (runtime/reuse.py): the PRE-expansion encoded
        # latent is cached on device keyed by the input sub-graph's
        # content hash — a retry/variant storm over the same
        # conditioning image pays VAE-encode once.  Donation-safe: a
        # cached device array reaches the sampler un-fresh, and
        # _prepare_sample_inputs only donates freshly-materialized
        # buffers.
        from comfyui_distributed_tpu.runtime import reuse as reuse_mod
        key, hit = _embed_cache_get(ctx, "vaeenc")
        if hit is not None:
            return _expand_encoded_latent(ctx, pixels, hit)
        # device path: a DeviceImage source (hires-fix chain) never
        # bounces through host on its way into the encoder
        img = as_device_image(pixels)
        with Timer("vae_encode"):
            lat = vae.vae_encode(img)
        _embed_cache_put(key, lat, reuse_mod.nbytes_of(lat))
        return _expand_encoded_latent(ctx, pixels, lat)


def _keep_fanout_meta(src, arr):
    """Re-attach fan-out metadata after an op that round-trips through jnp
    (which strips the ImageBatch subclass).  Image-space ops in a hires-fix
    chain must preserve it so a downstream VAEEncode doesn't re-tile an
    already-fanned batch."""
    if getattr(src, "fanout", 1) > 1:
        return ImageBatch(arr, local_batch=getattr(src, "local_batch", None),
                          fanout=src.fanout)
    return arr


def _overlap_window(H: int, W: int, h: int, w: int, x: int, y: int):
    """Visible paste window: ((y0, y1, x0, x1) in dest, (sy0, sy1, sx0,
    sx1) in src) or None when fully out of bounds — the ONE copy of the
    clamp/offset math every composite node uses."""
    x0, y0 = max(int(x), 0), max(int(y), 0)
    x1, y1 = min(int(x) + w, W), min(int(y) + h, H)
    if x0 >= x1 or y0 >= y1:
        return None
    sx0, sy0 = x0 - int(x), y0 - int(y)
    return ((y0, y1, x0, x1),
            (sy0, sy0 + (y1 - y0), sx0, sx0 + (x1 - x0)))


def _paste(dest: np.ndarray, src: np.ndarray, x: int, y: int,
           mask=None) -> np.ndarray:
    """Composite core shared by Image/Latent/Mask composite nodes:
    paste ``src`` [Bs,h,w,C] onto ``dest`` [B,H,W,C] at (x, y), blending
    by ``mask`` [.,h,w] where given.  Out-of-bounds regions crop away
    (ComfyUI's composite clamps the visible window); a short source
    batch cycles over the destination batch."""
    out = dest.copy()
    B, H, W, _ = dest.shape
    h, w = src.shape[1], src.shape[2]
    win = _overlap_window(H, W, h, w, x, y)
    if win is None:
        return out
    (y0, y1, x0, x1), (sy0, sy1, sx0, sx1) = win
    src_b = _cycle_batch(src, B)[:, sy0:sy1, sx0:sx1]
    if mask is None:
        out[:, y0:y1, x0:x1] = src_b
        return out
    m = np.asarray(mask, np.float32)
    if m.ndim == 2:
        m = m[None]
    if m.shape[1] != h or m.shape[2] != w:
        m = resize_image(m[..., None], w, h, "area")[..., 0]
    m = np.clip(_cycle_batch(m, B)[:, sy0:sy1, sx0:sx1, None], 0.0, 1.0)
    out[:, y0:y1, x0:x1] = src_b * m + out[:, y0:y1, x0:x1] * (1.0 - m)
    return out


@register_op
class SolidMask(Op):
    """-> MASK [1, H, W] filled with ``value``."""
    TYPE = "SolidMask"
    WIDGETS = ["value", "width", "height"]
    DEFAULTS = {"value": 1.0, "width": 512, "height": 512}

    def execute(self, ctx: OpContext, value: float = 1.0,
                width: int = 512, height: int = 512):
        return (np.full((1, int(height), int(width)), float(value),
                        np.float32),)


@register_op
class InvertMask(Op):
    TYPE = "InvertMask"

    def execute(self, ctx: OpContext, mask):
        return (1.0 - np.asarray(mask, np.float32),)


@register_op
class GrowMask(Op):
    """Morphological grow/shrink by ``expand`` steps of a 3x3 kernel
    (corners zeroed when ``tapered_corners`` — ComfyUI's shape);
    negative expand erodes."""
    TYPE = "GrowMask"
    WIDGETS = ["expand", "tapered_corners"]
    DEFAULTS = {"expand": 0, "tapered_corners": True}

    def execute(self, ctx: OpContext, mask, expand: int = 0,
                tapered_corners: bool = True):
        m = np.asarray(mask, np.float32)
        if m.ndim == 2:
            m = m[None]
        n = int(expand)
        erode = n < 0
        if erode:
            m = 1.0 - m
        tapered = str(tapered_corners).lower() not in ("false", "0")
        shifts = [(0, 0), (-1, 0), (1, 0), (0, -1), (0, 1)]
        if not tapered:
            shifts += [(-1, -1), (-1, 1), (1, -1), (1, 1)]
        Hm, Wm = m.shape[1], m.shape[2]
        for _ in range(abs(n)):
            padded = np.pad(m, ((0, 0), (1, 1), (1, 1)))
            m = np.max(np.stack(
                [padded[:, 1 + dy:1 + dy + Hm, 1 + dx:1 + dx + Wm]
                 for dy, dx in shifts]), axis=0)
        if erode:
            m = 1.0 - m
        return (m,)


@register_op
class MaskComposite(Op):
    """Combine ``source`` into ``destination`` at (x, y):
    multiply / add / subtract / and / or / xor (ComfyUI's set)."""
    TYPE = "MaskComposite"
    WIDGETS = ["x", "y", "operation"]
    DEFAULTS = {"x": 0, "y": 0, "operation": "multiply"}

    def execute(self, ctx: OpContext, destination, source, x: int = 0,
                y: int = 0, operation: str = "multiply"):
        d = np.asarray(destination, np.float32)
        if d.ndim == 2:
            d = d[None]
        s = np.asarray(source, np.float32)
        if s.ndim == 2:
            s = s[None]
        B, H, W = d.shape
        out = d.copy()
        win = _overlap_window(H, W, s.shape[1], s.shape[2], x, y)
        if win is None:
            return (out,)
        (y0, y1, x0, x1), (sy0, sy1, sx0, sx1) = win
        sb = _cycle_batch(s, B)[:, sy0:sy1, sx0:sx1]
        reg = out[:, y0:y1, x0:x1]
        op = str(operation)
        if op == "multiply":
            reg = reg * sb
        elif op == "add":
            reg = reg + sb
        elif op == "subtract":
            reg = reg - sb
        elif op == "and":
            reg = np.minimum(np.round(reg), np.round(sb))
        elif op == "or":
            reg = np.maximum(np.round(reg), np.round(sb))
        elif op == "xor":
            reg = np.abs(np.round(reg) - np.round(sb))
        else:
            raise ValueError(f"unknown mask operation {op!r}")
        out[:, y0:y1, x0:x1] = np.clip(reg, 0.0, 1.0)
        return (out,)


@register_op
class MaskToImage(Op):
    TYPE = "MaskToImage"

    def execute(self, ctx: OpContext, mask):
        m = np.asarray(mask, np.float32)
        if m.ndim == 2:
            m = m[None]
        return (np.repeat(m[..., None], 3, axis=-1),)


@register_op
class ImageToMask(Op):
    TYPE = "ImageToMask"
    WIDGETS = ["channel"]
    DEFAULTS = {"channel": "red"}

    def execute(self, ctx: OpContext, image, channel: str = "red"):
        img = as_image_array(image)
        idx = {"red": 0, "green": 1, "blue": 2,
               "alpha": 3}.get(str(channel), 0)
        if idx >= img.shape[-1]:
            raise ValueError(
                f"ImageToMask: image has no {channel!r} channel "
                f"({img.shape[-1]} channels)")
        return (np.asarray(img[..., idx], np.float32),)


@register_op
class ImageColorToMask(Op):
    """Pixels matching the 24-bit ``color`` exactly (after 8-bit
    quantization) become 1."""
    TYPE = "ImageColorToMask"
    WIDGETS = ["color"]
    DEFAULTS = {"color": 0}

    def execute(self, ctx: OpContext, image, color: int = 0):
        img = as_image_array(image)
        q = np.clip(np.asarray(img[..., :3]) * 255.0, 0,
                    255).round().astype(np.int64)
        packed = (q[..., 0] << 16) | (q[..., 1] << 8) | q[..., 2]
        return ((packed == int(color)).astype(np.float32),)


@register_op
class CropMask(Op):
    TYPE = "CropMask"
    WIDGETS = ["x", "y", "width", "height"]

    def execute(self, ctx: OpContext, mask, x: int = 0, y: int = 0,
                width: int = 64, height: int = 64):
        m = np.asarray(mask, np.float32)
        if m.ndim == 2:
            m = m[None]
        H, W = m.shape[1], m.shape[2]
        x0 = min(max(int(x), 0), max(W - 1, 0))
        y0 = min(max(int(y), 0), max(H - 1, 0))
        return (m[:, y0:y0 + max(int(height), 1),
                  x0:x0 + max(int(width), 1)].copy(),)


@register_op
class FeatherMask(Op):
    """Linear ramps toward 0 over the given margin on each side —
    reference rate (t+1)/margin, so the innermost feathered row
    reaches 1.0 (a margin of 1 is a no-op, like ComfyUI)."""
    TYPE = "FeatherMask"
    WIDGETS = ["left", "top", "right", "bottom"]
    DEFAULTS = {"left": 0, "top": 0, "right": 0, "bottom": 0}

    def execute(self, ctx: OpContext, mask, left: int = 0, top: int = 0,
                right: int = 0, bottom: int = 0):
        m = np.asarray(mask, np.float32)
        if m.ndim == 2:
            m = m[None]
        out = m.copy()
        H, W = out.shape[1], out.shape[2]
        for t in range(min(max(int(top), 0), H)):
            out[:, t, :] *= (t + 1) / int(top)
        for t in range(min(max(int(bottom), 0), H)):
            out[:, H - 1 - t, :] *= (t + 1) / int(bottom)
        for t in range(min(max(int(left), 0), W)):
            out[:, :, t] *= (t + 1) / int(left)
        for t in range(min(max(int(right), 0), W)):
            out[:, :, W - 1 - t] *= (t + 1) / int(right)
        return (out,)


@register_op
class ThresholdMask(Op):
    TYPE = "ThresholdMask"
    WIDGETS = ["value"]
    DEFAULTS = {"value": 0.5}

    def execute(self, ctx: OpContext, mask, value: float = 0.5):
        m = np.asarray(mask, np.float32)
        if m.ndim == 2:
            m = m[None]
        return ((m > float(value)).astype(np.float32),)


@register_op
class LoadImageMask(Op):
    """Load one channel of an image as a MASK (alpha inverts: fully
    transparent = 1 = resample, matching LoadImage's mask output)."""
    TYPE = "LoadImageMask"
    WIDGETS = ["image", "channel", CONTROL]
    DEFAULTS = {"channel": "alpha"}

    def execute(self, ctx: OpContext, image: str, channel: str = "alpha"):
        from PIL import Image
        path = image
        if ctx.input_dir and not os.path.isabs(path):
            path = os.path.join(ctx.input_dir, image)
        ch = str(channel)[:1].upper()
        if os.path.exists(path):
            im = Image.open(path).convert("RGBA")
            arr = np.asarray(im, np.float32) / 255.0
        else:
            debug_log(f"LoadImageMask: {image!r} not found, synthesizing "
                      "512x512")
            h = w = 512
            yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
            arr = np.stack([xx / w, yy / h, (xx + yy) / (h + w),
                            np.ones((h, w), np.float32)], axis=-1)
        idx = {"R": 0, "G": 1, "B": 2, "A": 3}.get(ch, 3)
        m = arr[..., idx]
        if idx == 3:
            m = 1.0 - m
        return (m[None],)


@register_op
class ImageInvert(Op):
    TYPE = "ImageInvert"

    def execute(self, ctx: OpContext, image):
        return (1.0 - as_image_array(image),)


@register_op
class ImageBlend(Op):
    """Blend two image batches: ``image2`` composited onto ``image1``
    with the named mode, then lerped by ``blend_factor`` (ComfyUI's
    mode set; image2 resizes to image1's dims when they differ)."""
    TYPE = "ImageBlend"
    WIDGETS = ["blend_factor", "blend_mode"]
    DEFAULTS = {"blend_factor": 0.5, "blend_mode": "normal"}

    MODES = ("normal", "multiply", "screen", "overlay", "soft_light",
             "difference")

    def execute(self, ctx: OpContext, image1, image2,
                blend_factor: float = 0.5, blend_mode: str = "normal"):
        a = np.asarray(as_image_array(image1), np.float32)
        b = np.asarray(as_image_array(image2), np.float32)
        if b.shape[1:3] != a.shape[1:3]:
            b = resize_image(b, a.shape[2], a.shape[1], "bilinear")
        b = _cycle_batch(b, a.shape[0])
        mode = str(blend_mode)
        if mode == "normal":
            blended = b
        elif mode == "multiply":
            blended = a * b
        elif mode == "screen":
            blended = 1.0 - (1.0 - a) * (1.0 - b)
        elif mode == "overlay":
            blended = np.where(a <= 0.5, 2.0 * a * b,
                               1.0 - 2.0 * (1.0 - a) * (1.0 - b))
        elif mode == "soft_light":
            # W3C/Photoshop piecewise form (ComfyUI's)
            g = np.where(a <= 0.25,
                         ((16.0 * a - 12.0) * a + 4.0) * a,
                         np.sqrt(np.maximum(a, 0.0)))
            blended = np.where(b <= 0.5,
                               a - (1.0 - 2.0 * b) * a * (1.0 - a),
                               a + (2.0 * b - 1.0) * (g - a))
        elif mode == "difference":
            blended = np.abs(a - b)
        else:
            raise ValueError(f"ImageBlend: unknown mode {mode!r}; "
                             f"available: {self.MODES}")
        f = float(blend_factor)
        return (np.clip(a * (1.0 - f) + blended * f, 0.0, 1.0),)


@register_op
class ImageBatchOp(Op):
    """Concatenate two image batches; the second resizes to the first's
    dims when they differ (ComfyUI bilinear).  (Class named ...Op: the
    module's ``ImageBatch`` is the fan-out-metadata ndarray wrapper.)"""
    TYPE = "ImageBatch"

    def execute(self, ctx: OpContext, image1, image2):
        a = as_image_array(image1)
        b = as_image_array(image2)
        if a.shape[1:3] != b.shape[1:3]:
            b = resize_image(b, a.shape[2], a.shape[1], "bilinear")
        return (np.concatenate([a, b], axis=0),)


@register_op
class ImageCrop(Op):
    TYPE = "ImageCrop"
    WIDGETS = ["width", "height", "x", "y"]

    def execute(self, ctx: OpContext, image, width: int, height: int,
                x: int = 0, y: int = 0):
        img = as_image_array(image)
        H, W = img.shape[1], img.shape[2]
        x0 = min(max(int(x), 0), W - 1)
        y0 = min(max(int(y), 0), H - 1)
        x1 = min(x0 + max(int(width), 1), W)
        y1 = min(y0 + max(int(height), 1), H)
        return (img[:, y0:y1, x0:x1],)


@register_op
class EmptyImage(Op):
    TYPE = "EmptyImage"
    WIDGETS = ["width", "height", "batch_size", "color"]
    DEFAULTS = {"width": 512, "height": 512, "batch_size": 1, "color": 0}

    def execute(self, ctx: OpContext, width: int = 512, height: int = 512,
                batch_size: int = 1, color: int = 0):
        c = int(color)
        rgb = np.asarray([(c >> 16) & 0xFF, (c >> 8) & 0xFF, c & 0xFF],
                         np.float32) / 255.0
        return (np.broadcast_to(
            rgb, (int(batch_size), int(height), int(width), 3)).copy(),)


def _canny_edges(gray: np.ndarray, low: float, high: float) -> np.ndarray:
    """Canny on one [H, W] grayscale frame: gaussian 5x5 -> sobel ->
    gradient NMS (4-way quantized) -> double threshold + hysteresis
    (the reference ecosystem's kornia-backed Canny node's pipeline)."""
    g = _gaussian_blur(gray[None, ..., None], 2, 1.4)[0, ..., 0]
    kx = np.asarray([[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]], np.float32)
    ky = kx.T
    pad = np.pad(g, 1, mode="edge")
    gx = sum(kx[i, j] * pad[i:i + g.shape[0], j:j + g.shape[1]]
             for i in range(3) for j in range(3))
    gy = sum(ky[i, j] * pad[i:i + g.shape[0], j:j + g.shape[1]]
             for i in range(3) for j in range(3))
    mag = np.hypot(gx, gy)
    ang = (np.rad2deg(np.arctan2(gy, gx)) + 180.0) % 180.0
    # non-maximum suppression along the quantized gradient direction
    mp = np.pad(mag, 1)
    offs = np.where(ang < 22.5, 0, np.where(ang < 67.5, 1,
                    np.where(ang < 112.5, 2, np.where(ang < 157.5, 3,
                                                      0))))
    d = {0: ((0, 1), (0, -1)), 1: ((-1, 1), (1, -1)),
         2: ((-1, 0), (1, 0)), 3: ((-1, -1), (1, 1))}
    keep = np.zeros_like(mag, bool)
    for o, ((dy1, dx1), (dy2, dx2)) in d.items():
        sel = offs == o
        n1 = mp[1 + dy1:1 + dy1 + mag.shape[0],
                1 + dx1:1 + dx1 + mag.shape[1]]
        n2 = mp[1 + dy2:1 + dy2 + mag.shape[0],
                1 + dx2:1 + dx2 + mag.shape[1]]
        keep |= sel & (mag >= n1) & (mag >= n2)
    nms = np.where(keep, mag, 0.0)
    strong = nms >= high
    weak = (nms >= low) & ~strong
    # hysteresis, EXACT: an 8-connected component of candidate pixels
    # survives iff it contains a strong pixel (one labeling pass —
    # iterative flooding would truncate chains longer than the image
    # diameter)
    from scipy import ndimage
    labels, _ = ndimage.label(strong | weak, structure=np.ones((3, 3)))
    keep_ids = np.unique(labels[strong])
    keep_ids = keep_ids[keep_ids != 0]
    return np.isin(labels, keep_ids).astype(np.float32)


@register_op
class Canny(Op):
    """IMAGE -> edge IMAGE (ControlNet hint preprocessor)."""
    TYPE = "Canny"
    WIDGETS = ["low_threshold", "high_threshold"]
    DEFAULTS = {"low_threshold": 0.4, "high_threshold": 0.8}

    def execute(self, ctx: OpContext, image, low_threshold: float = 0.4,
                high_threshold: float = 0.8):
        img = as_image_array(image)
        gray = img @ np.asarray([0.299, 0.587, 0.114], np.float32)
        with Timer("canny"):
            edges = np.stack([_canny_edges(f, float(low_threshold),
                                           float(high_threshold))
                              for f in gray])
        return (np.repeat(edges[..., None], 3, axis=-1),)


@register_op
class ImageFromBatch(Op):
    TYPE = "ImageFromBatch"
    WIDGETS = ["batch_index", "length"]
    DEFAULTS = {"batch_index": 0, "length": 1}

    def execute(self, ctx: OpContext, image, batch_index: int = 0,
                length: int = 1):
        img = as_image_array(image)
        i = min(max(int(batch_index), 0), img.shape[0] - 1)
        return (img[i:i + max(int(length), 1)],)


@register_op
class RebatchImages(Op):
    """IMAGE -> IMAGE (batch_size ignored headless: this framework's
    executor carries whole arrays, so rebatching is an identity — the
    reference node exists to bound per-call VRAM in its executor)."""
    TYPE = "RebatchImages"
    WIDGETS = ["batch_size"]
    DEFAULTS = {"batch_size": 1}

    def execute(self, ctx: OpContext, images, batch_size: int = 1):
        return (as_image_array(images),)


@register_op
class RebatchLatents(Op):
    """LATENT -> LATENT (same identity rationale as RebatchImages)."""
    TYPE = "RebatchLatents"
    WIDGETS = ["batch_size"]
    DEFAULTS = {"batch_size": 1}

    def execute(self, ctx: OpContext, latents, batch_size: int = 1):
        return ({**_latent_meta(latents),
                 "samples": np.asarray(latents["samples"],
                                       np.float32)},)


def _morpho(m: np.ndarray, op: str, size: int) -> np.ndarray:
    """Grayscale morphology with a square structuring element (the
    reference's Morphology node set)."""
    from scipy import ndimage
    k = max(int(size), 1)
    fns = {"erode": ndimage.grey_erosion,
           "dilate": ndimage.grey_dilation,
           "open": ndimage.grey_opening,
           "close": ndimage.grey_closing}
    if op in fns:
        return np.stack([fns[op](f, size=(k, k)) for f in m])
    if op == "gradient":
        return np.stack([ndimage.grey_dilation(f, size=(k, k))
                         - ndimage.grey_erosion(f, size=(k, k))
                         for f in m])
    if op == "top_hat":
        return np.stack([f - ndimage.grey_opening(f, size=(k, k))
                         for f in m])
    if op == "bottom_hat":
        return np.stack([ndimage.grey_closing(f, size=(k, k)) - f
                         for f in m])
    raise ValueError(f"unknown morphology operation {op!r}")


@register_op
class Morphology(Op):
    TYPE = "Morphology"
    WIDGETS = ["operation", "kernel_size"]
    DEFAULTS = {"operation": "dilate", "kernel_size": 3}

    def execute(self, ctx: OpContext, image, operation: str = "dilate",
                kernel_size: int = 3):
        img = as_image_array(image)
        out = np.stack([_morpho(img[..., c], str(operation),
                                int(kernel_size))
                        for c in range(img.shape[-1])], axis=-1)
        return (np.clip(out, 0.0, 1.0).astype(np.float32),)


def _porter_duff(mode, cs, cd, a_s, a_d):
    """The reference node's straight-alpha formula table (the Android
    PorterDuff documentation set it mirrors), applied verbatim to
    unpremultiplied image values — matching the reference's tensors
    exactly, including its known quirks at partial alpha."""
    asr, adr = a_s[..., None], a_d[..., None]
    if mode == "ADD":
        return np.clip(cs + cd, 0, 1), np.clip(a_s + a_d, 0, 1)
    if mode == "CLEAR":
        return np.zeros_like(cs), np.zeros_like(a_s)
    if mode == "DARKEN":
        return ((1 - adr) * cs + (1 - asr) * cd
                + np.minimum(cs, cd)), a_s + (1 - a_s) * a_d
    if mode == "DST":
        return cd, a_d
    if mode == "DST_ATOP":
        return asr * cd + (1 - adr) * cs, a_s
    if mode == "DST_IN":
        return cd * asr, a_s * a_d
    if mode == "DST_OUT":
        return (1 - asr) * cd, (1 - a_s) * a_d
    if mode == "DST_OVER":
        return cd + (1 - adr) * cs, a_d + (1 - a_d) * a_s
    if mode == "LIGHTEN":
        return ((1 - adr) * cs + (1 - asr) * cd
                + np.maximum(cs, cd)), a_s + (1 - a_s) * a_d
    if mode == "MULTIPLY":
        return cs * cd, a_s * a_d
    if mode == "OVERLAY":
        out_a = a_s + (1 - a_s) * a_d
        lo = 2 * cs * cd + cs * (1 - adr) + cd * (1 - asr)
        hi = cs * (1 + adr) + cd * (1 + asr) - 2 * cd * cs - adr * asr
        return np.where(2 * cd <= adr, lo, hi), out_a
    if mode == "SCREEN":
        return cs + cd - cs * cd, a_s + (1 - a_s) * a_d
    if mode == "SRC":
        return cs, a_s
    if mode == "SRC_ATOP":
        return adr * cs + (1 - asr) * cd, a_d
    if mode == "SRC_IN":
        return cs * adr, a_s * a_d
    if mode == "SRC_OUT":
        return (1 - adr) * cs, (1 - a_d) * a_s
    if mode == "SRC_OVER":
        return cs + (1 - asr) * cd, a_s + (1 - a_s) * a_d
    if mode == "XOR":
        return ((1 - adr) * cs + (1 - asr) * cd,
                (1 - a_d) * a_s + (1 - a_s) * a_d)
    raise ValueError(f"unknown Porter-Duff mode {mode!r}")


@register_op
class PorterDuffImageComposite(Op):
    """Porter-Duff compositing of (source, source_alpha) over
    (destination, destination_alpha) — the reference's straight-alpha
    formula table (_porter_duff)."""
    TYPE = "PorterDuffImageComposite"
    WIDGETS = ["mode"]
    DEFAULTS = {"mode": "DST"}

    def execute(self, ctx: OpContext, source, source_alpha, destination,
                destination_alpha, mode: str = "DST"):
        cs = np.asarray(as_image_array(source), np.float32)
        cd = as_image_array(destination)
        if cd.shape[1:3] != cs.shape[1:3]:
            cd = resize_image(cd, cs.shape[2], cs.shape[1], "bilinear")
        cd = _cycle_batch(np.asarray(cd, np.float32), cs.shape[0])

        def _align_alpha(a):
            a = np.asarray(a, np.float32)
            if a.ndim == 2:
                a = a[None]
            if a.shape[1:3] != cs.shape[1:3]:
                a = resize_image(a[..., None], cs.shape[2],
                                 cs.shape[1], "bilinear")[..., 0]
            return _cycle_batch(a, cs.shape[0])

        a_s = _align_alpha(source_alpha)
        a_d = _align_alpha(destination_alpha)
        out_c, out_a = _porter_duff(str(mode).upper(), cs, cd, a_s, a_d)
        return (np.clip(out_c, 0.0, 1.0).astype(np.float32),
                np.clip(out_a, 0.0, 1.0).astype(np.float32))


@register_op
class SplitImageWithAlpha(Op):
    TYPE = "SplitImageWithAlpha"

    def execute(self, ctx: OpContext, image):
        img = np.asarray(image, np.float32)
        if img.ndim == 3:
            img = img[None]
        rgb = img[..., :3]
        alpha = img[..., 3] if img.shape[-1] == 4 \
            else np.ones(img.shape[:3], np.float32)
        # the reference returns the INVERTED alpha as the mask
        return (rgb, 1.0 - alpha)


@register_op
class JoinImageWithAlpha(Op):
    TYPE = "JoinImageWithAlpha"

    def execute(self, ctx: OpContext, image, alpha):
        img = as_image_array(image)[..., :3]
        a = np.asarray(alpha, np.float32)
        if a.ndim == 2:
            a = a[None]
        if a.shape[1:3] != img.shape[1:3]:
            a = resize_image(a[..., None], img.shape[2], img.shape[1],
                             "bilinear")[..., 0]
        a = _cycle_batch(a, img.shape[0])
        # inverse of SplitImageWithAlpha's inverted-mask convention
        return (np.concatenate([img, (1.0 - a)[..., None]], axis=-1)
                .astype(np.float32),)


@register_op
class LatentBatchSeedBehavior(Op):
    """'fixed': every latent in the batch gets the SAME noise stream
    (the per-sample fold-in index zeroes); 'random' (default) keeps
    per-sample streams."""
    TYPE = "LatentBatchSeedBehavior"
    WIDGETS = ["seed_behavior"]
    DEFAULTS = {"seed_behavior": "random"}

    def execute(self, ctx: OpContext, samples,
                seed_behavior: str = "random"):
        out = {**_latent_meta(samples),
               "samples": np.asarray(samples["samples"], np.float32)}
        if str(seed_behavior) == "fixed":
            out["seed_fixed_batch"] = True
        else:
            out.pop("seed_fixed_batch", None)
        return (out,)


@register_op
class ImageCompositeMasked(Op):
    """Paste ``source`` over ``destination`` at pixel (x, y), optionally
    through a MASK; ``resize_source`` first scales the source to the
    destination's dims."""
    TYPE = "ImageCompositeMasked"
    WIDGETS = ["x", "y", "resize_source"]
    DEFAULTS = {"x": 0, "y": 0, "resize_source": False}

    def execute(self, ctx: OpContext, destination, source, x: int = 0,
                y: int = 0, resize_source=False, mask=None):
        dest = as_image_array(destination)
        src = as_image_array(source)
        if str(resize_source).lower() not in ("false", "0", ""):
            src = resize_image(src, dest.shape[2], dest.shape[1],
                               "bilinear")
        return (_paste(dest, src, int(x), int(y), mask),)


@register_op
class LatentCompositeMasked(Op):
    """LatentComposite through an optional mask; x/y are pixels, //8 to
    latent units (ComfyUI convention)."""
    TYPE = "LatentCompositeMasked"
    WIDGETS = ["x", "y", "resize_source"]
    DEFAULTS = {"x": 0, "y": 0, "resize_source": False}

    def execute(self, ctx: OpContext, destination, source, x: int = 0,
                y: int = 0, resize_source=False, mask=None):
        dest = np.asarray(destination["samples"], np.float32)
        src = np.asarray(source["samples"], np.float32)
        if str(resize_source).lower() not in ("false", "0", ""):
            src = resize_image(src, dest.shape[2], dest.shape[1],
                               "bilinear")
        out = _paste(dest, src, int(x) // 8, int(y) // 8, mask)
        return ({**_latent_meta(destination), "samples": out},)


@register_op
class LatentComposite(Op):
    """Paste one latent onto another at pixel (x, y) (//8 latent units)
    with a ``feather``-pixel edge ramp on the pasted rect."""
    TYPE = "LatentComposite"
    WIDGETS = ["x", "y", "feather"]
    DEFAULTS = {"x": 0, "y": 0, "feather": 0}

    def execute(self, ctx: OpContext, samples_to, samples_from,
                x: int = 0, y: int = 0, feather: int = 0):
        dest = np.asarray(samples_to["samples"], np.float32)
        src = np.asarray(samples_from["samples"], np.float32)
        xl, yl = int(x) // 8, int(y) // 8
        f = max(int(feather), 0) // 8
        mask = None
        if f > 0:
            h, w = src.shape[1], src.shape[2]
            H, W = dest.shape[1], dest.shape[2]
            mask = np.ones((1, h, w), np.float32)
            # ComfyUI semantics: an edge ramps only when destination
            # content exists beyond it (border-flush pastes stay solid)
            # and corner rates MULTIPLY
            for t in range(min(f, h, w)):
                rate = (t + 1) / f
                if yl != 0:
                    mask[:, t, :] *= rate
                if yl + h < H:
                    mask[:, h - 1 - t, :] *= rate
                if xl != 0:
                    mask[:, :, t] *= rate
                if xl + w < W:
                    mask[:, :, w - 1 - t] *= rate
        out = _paste(dest, src, xl, yl, mask)
        return ({**_latent_meta(samples_to), "samples": out},)


def _counted_output_path(ctx: OpContext, filename_prefix: str,
                         ext: str) -> str:
    """Counter-suffixed save path (never-overwrite semantics shared
    with SaveImage: a second queue of the same workflow must not
    clobber earlier outputs)."""
    probe = _safe_output_path(ctx.output_dir or os.getcwd(),
                              f"{filename_prefix}_00000.{ext}")
    d, fname = os.path.split(probe)
    base = fname[: -len(f"_00000.{ext}")]
    os.makedirs(d, exist_ok=True)
    n = _next_image_counter(d, base, ext)
    return os.path.join(d, f"{base}_{n:05d}.{ext}")


@register_op
class SaveLatent(Op):
    """Write the latent batch as a ``.latent`` safetensors (the
    reference's format: key ``latent_tensor`` in NCHW + a
    ``latent_format_version_0`` marker)."""
    TYPE = "SaveLatent"
    OUTPUT_NODE = True
    WIDGETS = ["filename_prefix"]
    DEFAULTS = {"filename_prefix": "latents/save"}

    def execute(self, ctx: OpContext, samples,
                filename_prefix: str = "latents/save"):
        # save_state_dict, not raw safetensors save_file: the NCHW
        # transpose is a strided view and save_file ignores strides
        from comfyui_distributed_tpu.models.checkpoints import \
            save_state_dict
        path = _counted_output_path(ctx, filename_prefix, "latent")
        lat = np.asarray(samples["samples"], np.float32)
        save_state_dict({"latent_tensor": lat.transpose(0, 3, 1, 2),
                         "latent_format_version_0": np.asarray([0])},
                        path)
        debug_log(f"SaveLatent: wrote {path}")
        return ()


@register_op
class LoadLatent(Op):
    TYPE = "LoadLatent"
    WIDGETS = ["latent"]

    def execute(self, ctx: OpContext, latent: str):
        from safetensors import safe_open
        path = latent
        if ctx.input_dir and not os.path.isabs(path):
            path = os.path.join(ctx.input_dir, latent)
        with safe_open(path, framework="numpy") as f:
            keys = set(f.keys())
            lat = np.asarray(f.get_tensor("latent_tensor"), np.float32)
        # reference parity: files WITHOUT the version marker predate
        # latent standardization and stored SCALED latents
        if "latent_format_version_0" not in keys:
            lat = lat * (1.0 / 0.18215)
        # reference files are NCHW; this framework is NHWC
        return ({"samples": lat.transpose(0, 2, 3, 1)},)


@register_op
class SaveAnimatedWEBP(Op):
    """Write the image batch as one animated WEBP."""
    TYPE = "SaveAnimatedWEBP"
    OUTPUT_NODE = True
    WIDGETS = ["filename_prefix", "fps", "lossless", "quality"]
    DEFAULTS = {"filename_prefix": "anim/save", "fps": 6.0,
                "lossless": True, "quality": 80}

    def execute(self, ctx: OpContext, images,
                filename_prefix: str = "anim/save", fps: float = 6.0,
                lossless=True, quality: int = 80, method: str = "default"):
        frames = [tensor_to_pil(f) for f in as_image_array(images)]
        path = _counted_output_path(ctx, filename_prefix, "webp")
        methods = {"default": 4, "fastest": 0, "slowest": 6}
        frames[0].save(
            path, save_all=True, append_images=frames[1:],
            duration=int(1000.0 / max(float(fps), 0.01)), loop=0,
            lossless=str(lossless).lower() not in ("false", "0", ""),
            quality=int(quality),
            method=methods.get(str(method), 4))
        debug_log(f"SaveAnimatedWEBP: wrote {path} "
                  f"({len(frames)} frames)")
        return ()


@register_op
class SaveAnimatedPNG(Op):
    """Write the image batch as one APNG."""
    TYPE = "SaveAnimatedPNG"
    OUTPUT_NODE = True
    WIDGETS = ["filename_prefix", "fps", "compress_level"]
    DEFAULTS = {"filename_prefix": "anim/save", "fps": 6.0,
                "compress_level": 4}

    def execute(self, ctx: OpContext, images,
                filename_prefix: str = "anim/save", fps: float = 6.0,
                compress_level: int = 4):
        frames = [tensor_to_pil(f) for f in as_image_array(images)]
        path = _counted_output_path(ctx, filename_prefix, "png")
        frames[0].save(
            path, save_all=True, append_images=frames[1:],
            duration=int(1000.0 / max(float(fps), 0.01)), loop=0,
            compress_level=int(compress_level),
            pnginfo=_png_metadata(ctx))
        debug_log(f"SaveAnimatedPNG: wrote {path} "
                  f"({len(frames)} frames)")
        return ()


@register_op
class SetLatentNoiseMask(Op):
    """Attach an inpaint mask to a latent batch (1 = resample, 0 = keep
    source); samplers blend per ComfyUI's KSamplerX0Inpaint semantics."""
    TYPE = "SetLatentNoiseMask"

    def execute(self, ctx: OpContext, samples, mask):
        m = np.asarray(mask, np.float32)
        if m.ndim == 2:
            m = m[None]
        # meta spread FIRST: _latent_meta forwards any pre-existing
        # noise_mask, and the NEW mask must win over it
        out = {**_latent_meta(samples),
               "samples": np.asarray(samples["samples"], np.float32),
               "noise_mask": m}
        return (out,)


@register_op
class ImagePadForOutpaint(Op):
    """ComfyUI's outpaint prep: extend the canvas with mid-gray on the
    requested sides and return (padded image, mask) where the mask is 1
    over the new area and feathers quadratically to 0 inside the original
    border — feed both into VAEEncodeForInpaint to outpaint."""
    TYPE = "ImagePadForOutpaint"
    WIDGETS = ["left", "top", "right", "bottom", "feathering"]
    DEFAULTS = {"left": 0, "top": 0, "right": 0, "bottom": 0,
                "feathering": 40}

    def execute(self, ctx: OpContext, image, left: int = 0, top: int = 0,
                right: int = 0, bottom: int = 0, feathering: int = 40):
        img = np.asarray(as_image_array(image), np.float32)
        b, h, w, c = img.shape
        left, top = max(int(left), 0), max(int(top), 0)
        right, bottom = max(int(right), 0), max(int(bottom), 0)
        out = np.full((b, h + top + bottom, w + left + right, c), 0.5,
                      np.float32)
        out[:, top:top + h, left:left + w] = img
        mask = np.ones((h + top + bottom, w + left + right), np.float32)
        inner = np.zeros((h, w), np.float32)
        f = int(feathering)
        if f > 0 and f * 2 < h and f * 2 < w:
            # distance to each EXTENDED edge (a side that isn't extended
            # contributes no feather); v = ((f - d)/f)^2 inside the band
            rows = np.arange(h, dtype=np.float32)[:, None]
            cols = np.arange(w, dtype=np.float32)[None, :]
            d = np.full((h, w), np.float32(max(h, w)))
            if top:
                d = np.minimum(d, rows)
            if bottom:
                d = np.minimum(d, h - rows)
            if left:
                d = np.minimum(d, cols)
            if right:
                d = np.minimum(d, w - cols)
            v = np.clip((f - d) / f, 0.0, 1.0)
            inner = (v * v).astype(np.float32)
        mask[top:top + h, left:left + w] = inner
        return (_keep_fanout_meta(image, out), mask)


@register_op
class VAEEncodeForInpaint(Op):
    """ComfyUI's inpaint encode: neutralize the masked region to mid-gray
    before encoding (so the encoder doesn't leak the old content into
    neighboring latents), grow the mask, attach it as noise_mask."""
    TYPE = "VAEEncodeForInpaint"
    WIDGETS = ["grow_mask_by"]
    DEFAULTS = {"grow_mask_by": 6}

    def execute(self, ctx: OpContext, pixels, vae, mask,
                grow_mask_by: int = 6):
        img = np.asarray(as_image_array(pixels), np.float32)
        m = np.asarray(mask, np.float32)
        if m.ndim == 2:
            m = m[None]
        if m.shape[1:3] != img.shape[1:3]:
            # ComfyUI interpolates the mask to the pixel size — the
            # LoadImage mask keeps the ORIGINAL image's dims while the
            # pixels may have gone through ImageScale
            m = resize_image(m[..., None], img.shape[2],
                             img.shape[1], "bilinear")[..., 0]
        grow = max(int(grow_mask_by), 0)
        if grow:
            # dilate by max-pooling: a (2g+1)-square structuring element
            from scipy import ndimage  # scipy ships with jax's deps
            m = np.stack([ndimage.maximum_filter(mi, size=2 * grow + 1)
                          for mi in m])
        # neutralize with the GROWN mask: pixels anywhere in the grown
        # band will be resampled, so their old content must not leak
        # into the encoder (ComfyUI rounds the grown mask here)
        hard = (m > 0.5).astype(np.float32)
        img = (img - 0.5) * (1.0 - hard[..., None]) + 0.5
        with Timer("vae_encode_inpaint"):
            lat = vae.vae_encode(jnp.asarray(img))
        # shared fan-out rule (already-fanned pixels pass through — a
        # re-tile here would square the fan-out); the mask rides along at
        # its own batch size, _prepare_sample_inputs cycles it
        (out_d,) = _expand_encoded_latent(ctx, pixels, lat)
        out_d["noise_mask"] = m
        return (out_d,)


@register_op
class InpaintModelConditioning(Op):
    """ComfyUI's inpaint-MODEL prep (9-channel checkpoints like
    sd-v1-5-inpainting): encode BOTH the original pixels (the sampled
    latent) and a masked-neutralized copy (the UNet's extra concat
    channels), attach [mask, masked-latent] to both conditionings, and
    optionally ride the mask as a noise_mask too."""
    TYPE = "InpaintModelConditioning"
    WIDGETS = ["noise_mask"]
    DEFAULTS = {"noise_mask": True}

    def execute(self, ctx: OpContext, positive: Conditioning,
                negative: Conditioning, vae, pixels, mask,
                noise_mask=True):
        img = np.asarray(as_image_array(pixels), np.float32)
        m = np.asarray(mask, np.float32)
        if m.ndim == 2:
            m = m[None]
        if m.shape[1:3] != img.shape[1:3]:
            m = resize_image(m[..., None], img.shape[2],
                             img.shape[1], "bilinear")[..., 0]
        hard = (m > 0.5).astype(np.float32)
        neutral = (img - 0.5) * (1.0 - hard[..., None]) + 0.5
        with Timer("inpaint_model_cond_encode"):
            orig_lat = np.asarray(vae.vae_encode(jnp.asarray(img)),
                                  np.float32)
            masked_lat = np.asarray(vae.vae_encode(jnp.asarray(neutral)),
                                    np.float32)
        h, w = orig_lat.shape[1], orig_lat.shape[2]
        m_lat = _image_mask_to_latent(m, h, w, orig_lat.shape[0])
        m_lat = _cycle_batch(m_lat, orig_lat.shape[0])
        concat = np.concatenate([m_lat, masked_lat], axis=-1)
        pos2 = dataclasses.replace(positive, concat_latent=concat)
        neg2 = dataclasses.replace(negative, concat_latent=concat)
        (out_d,) = _expand_encoded_latent(ctx, pixels, orig_lat)
        if str(noise_mask).lower() not in ("false", "0", ""):
            out_d["noise_mask"] = m
        return (pos2, neg2, out_d)


@register_op
class InstructPixToPixConditioning(Op):
    """InstructPix2Pix prep: the source image's latent rides every model
    call as concat channels (8-channel UNets), sampling starts from a
    zero latent of the same spatial dims; both CFG sides carry the
    concat (the ecosystem sets it on positive AND negative)."""
    TYPE = "InstructPixToPixConditioning"

    def execute(self, ctx: OpContext, positive: Conditioning,
                negative: Conditioning, vae, pixels):
        img = np.asarray(as_image_array(pixels), np.float32)
        with Timer("ip2p_cond_encode"):
            concat = np.asarray(vae.vae_encode(jnp.asarray(img)),
                                np.float32)
        pos2 = dataclasses.replace(positive, concat_latent=concat)
        neg2 = dataclasses.replace(negative, concat_latent=concat)
        (out_d,) = _expand_encoded_latent(ctx, pixels,
                                          np.zeros_like(concat))
        return (pos2, neg2, out_d)


class ImageBatch(np.ndarray):
    """IMAGE ndarray carrying fan-out metadata through image-space ops."""

    def __new__(cls, arr, local_batch: Optional[int] = None,
                fanout: int = 1):
        obj = np.asarray(arr, dtype=np.float32).view(cls)
        obj.local_batch = local_batch
        obj.fanout = fanout
        return obj

    def __array_finalize__(self, obj):
        if obj is not None:
            self.local_batch = getattr(obj, "local_batch", None)
            self.fanout = getattr(obj, "fanout", 1)


@register_op
class ConditioningConcat(Op):
    """Concatenate conditionings along the TOKEN axis (prompt chaining).
    Applies to EVERY entry of a multi-entry ``conditioning_to`` (ComfyUI
    loops the cond list); only ``conditioning_from``'s primary entry is
    used, like ComfyUI's warning-and-first behavior."""
    TYPE = "ConditioningConcat"

    def execute(self, ctx: OpContext, conditioning_to: Conditioning,
                conditioning_from: Conditioning):
        if getattr(conditioning_from, "siblings", ()):
            debug_log("ConditioningConcat: conditioning_from has multiple "
                      "entries; using the first (ComfyUI behavior)")
        c_from = conditioning_from.context

        def _cat(e: Conditioning) -> Conditioning:
            return dataclasses.replace(
                e, context=jnp.concatenate([e.context, c_from], axis=1),
                control=e.control or conditioning_from.control)

        return (dataclasses.replace(
            _cat(conditioning_to),
            siblings=tuple(_cat(s)
                           for s in conditioning_to.siblings)),)


@register_op
class ConditioningAverage(Op):
    """Weighted blend of two conditionings.  Applies to EVERY entry of a
    multi-entry ``conditioning_to`` (ComfyUI loops the cond list; only
    ``conditioning_from``'s primary entry is blended in)."""
    TYPE = "ConditioningAverage"
    WIDGETS = ["conditioning_to_strength"]
    DEFAULTS = {"conditioning_to_strength": 1.0}

    def execute(self, ctx: OpContext, conditioning_to: Conditioning,
                conditioning_from: Conditioning,
                conditioning_to_strength: float = 1.0):
        if getattr(conditioning_from, "siblings", ()):
            debug_log("ConditioningAverage: conditioning_from has "
                      "multiple entries; using the first (ComfyUI "
                      "behavior)")
        w = float(conditioning_to_strength)

        def _blend(e: Conditioning) -> Conditioning:
            c_to, c_from = e.context, conditioning_from.context
            if c_from.shape[1] != c_to.shape[1]:
                # ComfyUI zero-pads/truncates cond_from to cond_to's len
                t0 = c_to.shape[1]
                if c_from.shape[1] < t0:
                    c_from = jnp.pad(
                        c_from,
                        ((0, 0), (0, t0 - c_from.shape[1]), (0, 0)))
                else:
                    c_from = c_from[:, :t0, :]
            ctx_out = c_to * w + c_from * (1.0 - w)
            # pooled fallback order matches ComfyUI: to's, else from's
            pooled = e.pooled
            if pooled is not None and conditioning_from.pooled is not None:
                pooled = pooled * w + conditioning_from.pooled * (1.0 - w)
            elif pooled is None:
                pooled = conditioning_from.pooled
            return dataclasses.replace(
                e, context=ctx_out, pooled=pooled,
                control=e.control or conditioning_from.control)

        return (dataclasses.replace(
            _blend(conditioning_to),
            siblings=tuple(_blend(s)
                           for s in conditioning_to.siblings)),)


@register_op
class ConditioningCombine(Op):
    """ComfyUI's Combine: BOTH conditionings are evaluated at sample
    time and their denoised predictions blend (by their masks/strengths
    — regional prompting when paired with ConditioningSetMask/SetArea).
    Bundled as sibling entries; the KSampler stacks every entry into one
    model call (samplers.cfg_denoiser_multi)."""
    TYPE = "ConditioningCombine"

    def execute(self, ctx: OpContext, conditioning_1: Conditioning,
                conditioning_2: Conditioning):
        def flat(c: Conditioning):
            return (dataclasses.replace(c, siblings=()),) + tuple(c.siblings)

        merged = flat(conditioning_1) + flat(conditioning_2)
        return (dataclasses.replace(merged[0], siblings=merged[1:]),)


@register_op
class ConditioningSetMask(Op):
    """Restrict a conditioning's influence to a mask (ComfyUI regional
    prompting).  ``set_cond_area="default"`` semantics: every entry still
    evaluates on the full latent (static shapes) and the mask weights the
    denoised blend — the "mask bounds" crop variant is intentionally not
    implemented (dynamic shapes defeat XLA compilation)."""
    TYPE = "ConditioningSetMask"
    WIDGETS = ["strength", "set_cond_area"]
    DEFAULTS = {"strength": 1.0, "set_cond_area": "default"}

    def execute(self, ctx: OpContext, conditioning: Conditioning, mask,
                strength: float = 1.0, set_cond_area: str = "default"):
        m = np.asarray(mask, np.float32)
        if m.ndim == 2:
            m = m[None]
        return (_set_area_on_all(conditioning, m, float(strength)),)


@register_op
class ConditioningSetArea(Op):
    """Rectangular region in pixels (ComfyUI's //8 latent-unit
    convention); materialized against the actual latent dims at sample
    time."""
    TYPE = "ConditioningSetArea"
    WIDGETS = ["width", "height", "x", "y", "strength"]
    DEFAULTS = {"strength": 1.0}

    def execute(self, ctx: OpContext, conditioning: Conditioning,
                width: int, height: int, x: int, y: int,
                strength: float = 1.0):
        rect = ("px", int(x), int(y), int(width), int(height))
        return (_set_area_on_all(conditioning, rect, float(strength)),)


@register_op
class ConditioningSetAreaPercentage(Op):
    """Rectangular region in canvas fractions (resolution-independent)."""
    TYPE = "ConditioningSetAreaPercentage"
    WIDGETS = ["width", "height", "x", "y", "strength"]
    DEFAULTS = {"strength": 1.0}

    def execute(self, ctx: OpContext, conditioning: Conditioning,
                width: float, height: float, x: float, y: float,
                strength: float = 1.0):
        rect = ("pct", float(x), float(y), float(width), float(height))
        return (_set_area_on_all(conditioning, rect, float(strength)),)


@register_op
class ConditioningSetTimestepRange(Op):
    """ComfyUI's prompt scheduling: the conditioning contributes only
    within the [start, end] sampling-percent window (inclusive sigma
    bounds, matching ComfyUI; 0.0 = the very start / sigma_max side).  Applied to every entry of a cond list; the
    gate is a traced elementwise select on the step sigma — no dynamic
    control flow under jit."""
    TYPE = "ConditioningSetTimestepRange"
    WIDGETS = ["start", "end"]
    DEFAULTS = {"start": 0.0, "end": 1.0}

    def execute(self, ctx: OpContext, conditioning: Conditioning,
                start: float = 0.0, end: float = 1.0):
        rng = (float(start), float(end))
        return (dataclasses.replace(
            conditioning, timestep_range=rng,
            siblings=tuple(dataclasses.replace(s, timestep_range=rng)
                           for s in conditioning.siblings)),)


def _set_area_on_all(cond: Conditioning, area, strength: float):
    """Apply a mask/area to the conditioning AND every bundled sibling —
    ComfyUI's Set nodes loop over all entries of a cond list, so masking
    downstream of a Combine must restrict both prompts."""
    return dataclasses.replace(
        cond, area_mask=area, area_strength=strength,
        siblings=tuple(dataclasses.replace(s, area_mask=area,
                                           area_strength=strength)
                       for s in cond.siblings))


def _latent_pair(samples1, samples2):
    a = np.asarray(samples1["samples"], np.float32)
    b = np.asarray(samples2["samples"], np.float32)
    if a.shape[1:3] != b.shape[1:3]:
        b = resize_image(b, a.shape[2], a.shape[1], "bilinear")
    return a, _cycle_batch(b, a.shape[0])


@register_op
class LatentAdd(Op):
    TYPE = "LatentAdd"

    def execute(self, ctx: OpContext, samples1, samples2):
        a, b = _latent_pair(samples1, samples2)
        return ({**_latent_meta(samples1), "samples": a + b},)


@register_op
class LatentSubtract(Op):
    TYPE = "LatentSubtract"

    def execute(self, ctx: OpContext, samples1, samples2):
        a, b = _latent_pair(samples1, samples2)
        return ({**_latent_meta(samples1), "samples": a - b},)


@register_op
class LatentMultiply(Op):
    TYPE = "LatentMultiply"
    WIDGETS = ["multiplier"]
    DEFAULTS = {"multiplier": 1.0}

    def execute(self, ctx: OpContext, samples, multiplier: float = 1.0):
        lat = np.asarray(samples["samples"], np.float32)
        return ({**_latent_meta(samples),
                 "samples": lat * float(multiplier)},)


@register_op
class LatentInterpolate(Op):
    """Direction-magnitude interpolation (ComfyUI nodes_latent): unit
    directions blend by ``ratio`` per pixel across channels, then the
    result rescales to the interpolated magnitudes."""
    TYPE = "LatentInterpolate"
    WIDGETS = ["ratio"]
    DEFAULTS = {"ratio": 1.0}

    def execute(self, ctx: OpContext, samples1, samples2,
                ratio: float = 1.0):
        a, b = _latent_pair(samples1, samples2)
        t = float(ratio)
        m1 = np.linalg.norm(a, axis=-1, keepdims=True)
        m2 = np.linalg.norm(b, axis=-1, keepdims=True)
        d1 = a / np.maximum(m1, 1e-10)
        d2 = b / np.maximum(m2, 1e-10)
        out = d1 * t + d2 * (1.0 - t)
        mo = np.linalg.norm(out, axis=-1, keepdims=True)
        out = out / np.maximum(mo, 1e-10) * (m1 * t + m2 * (1.0 - t))
        return ({**_latent_meta(samples1), "samples": out},)


@register_op
class LatentFlip(Op):
    TYPE = "LatentFlip"
    WIDGETS = ["flip_method"]
    DEFAULTS = {"flip_method": "x-axis: vertically"}

    def execute(self, ctx: OpContext, samples,
                flip_method: str = "x-axis: vertically"):
        lat = np.asarray(samples["samples"], np.float32)
        axis = 1 if str(flip_method).startswith("x") else 2
        return ({**_latent_meta(samples),
                 "samples": np.flip(lat, axis=axis).copy()},)


@register_op
class LatentRotate(Op):
    TYPE = "LatentRotate"
    WIDGETS = ["rotation"]
    DEFAULTS = {"rotation": "none"}

    def execute(self, ctx: OpContext, samples, rotation: str = "none"):
        lat = np.asarray(samples["samples"], np.float32)
        r = str(rotation)
        k = 0
        if r.startswith("90"):
            k = 3          # 90 deg clockwise (ComfyUI's orientation)
        elif r.startswith("180"):
            k = 2
        elif r.startswith("270"):
            k = 1
        out = np.rot90(lat, k=k, axes=(1, 2)).copy() if k else lat
        return ({**_latent_meta(samples), "samples": out},)


@register_op
class LatentCrop(Op):
    """Crop a latent batch; x/y/width/height are PIXELS, //8 to latent
    units (ComfyUI convention)."""
    TYPE = "LatentCrop"
    WIDGETS = ["width", "height", "x", "y"]

    def execute(self, ctx: OpContext, samples, width: int, height: int,
                x: int = 0, y: int = 0):
        lat = np.asarray(samples["samples"], np.float32)
        H, W = lat.shape[1], lat.shape[2]
        w = max(int(width) // 8, 1)
        h = max(int(height) // 8, 1)
        x0 = min(max(int(x) // 8, 0), max(W - w, 0))
        y0 = min(max(int(y) // 8, 0), max(H - h, 0))
        out = lat[:, y0:y0 + h, x0:x0 + w]
        return ({**_latent_meta(samples), "samples": out.copy()},)


@register_op
class LatentBlend(Op):
    """samples1 * blend_factor + samples2 * (1 - blend_factor); the
    second latent resizes to the first's dims when they differ."""
    TYPE = "LatentBlend"
    WIDGETS = ["blend_factor"]
    DEFAULTS = {"blend_factor": 0.5}

    def execute(self, ctx: OpContext, samples1, samples2,
                blend_factor: float = 0.5):
        a, b = _latent_pair(samples1, samples2)
        f = float(blend_factor)
        return ({**_latent_meta(samples1), "samples": a * f
                 + b * (1.0 - f)},)


@register_op
class LatentBatch(Op):
    """Concatenate two latent batches (the second spatially resizes to
    the first).  The result is a plain re-batched latent — fan-out meta
    does not survive an arbitrary concat."""
    TYPE = "LatentBatch"

    def execute(self, ctx: OpContext, samples1, samples2):
        a = np.asarray(samples1["samples"], np.float32)
        b = np.asarray(samples2["samples"], np.float32)
        if a.shape[1:3] != b.shape[1:3]:
            b = resize_image(b, a.shape[2], a.shape[1], "bilinear")
        return ({"samples": np.concatenate([a, b], axis=0)},)


@register_op
class ConditioningZeroOut(Op):
    """Zero the context and pooled outputs (ComfyUI's 'negative that is
    truly nothing' — SDXL-refiner style unconditional)."""
    TYPE = "ConditioningZeroOut"

    def execute(self, ctx: OpContext, conditioning: Conditioning):
        z = dataclasses.replace(
            conditioning,
            context=jnp.zeros_like(jnp.asarray(conditioning.context)),
            pooled=(jnp.zeros_like(jnp.asarray(conditioning.pooled))
                    if conditioning.pooled is not None else None),
            siblings=tuple(
                dataclasses.replace(
                    s, context=jnp.zeros_like(jnp.asarray(s.context)),
                    pooled=(jnp.zeros_like(jnp.asarray(s.pooled))
                            if s.pooled is not None else None))
                for s in getattr(conditioning, "siblings", ()) or ()))
        return (z,)


@register_op
class ConditioningSetAreaStrength(Op):
    TYPE = "ConditioningSetAreaStrength"
    WIDGETS = ["strength"]
    DEFAULTS = {"strength": 1.0}

    def execute(self, ctx: OpContext, conditioning: Conditioning,
                strength: float = 1.0):
        s = float(strength)
        return (dataclasses.replace(
            conditioning, area_strength=s,
            siblings=tuple(dataclasses.replace(e, area_strength=s)
                           for e in getattr(conditioning, "siblings",
                                            ()) or ())),)


def _gaussian_kernel(radius: int, sigma: float) -> np.ndarray:
    xs = np.arange(-radius, radius + 1, dtype=np.float32)
    k = np.exp(-(xs ** 2) / max(2.0 * sigma * sigma, 1e-8))
    return k / k.sum()


def _gaussian_blur(img: np.ndarray, radius: int,
                   sigma: float) -> np.ndarray:
    """Separable gaussian blur, reflect padding (ComfyUI's ImageBlur
    border convention), [B,H,W,C]."""
    k = _gaussian_kernel(radius, sigma)
    pad = [(0, 0), (radius, radius), (0, 0), (0, 0)]
    x = np.pad(img, pad, mode="reflect")
    x = sum(k[i] * x[:, i:i + img.shape[1]] for i in range(len(k)))
    pad = [(0, 0), (0, 0), (radius, radius), (0, 0)]
    x = np.pad(x, pad, mode="reflect")
    return sum(k[i] * x[:, :, i:i + img.shape[2]] for i in range(len(k)))


@register_op
class ImageBlur(Op):
    TYPE = "ImageBlur"
    WIDGETS = ["blur_radius", "sigma"]
    DEFAULTS = {"blur_radius": 1, "sigma": 1.0}

    def execute(self, ctx: OpContext, image, blur_radius: int = 1,
                sigma: float = 1.0):
        img = as_image_array(image)
        r = int(blur_radius)
        if r < 1:
            return (img,)
        return (_gaussian_blur(img, r, float(sigma)).astype(np.float32),)


@register_op
class ImageSharpen(Op):
    """Unsharp mask: image + alpha * (image - gaussian_blur(image))."""
    TYPE = "ImageSharpen"
    WIDGETS = ["sharpen_radius", "sigma", "alpha"]
    DEFAULTS = {"sharpen_radius": 1, "sigma": 1.0, "alpha": 1.0}

    def execute(self, ctx: OpContext, image, sharpen_radius: int = 1,
                sigma: float = 1.0, alpha: float = 1.0):
        img = as_image_array(image)
        r = int(sharpen_radius)
        if r < 1:
            return (img,)
        blurred = _gaussian_blur(img, r, float(sigma))
        out = img + float(alpha) * (img - blurred)
        return (np.clip(out, 0.0, 1.0).astype(np.float32),)


@register_op
class ImageQuantize(Op):
    """Reduce to ``colors`` palette entries via PIL quantization
    (dither: none / floyd-steinberg)."""
    TYPE = "ImageQuantize"
    WIDGETS = ["colors", "dither"]
    DEFAULTS = {"colors": 256, "dither": "floyd-steinberg"}

    def execute(self, ctx: OpContext, image, colors: int = 256,
                dither: str = "floyd-steinberg"):
        from PIL import Image
        img = as_image_array(image)
        dm = Image.Dither.FLOYDSTEINBERG \
            if str(dither).startswith("floyd") else Image.Dither.NONE
        out = []
        for frame in img:
            pil = Image.fromarray(
                (np.clip(frame, 0, 1) * 255).astype(np.uint8))
            # two-pass like the reference: PIL ignores ``dither`` unless
            # quantizing AGAINST a palette image, so build the median-cut
            # palette first, then re-quantize with dithering
            pal = pil.quantize(colors=max(int(colors), 1))
            q = pil.quantize(colors=max(int(colors), 1), palette=pal,
                             dither=dm)
            out.append(np.asarray(q.convert("RGB"), np.float32) / 255.0)
        return (np.stack(out),)


@register_op
class ImageScaleToTotalPixels(Op):
    TYPE = "ImageScaleToTotalPixels"
    WIDGETS = ["upscale_method", "megapixels"]
    DEFAULTS = {"upscale_method": "lanczos", "megapixels": 1.0}

    def execute(self, ctx: OpContext, image,
                upscale_method: str = "lanczos",
                megapixels: float = 1.0):
        img = as_image_array(image)
        H, W = img.shape[1], img.shape[2]
        scale = math.sqrt(float(megapixels) * 1024 * 1024 / (H * W))
        w = max(int(round(W * scale)), 1)
        h = max(int(round(H * scale)), 1)
        return (resize_image(img, w, h, str(upscale_method)),)


@register_op
class RepeatLatentBatch(Op):
    TYPE = "RepeatLatentBatch"
    WIDGETS = ["amount"]
    DEFAULTS = {"amount": 1}

    def execute(self, ctx: OpContext, samples, amount: int = 1):
        lat = np.asarray(samples["samples"], np.float32)
        n = max(int(amount), 1)
        meta = _latent_meta(samples)
        fanout = int(meta.get("fanout", 1))
        if fanout > 1:
            # repeat WITHIN each replica block: replica r owns contiguous
            # rows [r*local_b, (r+1)*local_b) and a whole-batch tile would
            # interleave replicas' latents
            out = np.concatenate([np.tile(blk, (n, 1, 1, 1))
                                  for blk in np.split(lat, fanout)], axis=0)
        else:
            out = np.tile(lat, (n, 1, 1, 1))
        if "local_batch" in meta:
            meta["local_batch"] = meta["local_batch"] * n
        return ({"samples": out, **meta},)


@register_op
class LatentFromBatch(Op):
    """Slice [batch_index, batch_index+length) out of a latent batch."""
    TYPE = "LatentFromBatch"
    WIDGETS = ["batch_index", "length"]
    DEFAULTS = {"batch_index": 0, "length": 1}

    def execute(self, ctx: OpContext, samples, batch_index: int = 0,
                length: int = 1):
        lat = np.asarray(samples["samples"], np.float32)
        i = min(max(int(batch_index), 0), lat.shape[0] - 1)
        n = min(max(int(length), 1), lat.shape[0] - i)
        # slicing breaks replica alignment: the result is a plain batch
        out = {"samples": lat[i:i + n]}
        if "noise_mask" in samples:
            # the mask travels with its rows (ComfyUI slices it alongside;
            # dropping it would silently resample the whole image)
            m = np.asarray(samples["noise_mask"], np.float32)
            if m.ndim == 2:
                m = m[None]
            if m.shape[0] == 1:
                out["noise_mask"] = m
            else:  # short mask cycles the batch before slicing
                out["noise_mask"] = _cycle_batch(m, lat.shape[0])[i:i + n]
        return (out,)


@register_op
class CheckpointSave(Op):
    """Export the (possibly LoRA-patched) pipeline back to a single-file
    torch-layout checkpoint — the interop loop back into the reference's
    ecosystem (\"same models on all machines\", reference README:189-193)."""
    TYPE = "CheckpointSave"
    OUTPUT_NODE = True
    WIDGETS = ["filename_prefix"]
    DEFAULTS = {"filename_prefix": "checkpoints/save"}

    def execute(self, ctx: OpContext, model, clip, vae,
                filename_prefix: str = "checkpoints/save"):
        from comfyui_distributed_tpu.models.checkpoints import save_checkpoint
        path = _safe_output_path(ctx.output_dir or os.getcwd(),
                                 f"{filename_prefix}.safetensors")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        import jax
        if any(getattr(a, "dtype", None) == jnp.bfloat16
               for a in jax.tree_util.tree_leaves(model.unet_params)):
            # bf16 weight STORAGE (registry.load_pipeline) reaches the
            # export: the saved file will be bf16 — fine for reuse, but
            # not a bit-exact round-trip of an fp32/fp16 source.  For a
            # full-precision export: DTPU_BF16_WEIGHTS=0 + reload first.
            log("CheckpointSave: weights are stored bf16 "
                "(DTPU_BF16_WEIGHTS); the exported file will be bf16 — "
                "set DTPU_BF16_WEIGHTS=0 and reload for a full-precision "
                "export")
        # model/clip/vae may be three different pipelines (VAELoader,
        # clip-skip, LoRA splits): take each tower from its own source
        save_checkpoint(path, model.unet_params, clip.clip_params,
                        vae.vae_params, model.family)
        debug_log(f"CheckpointSave: wrote {path}")
        return ()


@register_op
class ModelSave(Op):
    """Export the diffusion model alone as a single-file safetensors
    with ``model.diffusion_model.`` keys (loads back via UNETLoader and
    in the reference ecosystem)."""
    TYPE = "ModelSave"
    OUTPUT_NODE = True
    WIDGETS = ["filename_prefix"]
    DEFAULTS = {"filename_prefix": "diffusion_models/save"}

    def execute(self, ctx: OpContext, model,
                filename_prefix: str = "diffusion_models/save"):
        import jax
        from comfyui_distributed_tpu.models.checkpoints import (
            UNET_PREFIX, _ExportMapper, _run_unet, save_state_dict)
        if any(getattr(a, "dtype", None) == jnp.bfloat16
               for a in jax.tree_util.tree_leaves(model.unet_params)):
            log("ModelSave: weights are stored bf16 (DTPU_BF16_WEIGHTS);"
                " the exported file will be bf16 — set "
                "DTPU_BF16_WEIGHTS=0 and reload for a full-precision "
                "export")
        path = _safe_output_path(ctx.output_dir or os.getcwd(),
                                 f"{filename_prefix}.safetensors")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        sd = _run_unet(_ExportMapper(model.unet_params, UNET_PREFIX),
                       model.family.unet)
        save_state_dict(sd, path)
        debug_log(f"ModelSave: wrote {path}")
        return ()


def _resize_maybe_center(arr: np.ndarray, width: int, height: int,
                         method: str, crop: str) -> np.ndarray:
    """Resize [B,H,W,C] to (width, height); crop=\"center\" scales
    aspect-preserving then center-crops (ComfyUI common_upscale) — the ONE
    copy of the crop math for image-space AND latent-space resizes."""
    if crop == "center":
        b, h, w, c = arr.shape
        ratio = max(width / w, height / h)
        iw, ih = round(w * ratio), round(h * ratio)
        arr = resize_image(arr, iw, ih, method)
        x0 = (iw - width) // 2
        y0 = (ih - height) // 2
        return arr[:, y0:y0 + height, x0:x0 + width, :]
    return resize_image(arr, width, height, method)


def _image_meta(samples) -> dict:
    """Batch metadata an IMAGE can carry — the latent->image boundary
    filter.  Latent-only keys (noise_mask) stop here; ImageBatch accepts
    exactly these keys, so a future latent-only meta key added to
    _latent_meta can't crash a decode op."""
    return {k: samples[k] for k in ("local_batch", "fanout")
            if k in samples}


def _latent_meta(samples) -> dict:
    """Fan-out metadata to carry through latent-space ops — one copy, so a
    future meta key can't be forwarded by one op and dropped by another
    (which would make a downstream VAEEncode re-tile a fanned batch)."""
    return {k: samples[k] for k in ("local_batch", "fanout",
                                    "noise_mask", "seed_fixed_batch")
            if k in samples}


@register_op
class LatentUpscale(Op):
    """ComfyUI's latent-space resize (hires-fix stage 1 -> 2).  Pixel
    widget values divide by 8; width/height of 0 derive from the other
    dimension preserving aspect (0/0 = passthrough); crop="center"
    resizes aspect-preserving then center-crops."""
    TYPE = "LatentUpscale"
    WIDGETS = ["upscale_method", "width", "height", "crop"]
    DEFAULTS = {"crop": "disabled", "upscale_method": "nearest-exact"}

    def execute(self, ctx: OpContext, samples, upscale_method: str,
                width: int, height: int, crop: str = "disabled"):
        lat = np.asarray(samples["samples"], np.float32)
        b, h, w, _ = lat.shape
        width, height = int(width), int(height)
        if width == 0 and height == 0:
            return ({"samples": lat, **_latent_meta(samples)},)
        ds = 8  # ComfyUI divides the PIXEL widget values by 8
        if width == 0:
            lh = max(height // ds, 1)
            lw = max(round(w * lh / h), 1)
        elif height == 0:
            lw = max(width // ds, 1)
            lh = max(round(h * lw / w), 1)
        else:
            lw, lh = max(width // ds, 1), max(height // ds, 1)
        out = _resize_maybe_center(
            lat, lw, lh, upscale_method,
            crop if (width and height) else "disabled")
        return ({"samples": out, **_latent_meta(samples)},)


@register_op
class LatentUpscaleBy(Op):
    TYPE = "LatentUpscaleBy"
    WIDGETS = ["upscale_method", "scale_by"]
    DEFAULTS = {"upscale_method": "nearest-exact", "scale_by": 1.5}

    def execute(self, ctx: OpContext, samples, upscale_method: str,
                scale_by: float = 1.5):
        lat = np.asarray(samples["samples"], np.float32)
        lh = max(round(lat.shape[1] * float(scale_by)), 1)
        lw = max(round(lat.shape[2] * float(scale_by)), 1)
        out = resize_image(lat, lw, lh, upscale_method)
        return ({"samples": out, **_latent_meta(samples)},)


@register_op
class ImageScaleBy(Op):
    TYPE = "ImageScaleBy"
    WIDGETS = ["upscale_method", "scale_by"]
    DEFAULTS = {"upscale_method": "lanczos", "scale_by": 2.0}

    def execute(self, ctx: OpContext, image, upscale_method: str,
                scale_by: float = 2.0):
        arr = as_image_array(image)
        w = max(round(arr.shape[2] * float(scale_by)), 1)
        h = max(round(arr.shape[1] * float(scale_by)), 1)
        return (_keep_fanout_meta(image,
                                  resize_image(arr, w, h, upscale_method)),)


@register_op
class LoadImage(Op):
    TYPE = "LoadImage"
    WIDGETS = ["image", CONTROL]  # second widget is the upload button slot

    def execute(self, ctx: OpContext, image: str):
        from PIL import Image
        path = image
        if ctx.input_dir and not os.path.isabs(path):
            path = os.path.join(ctx.input_dir, image)
        if os.path.exists(path):
            arr = pil_to_tensor(Image.open(path))
        else:
            # zero-egress fallback: deterministic gradient test card
            debug_log(f"LoadImage: {image!r} not found, synthesizing 512x512")
            h = w = 512
            yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
            arr = np.stack([xx / w, yy / h, (xx + yy) / (h + w)],
                           axis=-1)[None]
        mask = 1.0 - arr[..., 3] if arr.shape[-1] == 4 else \
            np.zeros(arr.shape[:3], np.float32)
        return (arr[..., :3], mask)


@register_op
class ImageScale(Op):
    TYPE = "ImageScale"
    WIDGETS = ["upscale_method", "width", "height", "crop"]
    DEFAULTS = {"crop": "disabled"}

    def execute(self, ctx: OpContext, image, upscale_method: str,
                width: int, height: int, crop: str = "disabled"):
        arr = _resize_maybe_center(as_image_array(image), int(width),
                                   int(height), upscale_method, crop)
        return (_keep_fanout_meta(image, arr),)


@register_op
class UpscaleModelLoader(Op):
    TYPE = "UpscaleModelLoader"
    WIDGETS = ["model_name"]

    def execute(self, ctx: OpContext, model_name: str):
        return (registry.load_upscaler(model_name, models_dir=ctx.models_dir),)


@register_op
class ImageUpscaleWithModel(Op):
    TYPE = "ImageUpscaleWithModel"

    # beyond this many input pixels the SR net runs tiled: a whole-image
    # 4K+ pass would hold conv activations for the full canvas at once
    TILE_THRESHOLD = 1024 * 1024
    TILE = 512
    OVERLAP = 32

    def execute(self, ctx: OpContext, upscale_model, image):
        net, params, scale = upscale_model
        arr = as_image_array(image)
        b, h, w, _ = arr.shape
        with Timer(f"sr_upscale[x{scale}]"):
            if h * w <= self.TILE_THRESHOLD:
                out = np.asarray(net.apply({"params": params},
                                           jnp.asarray(arr)))
            else:
                out = self._tiled(net, params, arr, int(scale))
        return (_keep_fanout_meta(image, out),)

    def _tiled(self, net, params, arr: np.ndarray,
               scale: int) -> np.ndarray:
        """The shared uniform-tile feather loop (ops/tiling.tiled_apply);
        the jitted SR forward is cached at module level so repeated large
        upscales (video frames, batch queues) never retrace."""
        from comfyui_distributed_tpu.ops.tiling import tiled_apply
        key = repr(net)  # flax module dataclass repr == architecture
        fn = _sr_jit_cache.get(key)
        if fn is None:
            import jax as _jax
            fn = _sr_jit_cache[key] = _jax.jit(
                lambda p, z: net.apply({"params": p}, z))
        return tiled_apply(
            lambda tile: fn(params, jnp.asarray(tile)),
            arr, self.TILE, self.OVERLAP, scale,
            out_channels=arr.shape[-1])


# jitted SR forwards keyed by net architecture (module repr): get_op()
# returns a fresh op instance per call, so the cache must outlive them
_sr_jit_cache: dict = {}


@register_op
class PreviewImage(Op):
    TYPE = "PreviewImage"
    OUTPUT_NODE = True

    def execute(self, ctx: OpContext, images):
        ready = ctx.device_ready

        def host_side():
            arr = fetch_image_array(images, ready)
            return list(arr)

        # overlapped pipeline: the d2h fetch rides the host-IO pool (it
        # also absorbs the wait for the still-running device program —
        # nothing synchronizes the executor thread)
        ctx.collect_images(host_side)
        return ()


# the save counter scan+write must be atomic across pool threads: two
# overlapped jobs saving under one prefix would otherwise read the same
# counter and overwrite each other
_save_counter_lock = threading.Lock()


@register_op
class SaveImage(Op):
    TYPE = "SaveImage"
    WIDGETS = ["filename_prefix"]
    DEFAULTS = {"filename_prefix": "DistributedTPU"}
    OUTPUT_NODE = True

    def execute(self, ctx: OpContext, images,
                filename_prefix: str = "DistributedTPU"):
        # snapshot the metadata NOW: ctx.prompt_json/extra_pnginfo are
        # reassigned per run, and the deferred closure may execute while
        # the next job is already being set up.  Coalesced runs get one
        # metadata per MERGED PROMPT (each with its own seed values) so
        # a saved PNG dragged back into a UI reproduces ITS image.
        output_dir, ready = ctx.output_dir, ctx.device_ready
        metas = _png_metadata_per_prompt(ctx)

        def host_side():
            arr = fetch_image_array(images, ready)
            if output_dir:
                probe = _safe_output_path(output_dir,
                                          f"{filename_prefix}_00000.png")
                d, fname = os.path.split(probe)
                base = fname[:-len("_00000.png")]
                os.makedirs(d, exist_ok=True)
                # prompt-major batch: image i belongs to prompt i // per
                per = arr.shape[0] // len(metas) \
                    if arr.shape[0] % len(metas) == 0 else arr.shape[0]
                with trace_mod.stage("encode"), _save_counter_lock:
                    # counters continue across runs — a second queue of
                    # the same workflow must never overwrite earlier
                    # outputs (ComfyUI's incrementing-counter semantics)
                    start = _next_image_counter(d, base)
                    for i in range(arr.shape[0]):
                        meta = metas[min(i // max(per, 1),
                                         len(metas) - 1)]
                        tensor_to_pil(arr, i).save(
                            os.path.join(d, f"{base}_{start + i:05d}.png"),
                            pnginfo=meta)
                trace_mod.mark_instant("encoded")
            return list(arr)

        ctx.collect_images(host_side)
        return ()


def _png_metadata(ctx: OpContext, prompt_json=None):
    """PIL ``PngInfo`` carrying the executing prompt + extra_pnginfo as
    tEXt chunks (ComfyUI's save contract: ``prompt`` = API-format graph,
    plus one chunk per extra_pnginfo key — typically ``workflow``, the
    UI-format doc the reference ships with every dispatch,
    ``gpupanel.js:1344-1358``).  None when there is nothing to embed.
    ``prompt_json`` overrides ``ctx.prompt_json`` (the coalesced
    per-prompt rewrite)."""
    meta = None
    if prompt_json is None:
        prompt_json = getattr(ctx, "prompt_json", None)
    if prompt_json is not None:
        from PIL.PngImagePlugin import PngInfo
        meta = PngInfo()
        meta.add_text("prompt", json.dumps(prompt_json))
    extra = getattr(ctx, "extra_pnginfo", None)
    if extra:
        if meta is None:
            from PIL.PngImagePlugin import PngInfo
            meta = PngInfo()
        for k, v in dict(extra).items():
            meta.add_text(str(k), json.dumps(v))
    return meta


def _png_metadata_per_prompt(ctx: OpContext) -> list:
    """One PngInfo per prompt merged into this run (length 1 when not
    coalesced).  The merged graph is prompt 0's; each other prompt's
    metadata re-applies its own masked widget values from the
    scheduler's ``coalesced_<widget>s`` hidden overrides, so the
    ``prompt`` chunk a user reloads carries THEIR seed."""
    k = max(int(getattr(ctx, "coalesce", 1)), 1)
    overrides = getattr(ctx, "hidden_overrides", None) or {}
    base_json = getattr(ctx, "prompt_json", None)
    if k <= 1 or not overrides or base_json is None:
        return [_png_metadata(ctx)] * k
    import copy as _copy
    metas = []
    for j in range(k):
        pj = _copy.deepcopy(base_json)
        for nid, ov in overrides.items():
            node = pj.get(nid)
            if not isinstance(node, dict):
                continue
            for key, vals in ov.items():
                if key.startswith("coalesced_") and key.endswith("s") \
                        and isinstance(vals, (list, tuple)) \
                        and j < len(vals):
                    widget = key[len("coalesced_"):-1]
                    node.setdefault("inputs", {})[widget] = vals[j]
        metas.append(_png_metadata(ctx, prompt_json=pj))
    return metas


def _next_image_counter(dirpath: str, base: str,
                        ext: str = "png") -> int:
    """First unused counter for ``base_#####.<ext>`` files in
    ``dirpath``."""
    import re
    pat = re.compile(re.escape(base)
                     + r"_(\d+)\." + re.escape(ext) + r"$")  # \d+: the save
    # format widens past 99999, and a 5-digit match would overwrite there
    mx = -1
    try:
        for f in os.listdir(dirpath):
            m = pat.match(f)
            if m:
                mx = max(mx, int(m.group(1)))
    except OSError:
        pass
    return mx + 1
