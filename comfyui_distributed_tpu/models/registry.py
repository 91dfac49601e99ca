"""Model families, virtual checkpoints, and the DiffusionPipeline bundle.

The reference's CheckpointLoaderSimple hands back ComfyUI (MODEL, CLIP, VAE)
objects; here the equivalent bundle is a :class:`DiffusionPipeline`.  When the
named checkpoint file exists it is loaded (safetensors, torch key mapping —
``checkpoints.py``); when it does not (zero-egress dev boxes, CI), parameters
are **virtually initialized**: deterministic random init seeded from the
checkpoint name, so every mesh host materializes identical weights without
any file — the reference's "same models on all machines" requirement
(``README.md:189-193``) satisfied by construction.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import os
import threading
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from comfyui_distributed_tpu.models import clip as clip_mod
from comfyui_distributed_tpu.models import samplers as smp
from comfyui_distributed_tpu.models import schedules as sch
from comfyui_distributed_tpu.models import unet as unet_mod
from comfyui_distributed_tpu.models import vae as vae_mod
from comfyui_distributed_tpu.models.denoiser import make_denoiser
from comfyui_distributed_tpu.models.tokenizer import make_tokenizer
from comfyui_distributed_tpu.parallel import sharding as shd
from comfyui_distributed_tpu.models.upscalers import (
    ESRGAN_4X_CONFIG,
    TINY_RRDB_CONFIG,
    RRDBNet,
)
from comfyui_distributed_tpu.utils import trace as trace_mod
from comfyui_distributed_tpu.utils.logging import log


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    name: str
    unet: unet_mod.UNetConfig
    vae: vae_mod.VAEConfig
    clips: Tuple[clip_mod.CLIPConfig, ...]
    latent_channels: int = 4
    # how the UNet's ADM vector is built: "sdxl" (pooled text + size
    # embeds) or "unclip" (noise-augmented CLIP-vision embed + noise
    # level embedding — ops/basic.py _sdxl_vector_cond)
    adm_kind: str = "sdxl"
    # in-checkpoint key prefixes for the text tower(s), when the family
    # deviates from the standard cond_stage_model/conditioner layouts
    # (checkpoints._clip_prefixes falls back to those when None)
    clip_prefixes: Optional[Tuple[str, ...]] = None


FAMILIES: Dict[str, ModelFamily] = {
    "sd15": ModelFamily(
        name="sd15",
        unet=unet_mod.SD15_CONFIG,
        vae=vae_mod.SD_VAE_CONFIG,
        clips=(clip_mod.CLIP_L_CONFIG,),
    ),
    "sdxl": ModelFamily(
        name="sdxl",
        unet=unet_mod.SDXL_CONFIG,
        vae=vae_mod.SDXL_VAE_CONFIG,
        clips=(clip_mod.CLIP_L_SDXL_CONFIG, clip_mod.OPEN_CLIP_BIGG_CONFIG),
    ),
    # SDXL refiner: bigG tower only (embedder 0 in the refiner file),
    # 2560-channel ADM with the 5-scalar (h, w, crop_h, crop_w,
    # aesthetic_score) embedding layout CLIPTextEncodeSDXLRefiner emits
    "sdxl_refiner": ModelFamily(
        name="sdxl_refiner",
        unet=unet_mod.SDXL_REFINER_CONFIG,
        vae=vae_mod.SDXL_VAE_CONFIG,
        clips=(clip_mod.OPEN_CLIP_BIGG_CONFIG,),
        # the refiner stores its (only) bigG tower as embedder 0 of the
        # SGM conditioner, not under cond_stage_model
        clip_prefixes=("conditioner.embedders.0.model.",),
    ),
    "sd21": ModelFamily(
        name="sd21",
        unet=unet_mod.SD21_CONFIG,          # v-prediction (768-v line)
        vae=vae_mod.SD_VAE_CONFIG,
        clips=(clip_mod.OPEN_CLIP_H_CONFIG,),
    ),
    "sd21_base": ModelFamily(
        name="sd21_base",
        unet=unet_mod.SD21_BASE_CONFIG,     # eps (512-base line)
        vae=vae_mod.SD_VAE_CONFIG,
        clips=(clip_mod.OPEN_CLIP_H_CONFIG,),
    ),
    # inpaint model lines: the UNet consumes [latent(4), mask(1),
    # masked-image latent(4)] = 9 input channels (RunwayML
    # sd-v1.5-inpainting layout); everything else matches the base family
    "sd15_inpaint": ModelFamily(
        name="sd15_inpaint",
        unet=dataclasses.replace(unet_mod.SD15_CONFIG, in_channels=9),
        vae=vae_mod.SD_VAE_CONFIG,
        clips=(clip_mod.CLIP_L_CONFIG,),
    ),
    # InstructPix2Pix: [latent(4), source-image latent(4)] = 8 input
    # channels, no mask (timbrooks/instruct-pix2pix layout)
    "sd15_ip2p": ModelFamily(
        name="sd15_ip2p",
        unet=dataclasses.replace(unet_mod.SD15_CONFIG, in_channels=8),
        vae=vae_mod.SD_VAE_CONFIG,
        clips=(clip_mod.CLIP_L_CONFIG,),
    ),
    "sd21_inpaint": ModelFamily(       # 512-inpainting-ema (eps line)
        name="sd21_inpaint",
        unet=dataclasses.replace(unet_mod.SD21_BASE_CONFIG,
                                 in_channels=9),
        vae=vae_mod.SD_VAE_CONFIG,
        clips=(clip_mod.OPEN_CLIP_H_CONFIG,),
    ),
    "sdxl_inpaint": ModelFamily(
        name="sdxl_inpaint",
        unet=dataclasses.replace(unet_mod.SDXL_CONFIG, in_channels=9),
        vae=vae_mod.SDXL_VAE_CONFIG,
        clips=(clip_mod.CLIP_L_SDXL_CONFIG,
               clip_mod.OPEN_CLIP_BIGG_CONFIG),
    ),
    # SD2.1-unclip (stable-diffusion-2-1-unclip, "h" line): the SD21
    # v-pred UNet grown an ADM head consuming the noise-augmented ViT-H
    # image embedding (1024) + the noise-level timestep embedding (1024)
    "sd21_unclip": ModelFamily(
        name="sd21_unclip",
        unet=dataclasses.replace(unet_mod.SD21_CONFIG,
                                 adm_in_channels=2048),
        vae=vae_mod.SD_VAE_CONFIG,
        clips=(clip_mod.OPEN_CLIP_H_CONFIG,),
        adm_kind="unclip",
    ),
    "tiny": ModelFamily(
        name="tiny",
        unet=unet_mod.TINY_CONFIG,
        vae=vae_mod.TINY_VAE_CONFIG,
        clips=(clip_mod.TINY_CLIP_CONFIG,),
    ),
    "tiny_unclip": ModelFamily(
        name="tiny_unclip",
        unet=dataclasses.replace(unet_mod.TINY_CONFIG,
                                 adm_in_channels=64),
        vae=vae_mod.TINY_VAE_CONFIG,
        clips=(clip_mod.TINY_CLIP_CONFIG,),
        adm_kind="unclip",
    ),
    # SDXL-shaped tiny family: an ADM head wide enough (128 > the tiny
    # pooled width 64) that CLIPTextEncodeSDXL's size embeddings
    # actually reach the UNet — the sdxl fixture's CPU test target
    "tiny_sdxl": ModelFamily(
        name="tiny_sdxl",
        unet=dataclasses.replace(unet_mod.TINY_CONFIG,
                                 adm_in_channels=128),
        vae=vae_mod.TINY_VAE_CONFIG,
        clips=(clip_mod.TINY_CLIP_CONFIG,),
    ),
    "tiny_inpaint": ModelFamily(
        name="tiny_inpaint",
        unet=dataclasses.replace(unet_mod.TINY_CONFIG, in_channels=9),
        vae=vae_mod.TINY_VAE_CONFIG,
        clips=(clip_mod.TINY_CLIP_CONFIG,),
    ),
    "tiny_ip2p": ModelFamily(
        name="tiny_ip2p",
        unet=dataclasses.replace(unet_mod.TINY_CONFIG, in_channels=8),
        vae=vae_mod.TINY_VAE_CONFIG,
        clips=(clip_mod.TINY_CLIP_CONFIG,),
    ),
}

FAMILY_ENV = "DTPU_DEFAULT_FAMILY"


def _window_key(w):
    """Hashable form of a ControlNet sigma-window spec: None, one
    (start, end) pair, or the ops-layer nested per-block structure."""
    if w is None:
        return None
    if isinstance(w, (tuple, list)) and w \
            and isinstance(w[0], (tuple, list, type(None))):
        return tuple(_window_key(x) for x in w)
    return (float(w[0]), float(w[1]))


def _strength_key(strength):
    """ControlNet strength as a hashable static value: a scalar, a flat
    per-block tuple, or ops/basic.py's ``(pos_strengths, neg_strengths)``
    nested pair (see models/denoiser.py for the block semantics)."""
    if isinstance(strength, (tuple, list)):
        return tuple(tuple(float(v) for v in s)
                     if isinstance(s, (tuple, list)) else float(s)
                     for s in strength)
    return float(strength)


def detect_family(ckpt_name: str) -> str:
    """Family from checkpoint-name heuristics; ``DTPU_DEFAULT_FAMILY``
    overrides (tests/CI force 'tiny')."""
    env = os.environ.get(FAMILY_ENV)
    if env:
        return env
    lowered = ckpt_name.lower()
    inpaint = "inpaint" in lowered
    if "tiny" in lowered or "test" in lowered:
        if "unclip" in lowered:
            return "tiny_unclip"
        if "ip2p" in lowered or "pix2pix" in lowered:
            return "tiny_ip2p"
        return "tiny_inpaint" if inpaint else "tiny"
    # timbrooks/instruct-pix2pix style finetunes (8-channel UNet)
    if "ip2p" in lowered or "pix2pix" in lowered:
        return "sd15_ip2p"
    if "unclip" in lowered:
        return "sd21_unclip"
    if "xl" in lowered:
        if "refiner" in lowered:
            return "sdxl_refiner"
        return "sdxl_inpaint" if inpaint else "sdxl"
    # Stability SD2 naming only — a bare "v2" would misroute SD1.5
    # community finetunes like anything-v2 / counterfeit-v2.5
    # (512-inpainting-ema is the SD2 line's inpaint checkpoint)
    if ("sd2" in lowered or "v2-0" in lowered or "v2-1" in lowered
            or "768-v" in lowered or "512-base" in lowered
            or "512-inpainting" in lowered):
        if inpaint:
            return "sd21_inpaint"
        # v2-1_768-ema-pruned is the v-pred line; v2-1_512-ema-pruned /
        # 512-base-ema the eps line
        return "sd21" if ("768" in lowered or "v-pred" in lowered
                          or "vpred" in lowered) else "sd21_base"
    # sd-v1-5-inpainting / *-inpainting finetunes (9-channel UNet)
    return "sd15_inpaint" if inpaint else "sd15"


def _name_seed(name: str) -> int:
    return int.from_bytes(hashlib.sha256(name.encode()).digest()[:4], "little")


class DiffusionPipeline:
    """(MODEL, CLIP, VAE) bundle + tokenizer + schedule + jit caches."""

    def __init__(self, name: str, family: ModelFamily,
                 unet_params: Any, clip_params: List[Any], vae_params: Any,
                 prediction_type: str = "eps",
                 assets_dir: Optional[str] = None):
        self.name = name
        self.family = family
        self.unet = unet_mod.UNet(family.unet)
        self.clip_models = [clip_mod.CLIPTextModel(c) for c in family.clips]
        self.vae = vae_mod.VAE(family.vae)
        self.unet_params = unet_params
        self.clip_params = clip_params
        self.vae_params = vae_params
        self.prediction_type = prediction_type
        self.assets_dir = assets_dir
        # unique identity for derived-pipeline caches: ``name`` alone is
        # just the ckpt filename, which two pipelines of different
        # families/models_dirs can share (load_pipeline overwrites this
        # with its full cache key)
        self.cache_token = f"{name}:{family.name}:{assets_dir or ''}"
        self.schedule = sch.make_discrete_schedule()
        # real CLIP BPE when vocab.json/merges.txt sit in the models dir
        # (zero-egress asset drop); deterministic hash tokenizer otherwise
        # pad convention follows the text tower: CLIP (SD1.x/SDXL) pads
        # with EOT, OpenCLIP (SD2.x) pads with 0 — ComfyUI's sd2 tokenizer
        self.tokenizer = make_tokenizer(
            assets_dir=assets_dir,
            vocab_size=min(c.vocab_size for c in family.clips),
            pad_with_end=not all(c.layout == "openclip"
                                 for c in family.clips))
        # LRU-bounded: every (resolution, batch, sampler...) combination is
        # its own compiled executable; an unbounded dict leaks one per shape
        # seen.  16 live entries cover a realistic session (clip×2, vae×2,
        # and a dozen sample configs; beside them the sampler inputs'
        # program and the placeholders, one a batch size, and an SDXL size
        # embedding a resolution); evictions are logged.
        self._jit_cache: "collections.OrderedDict[Any, Any]" = \
            collections.OrderedDict()
        self._jit_cache_cap = int(os.environ.get("DTPU_JIT_CACHE_CAP", "16"))
        self._lock = threading.Lock()
        self._tp_mesh = None   # mesh the params are currently tp-laid-out for

    # --- tensor parallelism -------------------------------------------------

    def _ensure_tp_sharded(self, batch: Any = None) -> None:
        """Lay the UNet/CLIP/VAE params out over the serving mesh, once per
        mesh (``parallel/sharding.params_shardings``): megatron-style
        column splits over a ``tensor`` axis (GSPMD inserts the matching
        collectives inside the jitted sample core), full replicas over
        ``data``.  Without it the weights sit uncommitted on device 0 and
        every sharded call copies them to the other chips again.

        The mesh is the one ``batch`` (latents, images) is sharded over
        when it is, else the live runtime's — in a server they are the
        same mesh.  No-op on a one-device mesh and when already laid out
        for this mesh, so the single-chip serving path pays nothing.  This
        is the serving-side counterpart of
        ``parallel/train.shard_train_step``.
        Floor override for tiny test models: ``DTPU_TP_MIN_SHARD_ELEMENTS``."""
        from comfyui_distributed_tpu.parallel.mesh import get_live_runtime
        mesh = shd.mesh_of(batch)
        if mesh is None:
            rt = get_live_runtime()
            mesh = rt.mesh if rt is not None else None
        if mesh is None or mesh.size <= 1 or self._tp_mesh is mesh:
            return
        min_el = int(os.environ.get("DTPU_TP_MIN_SHARD_ELEMENTS",
                                    shd.MIN_SHARD_ELEMENTS))
        with self._lock:
            if self._tp_mesh is mesh:
                return

            def lay_out(tree):
                if not tree:
                    return tree
                sh = shd.params_shardings(tree, mesh,
                                          min_elements=min_el)
                return shd.apply_shardings(tree, sh)

            with trace_mod.stage("load_weights"):
                self.unet_params = lay_out(self.unet_params)
                self.clip_params = [lay_out(p) for p in self.clip_params]
                self.vae_params = lay_out(self.vae_params)
                jax.block_until_ready((self.unet_params, self.clip_params,
                                       self.vae_params))
            self._tp_mesh = mesh
            # Cached cores were TRACED while no mesh was live, so every
            # activation constraint (shd.constrain*) resolved to a no-op
            # inside the cached jaxpr — jit re-lowers for the new param
            # shardings but never re-traces, which would serve the
            # tp-concat-cpu-miscompile graph.  A layout transition is a
            # serve-boot one-off; drop the cache so post-layout traces
            # re-resolve the gates against the live mesh.
            self._jit_cache.clear()
            log(f"{self.name}: UNet/CLIP/VAE params laid out over mesh "
                f"{dict(mesh.shape)} for serving")

    # --- text ---------------------------------------------------------------

    def encode_prompt(self, texts: List[str],
                      texts_alt: Optional[List[str]] = None,
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (context [B, 77, sum(widths)], pooled [B, pooled_dim]).
        Multi-encoder families (SDXL) concatenate hidden widths; pooled comes
        from the last encoder.  Token weights scale the hidden states around
        the per-sequence mean (comfy-style emphasis).

        ``texts_alt``: optional prompts for towers AFTER the first —
        ComfyUI's CLIPTextEncodeSDXL text_g/text_l split (text_l feeds
        CLIP-L, text_g the OpenCLIP tower whose pooled output becomes
        the ADM vector).  Single-tower families ignore it.

        ``embedding:name`` references (textual inversion) splice learned
        vectors from ``<models_dir>/embeddings/`` into the token stream,
        per tower (SDXL files carry clip_l/clip_g keys)."""
        from comfyui_distributed_tpu.models.tokenizer import (
            encode_with_embeddings, has_embedding_refs)

        self._ensure_tp_sharded()
        outs, pooled = [], None
        for i, (m, p) in enumerate(zip(self.clip_models,
                                       self.clip_params)):
            ts = texts if i == 0 or texts_alt is None else texts_alt
            width = int(m.cfg.width)
            if any(has_embedding_refs(t) for t in ts):
                def _look(nm, _i=i, _w=width):
                    return load_textual_embedding(
                        nm, self.assets_dir, _w, tower_idx=_i)

                quads = [encode_with_embeddings(self.tokenizer, t,
                                                _look, width) for t in ts]
                ia = jnp.asarray(np.stack([q[0] for q in quads]))
                wa = jnp.asarray(np.stack([q[1] for q in quads]))
                ov = jnp.asarray(np.stack([q[2] for q in quads]))
                mk = jnp.asarray(np.stack([q[3] for q in quads]))
                fn = self._jitted(("clip_ov", id(m)), partial(m.apply))
                hidden, pool = fn({"params": p}, ia, ov, mk)
            else:
                pairs = [self.tokenizer.encode(t) for t in ts]
                ia = jnp.asarray(np.stack([x for x, _ in pairs]))
                wa = jnp.asarray(np.stack([w for _, w in pairs]))
                fn = self._jitted(("clip", id(m)), partial(m.apply))
                hidden, pool = fn({"params": p}, ia)
            mean = hidden.mean(axis=1, keepdims=True)
            hidden = mean + (hidden - mean) * wa[..., None]
            outs.append(hidden)
            pooled = pool
        return jnp.concatenate(outs, axis=-1), pooled

    # --- latents ------------------------------------------------------------

    def vae_encode(self, images: jnp.ndarray) -> jnp.ndarray:
        self._ensure_tp_sharded(images)
        fn = self._jitted("vae_enc", lambda p, x: self.vae.apply(
            {"params": p}, x, method=self.vae.encode))
        return fn(self.vae_params, images)

    def vae_encode_tiled(self, images: jnp.ndarray, tile_size: int = 512,
                         overlap: int = 64,
                         check_interrupt=None) -> jnp.ndarray:
        """Encode in overlapping pixel tiles, feather-blending at latent
        resolution (ComfyUI's VAEEncodeTiled): bounds encoder activation
        memory for 4K+ sources.  Like the tiled decode, per-tile
        GroupNorm statistics make it close to — not bit-identical with —
        the one-shot encode."""
        ds = self.family.vae.downscale
        B, H, W, _ = images.shape
        lt = max(tile_size // ds, 2 * max(overlap // ds, 1))
        lo = max(overlap // ds, 1)
        if H // ds <= lt and W // ds <= lt:
            return self.vae_encode(images)
        from comfyui_distributed_tpu.ops.tiling import tiled_apply_down
        return jnp.asarray(tiled_apply_down(
            self.vae_encode, np.asarray(images, np.float32), lt, lo, ds,
            out_channels=self.family.latent_channels,
            check_interrupt=check_interrupt))

    def vae_decode(self, latents: jnp.ndarray) -> jnp.ndarray:
        self._ensure_tp_sharded(latents)
        fn = self._jitted("vae_dec", lambda p, z: self.vae.apply(
            {"params": p}, z, method=self.vae.decode))
        return fn(self.vae_params, latents)

    def vae_decode_tiled(self, latents: jnp.ndarray, tile_size: int = 512,
                         overlap: int = 64,
                         check_interrupt=None) -> jnp.ndarray:
        """Decode in overlapping latent tiles with feathered blending
        (ComfyUI's VAEDecodeTiled): bounds decoder activation memory at 4K+
        where a one-shot decode would OOM a chip.  Tiles are uniform
        (clamped start positions), so one executable serves every tile.

        Like the torch ecosystem's tiled VAE, per-tile GroupNorm statistics
        differ slightly from a full decode — the overlap feather hides the
        seams; it is not bit-identical to ``vae_decode``."""
        ds = self.family.vae.downscale
        B, H, W, _ = latents.shape
        lt = max(tile_size // ds, 2 * max(overlap // ds, 1))
        lo = max(overlap // ds, 1)
        if H <= lt and W <= lt:
            return self.vae_decode(latents)
        from comfyui_distributed_tpu.ops.tiling import tiled_apply
        return jnp.asarray(tiled_apply(
            self.vae_decode, np.asarray(latents, np.float32), lt, lo, ds,
            out_channels=3, check_interrupt=check_interrupt))

    # --- denoising ----------------------------------------------------------

    def raw_unet_apply(self, params, x, t, context, y=None, control=None,
                       context_v=None, objs=None):
        return self.unet.apply({"params": params}, x, t, context, y=y,
                               control=control, context_v=context_v,
                               objs=objs)

    def raw_unet_apply_capture(self, params, x, t, context, y=None,
                               control=None, context_v=None, objs=None):
        """Like raw_unet_apply but returns (prediction, attn_probs): the
        sag_capture family flag makes the mid-block attn1 sow its
        softmax weights (SAG's blur mask source)."""
        out, inters = self.unet.apply(
            {"params": params}, x, t, context, y=y, control=control,
            context_v=context_v, objs=objs, mutable=["intermediates"])
        leaves = jax.tree_util.tree_leaves(inters)
        if len(leaves) != 1:
            raise RuntimeError(
                f"SAG capture expected exactly one sown attn-probs "
                f"tensor, got {len(leaves)} (is sag_capture set on the "
                "family?)")
        return out, leaves[0]

    def denoiser(self):
        return make_denoiser(self.raw_unet_apply, self.unet_params,
                             self.schedule, self.prediction_type)

    def denoise_step_fn(self, sampler_name: str, cfg: float,
                        rows: int, latent_hw: tuple,
                        has_y: bool = False):
        """One jitted denoise STEP over a padded ``rows``-sample batch —
        the continuous-batching executor's per-bucket kernel
        (workflow/batch_executor.py).  Signature:

            step(unet_params, x, ctx, unc, y, keys, sigma, sigma_next,
                 step_i, active) -> x'

        where ``sigma``/``sigma_next``/``step_i`` are per-sample ``[rows]``
        vectors (each slot at its own schedule position), ``keys`` the
        per-sample PRNG keys, and ``active`` a ``[rows]`` bool mask —
        inactive (padding / retired-slot) rows pass through unchanged.

        The model construction mirrors :meth:`sample`'s ``make_core``
        for the plain single-entry CFG case EXACTLY (same
        ``make_denoiser`` + ``cfg_denoiser_multi`` wrapping, same y
        stacking), and the per-step math is the SAME extracted step
        callable the scan samplers run (samplers.SAMPLER_STEPS) — so a
        slot stepped here is bit-identical to its serial run.  Cached in
        the same LRU jit cache as the full-loop cores (one executable
        per (sampler, cfg, padded shape): zero steady-state retraces);
        ``x`` is donated, so the persistent batch updates in place."""
        self._ensure_tp_sharded()
        cfg_rescale = float(getattr(self, "cfg_rescale", 0.0) or 0.0)
        static_key = ("cb_step", sampler_name, float(cfg), cfg_rescale,
                      int(rows), tuple(latent_hw), bool(has_y),
                      self.prediction_type)

        def make_step():
            step_impl = smp.get_sampler_step(sampler_name)
            cfg_scale = float(cfg)
            reps = 1 + (1 if cfg_scale != 1.0 else 0)

            def step(unet_params, x, ctx, unc, y_in, keys, sigma,
                     sigma_next, step_i, active):
                # 2-D CB composition (ISSUE 16): pin the persistent batch
                # to its canonical rows-on-data layout on BOTH ends of the
                # step, so the donated output sharding always matches the
                # input and every steady-state call sees one layout —
                # anything else would re-lower per call and break the
                # zero-retrace invariant.  Inert without a tensor axis.
                x = shd.constrain_rows(x)
                den = make_denoiser(self.raw_unet_apply, unet_params,
                                    self.schedule, self.prediction_type)
                model = smp.cfg_denoiser_multi(
                    den, [(ctx, None, 1.0, None)],
                    [(unc, None, 1.0, None)], cfg_scale,
                    cfg_rescale=cfg_rescale)
                if not has_y:
                    extra = {}
                else:
                    y2 = shd.stack_rows([y_in] * reps) \
                        if reps > 1 else y_in
                    extra = {"y": y2}
                x_new = step_impl(model, x, sigma, sigma_next, step_i,
                                  keys, extra_args=extra)
                act = jnp.reshape(active, (-1,) + (1,) * (x.ndim - 1))
                return shd.constrain_rows(jnp.where(act, x_new, x))

            return jax.jit(step, donate_argnums=(1,))

        return self._cache_get_or_make(static_key, make_step)

    def sample(self, latents: jnp.ndarray, context: jnp.ndarray,
               uncond_context: jnp.ndarray, seeds,
               steps: int, cfg: float, sampler_name: str, scheduler: str,
               denoise: float = 1.0, y: Optional[jnp.ndarray] = None,
               add_noise: bool = True, sample_idx=None,
               start_step: int = 0, end_step: Optional[int] = None,
               force_full_denoise: bool = False,
               noise_mask: Optional[jnp.ndarray] = None,
               control=None,
               sigmas_override=None,
               middle_context=None,
               cfg2: float = 1.0,
               guidance: str = "dual",
               c_concat=None,
               gligen_objs=None,
               donate_latents: bool = False,
               keys=None) -> jnp.ndarray:
        """Full ksampler: schedule -> noise -> scan-sampler -> latents.

        ``seeds``: per-sample host seed array [B] (64-bit ok; replica offsets
        already applied by the distributed layer).  ``sample_idx``: optional
        per-sample fold-in indices (replica-local positions in SPMD runs).
        ``keys``: the per-sample PRNG keys of exactly these seeds and
        indices, when the caller made them in the one program that made
        the request's other inputs (``sampler_inputs``); made here, by
        the same program, when not given.
        ``start_step``/``end_step`` run a window of the schedule (ComfyUI's
        KSamplerAdvanced): noise scales by the window's FIRST sigma, and
        stopping early returns a still-noisy latent for a later stage
        unless ``force_full_denoise`` zeroes the final sigma.
        ``donate_latents``: the caller warrants no other reference to the
        ``latents`` buffer exists — the jitted denoise loop DONATES it to
        XLA (the scan carry aliases it), halving peak latent memory per
        replica; the input ``jax.Array`` is invalidated.  With it False
        a defensive on-device copy is donated instead (one extra latent
        buffer, identical numerics, upstream buffer untouched).
        ``noise_mask`` [B_or_1, h, w, 1] in latent resolution inpaints: 1 =
        resample, 0 = keep source.  ComfyUI's KSamplerX0Inpaint semantics —
        every model call sees the source re-noised to the current sigma
        outside the mask and its denoised output re-anchored to the clean
        source there.
        ``context`` / ``uncond_context`` are single cond arrays OR LISTS
        of ``(context, area_mask_or_None, strength)`` entries (ComfyUI
        multi-entry cond lists — regional prompting): all entries of
        both CFG sides evaluate in one stacked model call and blend by
        mask (samplers.cfg_denoiser_multi).  ``y`` may be a single
        per-sample ADM array (replicated over every block) or a list
        with one array per entry, conds first then unconds.
        The denoise loop is jit-compiled and cached per static config."""
        # lay the tower params out over the mesh the batch lives on
        # before they enter the jitted core
        self._ensure_tp_sharded(latents)

        def _norm(entries):
            if not isinstance(entries, (list, tuple)):
                return [(entries, None, 1.0, None)]
            return smp._norm_entries(entries)  # ONE copy of the contract

        conds = _norm(context)
        unconds = _norm(uncond_context)
        dual = middle_context is not None
        if dual:
            # DualCFGGuider path: plain [cond, middle, uncond] arrays only
            # (ComfyUI's dual guider likewise takes bare conds — regional
            # multi-entry lists don't compose with the 3-way combine)
            if len(conds) != 1 or len(unconds) != 1 or any(
                    m is not None or s != 1.0 or sr is not None
                    for _, m, s, sr in conds + unconds):
                raise ValueError(
                    f"3-row guidance ({guidance}) requires plain "
                    "single-entry positive/negative conditionings")
            conds = conds + [(jnp.asarray(middle_context), None, 1.0, None)]
        if sigmas_override is not None:
            # custom-sampling path (SamplerCustom): the caller supplies
            # the exact sigma sequence; scheduler/steps/denoise/window
            # args are ignored.  Only the LENGTH is static (scan trip
            # count) — the values ride in as a traced argument, so a
            # KarrasScheduler rho sweep reuses one executable per length
            sig_np = np.asarray(sigmas_override, np.float32)
            if sig_np.ndim != 1:
                raise ValueError("sigmas_override must be a 1-D sigma "
                                 "sequence (order is the sampler's "
                                 "business — FlipSigmas feeds ascending)")
            if sig_np.shape[0] < 2:
                # ComfyUI's denoise<=0 / empty-schedule no-op: the
                # latent passes through unchanged (same precedent as the
                # degenerate KSamplerAdvanced window below)
                return latents
            sigmas = sig_np
            steps = int(sig_np.shape[0]) - 1
            start, end = 0, steps
        else:
            # host values all the way to the call: the schedule and its
            # window enqueue no program of their own
            sigmas = np.asarray(sch.compute_sigmas(
                self.schedule, scheduler, steps, denoise), np.float32)
            start = max(int(start_step), 0)
            end = steps if end_step is None else min(int(end_step), steps)
            if start >= end:
                # degenerate window (start_at_step beyond the schedule):
                # ComfyUI returns the latent unchanged rather than erroring
                return latents
            if start > 0 or end < steps:
                sigmas = sigmas[start:end + 1].copy()
                if force_full_denoise:
                    sigmas[-1] = 0.0
        if keys is None:
            keys = sampler_inputs(self, latents.shape[0], seeds,
                                  sample_idx)[0]

        from comfyui_distributed_tpu.runtime.interrupt import polling_enabled

        def _entries_key(entries):
            return tuple((tuple(c.shape), m is not None,
                          tuple(m.shape) if m is not None else (),
                          float(s),
                          tuple(float(v) for v in sr) if sr is not None
                          else None) for c, m, s, sr in entries)

        # normalize control to a CHAIN of per-net wire specs (the ops
        # layer sends a tuple of (module, params, hint, strengths[,
        # windows]) — ComfyUI's previous_controlnet chain; a single
        # legacy spec becomes a 1-chain for direct callers)
        if control is not None and not isinstance(control[0], tuple):
            control = (control,)
        cfg_rescale = float(getattr(self, "cfg_rescale", 0.0) or 0.0)
        hn_spec = getattr(self, "hypernets", None) or None
        ds_spec = getattr(self, "deep_shrink_spec", None)
        if ds_spec is not None and control is not None:
            log("deep shrink: ControlNet residual shapes can't follow "
                "the shrunk encoder; sampling WITHOUT the downscale "
                "patch")
            ds_spec = None
        sag = getattr(self, "sag_params", None)
        sag_ok = False
        if sag is not None:
            ht = self.family.unet.hypertile
            mid_hypertiled = (ht is not None
                              and self.family.unet.num_levels - 1
                              <= int(ht[1]))
            sag_ok = (not dual and float(cfg) != 1.0
                      and len(conds) == 1 and len(unconds) == 1
                      and control is None and not mid_hypertiled
                      and not any(m is not None or s != 1.0
                                  or sr is not None
                                  for _, m, s, sr in conds + unconds))
            if not sag_ok:
                log("SAG: unsupported combination (regional/dual/"
                    "control/cfg==1/hypertiled mid-block); sampling "
                    "WITHOUT self-attention guidance")
        if ds_spec is not None and sag_ok:
            log("deep shrink: does not compose with SAG's capture "
                "branch; sampling WITHOUT the downscale patch")
            ds_spec = None
        if sag_ok:
            # mid-block spatial dims (stride-2 SAME convs: ceil halving
            # per level) — the attn-probs token grid the mask reshapes to
            mh, mw = int(latents.shape[1]), int(latents.shape[2])
            for _ in range(self.family.unet.num_levels - 1):
                mh, mw = (mh + 1) // 2, (mw + 1) // 2
        y_is_list = isinstance(y, (list, tuple))
        static_key = ("sample", sampler_name, scheduler, steps,
                      sigmas_override is not None,
                      cfg_rescale, float(cfg),
                      float(denoise), bool(add_noise), y is not None,
                      y_is_list, tuple(latents.shape), _entries_key(conds),
                      _entries_key(unconds),
                      polling_enabled(), start, end, dual, float(cfg2),
                      guidance,
                      (tuple(float(v) for v in sag), ) if sag_ok else (),
                      tuple(float(v) for v in ds_spec)
                      if ds_spec is not None else (),
                      tuple((float(s), tuple(sorted(h)))
                            for h, s in hn_spec)
                      if hn_spec is not None else (),
                      c_concat is not None,
                      tuple(c_concat.shape) if c_concat is not None
                      else (),
                      (tuple(gligen_objs[0].shape),
                       tuple(gligen_objs[2]))
                      if gligen_objs is not None else (),
                      bool(force_full_denoise), noise_mask is not None,
                      tuple((_strength_key(c[3]),
                             _window_key(c[4]) if len(c) > 4 else None)
                            for c in control)
                      if control is not None else None)

        def make_core():
            has_y = y is not None
            has_mask = noise_mask is not None
            has_control = control is not None
            cfg_scale = float(cfg)
            n_conds, n_unconds = len(conds), len(unconds)
            has_area = [m is not None for _, m, _, _ in conds + unconds]
            strengths = [float(s) for _, _, s, _ in conds + unconds]
            sranges = [sr for _, _, _, sr in conds + unconds]
            sampler = smp.get_sampler(sampler_name)
            if has_control:
                cn_modules = [c[0] for c in control]
                cn_strengths = [c[3] for c in control]
                cn_windows = [c[4] if len(c) > 4 else None
                              for c in control]

                def _make_apply(mod):
                    def cn_apply(p, xi, ts, ctx, hint, y_in):
                        return mod.apply({"params": p}, xi, ts, ctx,
                                         hint, y_in)
                    return cn_apply

                cn_applies = [_make_apply(m) for m in cn_modules]

            has_concat = c_concat is not None

            def core(unet_params, latents, ctx_list, area_list,
                     keys, sigmas, y_in, mask_in, cn_params, hint_in,
                     concat_in, objs_in):
                ctrl_spec = None
                if has_control:
                    ctrl_spec = []
                    for k in range(len(cn_applies)):
                        sk = _strength_key(cn_strengths[k])
                        cw = cn_windows[k]
                        if (isinstance(sk, tuple) and len(sk) == 2
                                and isinstance(sk[0], tuple)):
                            # ops-layer (pos_strengths, neg_strengths):
                            # flat per-block tuples sized to the actual
                            # layout — windows flatten IN LOCKSTEP with
                            # strengths so block i's gate stays block i's
                            pos_s, neg_s = sk
                            sk = tuple(pos_s) + (tuple(neg_s)
                                                 if cfg_scale != 1.0
                                                 else ())
                            if cw is not None:
                                pos_w, neg_w = cw
                                cw = tuple(pos_w) + (tuple(neg_w)
                                                     if cfg_scale != 1.0
                                                     else ())
                        spec = (cn_applies[k], cn_params[k], hint_in[k],
                                sk)
                        ctrl_spec.append(spec if cw is None
                                         else spec + (cw,))
                use_apply = self.raw_unet_apply
                if ds_spec is not None:
                    # deep shrink: a lax.cond over two config-variant
                    # UNet applies SHARING one param tree — the shrunk
                    # branch runs only inside the sigma window, so the
                    # early steps pay the small graph
                    lvl, fac, t_lo, t_hi = ds_spec
                    shrunk_mod = unet_mod.UNet(dataclasses.replace(
                        self.family.unet,
                        deep_shrink=(int(lvl), float(fac))))

                    def _shrunk(p, x, t, c, y=None, control=None,
                                context_v=None, objs=None):
                        return shrunk_mod.apply({"params": p}, x, t, c,
                                                y=y, control=control,
                                                context_v=context_v,
                                                objs=objs)

                    def use_apply(p, x, t, c, y=None, control=None,
                                  context_v=None, objs=None):
                        pred = jnp.logical_and(t[0] > t_lo, t[0] <= t_hi)
                        return jax.lax.cond(
                            pred,
                            lambda a: _shrunk(*a),
                            lambda a: self.raw_unet_apply(*a),
                            (p, x, t, c, y, control, context_v, objs))

                den = make_denoiser(
                    use_apply, unet_params, self.schedule,
                    self.prediction_type, control=ctrl_spec,
                    concat=concat_in if has_concat else None,
                    hypernet=hn_spec)
                entries = [(ctx_list[i],
                            area_list[i] if has_area[i] else None,
                            strengths[i], sranges[i])
                           for i in range(n_conds + n_unconds)]
                if dual:
                    # ctx_list rows: [cond, middle, uncond] (see sample())
                    combine = smp.cfg_denoiser_perp_neg \
                        if guidance == "perp_neg" else smp.cfg_denoiser_dual
                    model = combine(
                        den, ctx_list[0], ctx_list[1], ctx_list[2],
                        cfg_scale, float(cfg2), cfg_rescale=cfg_rescale)
                    reps = 3
                elif sag_ok:
                    den_cap = make_denoiser(
                        self.raw_unet_apply_capture, unet_params,
                        self.schedule, self.prediction_type,
                        capture=True,
                        concat=concat_in if has_concat else None,
                        hypernet=hn_spec)
                    model = smp.cfg_denoiser_sag(
                        den_cap, den, ctx_list[0], ctx_list[1],
                        cfg_scale, float(sag[0]), float(sag[1]),
                        (mh, mw), cfg_rescale=cfg_rescale)
                    reps = 2
                else:
                    model = smp.cfg_denoiser_multi(den, entries[:n_conds],
                                                   entries[n_conds:],
                                                   cfg_scale,
                                                   cfg_rescale=cfg_rescale)
                    reps = n_conds + (n_unconds if cfg_scale != 1.0
                                      else 0)
                if gligen_objs is not None:
                    # per-block grounding tokens: each block whose
                    # conditioning entry carries a gligen spec gets THAT
                    # spec's token set (the reference applies gligen
                    # per-cond); the rest get the null set.  Index order
                    # matches the ctx_list block layout (conds first,
                    # then unconds) — ops/basic.py.  og: [S, B, N, D]
                    # stacked per-spec sets; index -1 = null set
                    og, on = objs_in
                    idxs = tuple(gligen_objs[2])[:max(reps, 1)]
                    parts = [og[i] if i >= 0 else on for i in idxs]
                    parts += [on] * (max(reps, 1) - len(parts))
                    extra_objs = shd.stack_rows(parts) \
                        if reps > 1 else parts[0]
                else:
                    extra_objs = None
                if not has_y:
                    y2 = y_in
                elif y_is_list:
                    # one ADM vector per entry (regional SDXL: each
                    # region's own pooled), conds first then unconds
                    y2 = shd.stack_rows(list(y_in)[:reps]) \
                        if reps > 1 else y_in[0]
                else:
                    # a single ADM vector rides every block
                    y2 = shd.stack_rows([y_in] * reps) \
                        if reps > 1 else y_in
                # init noise uses a reserved fold-in index so it never
                # collides with per-step ancestral noise (steps from 0)
                noise = smp.make_noise_fn(keys)(
                    jnp.asarray(0x7FFFFFFF, jnp.uint32), latents.shape[1:])
                # noise always lands ON the latent (ComfyUI convention) —
                # txt2img passes zeros, so pure-noise starts fall out
                x = latents + noise * sigmas[0] if add_noise else latents
                extra = {"y": y2} if has_y else {}
                if extra_objs is not None:
                    extra["objs"] = extra_objs
                if has_mask:
                    # inpainting (KSamplerX0Inpaint): every model call sees
                    # the source re-noised to the CURRENT sigma outside the
                    # mask, and its denoised output re-anchored to the
                    # clean source there — so sampler math can't drift the
                    # protected region.  With add_noise disabled the blend
                    # noise is zero (ComfyUI's disable_noise: the input
                    # latent IS the noised state already)
                    inner = model
                    mnoise = noise if add_noise else jnp.zeros_like(noise)

                    def model(xi, sigma, **kw):  # noqa: F811
                        s = sigma.reshape((-1,) + (1,) * (xi.ndim - 1))
                        xi = xi * mask_in + (latents + mnoise * s) \
                            * (1.0 - mask_in)
                        out = inner(xi, sigma, **kw)
                        # CFG++ side-channel must survive the wrapper:
                        # samplers read ``model.last_uncond`` off the
                        # OUTER callable, so re-expose the inner CFG
                        # denoiser's uncond, re-anchored through the
                        # same blend as the cond output (without this,
                        # masked euler_cfg_pp silently degraded to
                        # plain euler semantics)
                        lu = getattr(inner, "last_uncond", out)
                        model.last_uncond = lu * mask_in \
                            + latents * (1.0 - mask_in)
                        return out * mask_in + latents * (1.0 - mask_in)

                out = sampler(model, x, sigmas, extra_args=extra, keys=keys)
                if has_mask:
                    out = out * mask_in + latents * (1.0 - mask_in)
                return out

            # the latent arg is donated: the scan carry (one latent-sized
            # buffer per step) aliases the input instead of doubling it.
            # sample() guards shared buffers by donating a copy.
            return jax.jit(core, donate_argnums=(1,))

        core = self._cache_get_or_make(static_key, make_core)
        absent = self._sample_placeholders(latents.shape[0])
        if y is None:
            y_arg = absent["y"]
        elif isinstance(y, (list, tuple)):
            y_arg = [jnp.asarray(v) for v in y]
        else:
            y_arg = y
        mask_arg = noise_mask if noise_mask is not None else absent["one"]
        cn_params_arg = [c[1] for c in control] if control is not None \
            else [{}]
        hint_arg = [c[2] for c in control] if control is not None \
            else [absent["hint"]]
        ctx_list = [jnp.asarray(c) for c, _, _, _ in conds + unconds]
        area_list = [jnp.asarray(m) if m is not None else absent["one"]
                     for _, m, _, _ in conds + unconds]
        concat_arg = c_concat if c_concat is not None else absent["concat"]
        objs_arg = gligen_objs[:2] if gligen_objs is not None \
            else absent["objs"]
        lat_arg = jnp.asarray(latents)
        if not donate_latents:
            # core always donates its latent arg; protect a buffer the
            # caller (or the workflow graph) still references by donating
            # a fresh on-device copy instead
            lat_arg = jnp.copy(lat_arg)
        # met by name, then enqueued by a plain call: a helper or a lambda
        # around ``core(...)`` costs seconds whenever the call traces
        # (see wait_previous_denoise)
        wait_previous_denoise()
        out = core(self.unet_params, lat_arg, ctx_list, area_list,
                   keys, sigmas, y_arg, mask_arg,
                   cn_params_arg, hint_arg, concat_arg, objs_arg)
        note_denoise(out)
        return out

    def _sample_placeholders(self, batch: int) -> Dict[str, Any]:
        """What ``core`` is given in place of what a request does not
        carry: no ADM vector (``y``), no area or noise mask (``one``), no
        control hint, no inpaint channels (``concat``), no grounding
        tokens (``objs``).  Made once a batch size and kept in the LRU:
        made anew they were eight tiny programs a request."""
        def make():
            return {"y": jnp.zeros((batch, 1)),
                    "one": jnp.ones((1, 1, 1, 1)),
                    "hint": jnp.zeros((1, 8, 8, 3)),
                    "concat": jnp.zeros((1, 1, 1, 1)),
                    "objs": (jnp.zeros((1, 1, 1)), jnp.zeros((1, 1, 1)))}
        return self._cache_get_or_make(("sample_placeholders", batch), make)

    # --- warmup -------------------------------------------------------------

    def warmup(self, height: int = 512, width: int = 512, batch: int = 1,
               steps: int = 20, cfg: float = 7.5,
               sampler_name: str = "euler", scheduler: str = "normal",
               denoise: float = 1.0, with_vae: bool = True) -> Dict[str, float]:
        """Ahead-of-time warmup for one serving shape: trace, compile and
        execute the CLIP encode, the jitted denoise loop and the VAE
        decode on zero inputs, exactly shaped like a txt2img request of
        ``batch`` images at ``width`` x ``height`` (ComfyUI //8 latent
        convention — the shapes EmptyLatentImage -> KSampler produce).

        Call at server startup (``POST /distributed/warmup`` or
        ``DTPU_WARMUP``): the first real request then hits the in-memory
        jit cache — time-to-first-image drops to dispatch cost — and,
        with the persistent compilation cache enabled
        (``runtime.manager.enable_persistent_compile_cache``), even a
        fresh process pays trace+deserialize instead of an XLA compile.

        When a live mesh with a >1 data axis exists, the warmup batch is
        fanned out and SHARDED exactly like a distributed run
        (jit keys compilations on input shardings: an unsharded warmup
        would leave the flagship SPMD program cold and the first real
        fan-out request would recompile anyway).
        Returns per-stage wall-clock seconds."""
        import time as _time

        from comfyui_distributed_tpu.ops.base import Conditioning
        from comfyui_distributed_tpu.parallel import collectives as coll
        from comfyui_distributed_tpu.parallel.mesh import get_live_runtime
        from comfyui_distributed_tpu.utils.trace import install_jax_monitoring
        install_jax_monitoring()
        timings: Dict[str, float] = {}
        t_all = _time.perf_counter()

        t0 = _time.perf_counter()
        ctx1, pooled = self.encode_prompt([""])
        jax.block_until_ready(ctx1)
        timings["clip_s"] = _time.perf_counter() - t0

        rt = get_live_runtime()
        mesh = rt.mesh if rt is not None and rt.num_participants > 1 \
            else None
        total = batch * (rt.num_participants if mesh is not None else 1)

        lh, lw = max(int(height) // 8, 1), max(int(width) // 8, 1)
        # the inputs as a txt2img request's sampler node makes them
        # (ops/basic.py _prepare_sample_inputs): the same program is warm
        seeds = np.zeros((total,), np.uint64)
        adm = self.family.unet.adm_in_channels is not None
        unclip = getattr(self.family, "adm_kind", "sdxl") == "unclip"
        cond = Conditioning(context=ctx1, pooled=pooled)
        vectors = []
        if adm and not unclip:   # one a CFG side, as the node asks
            from comfyui_distributed_tpu.ops.basic import _sdxl_vector_source
            vectors = [_sdxl_vector_source(self, cond, lh * 8, lw * 8)] * 2
        keys, ys, (context, uncond) = sampler_inputs(
            self, total, seeds,
            np.tile(np.arange(batch, dtype=np.uint32), total // batch),
            vectors, [ctx1, ctx1])
        y = ys[0] if ys else None
        if adm and unclip:
            from comfyui_distributed_tpu.ops.basic import _unclip_vector_cond
            y = _unclip_vector_cond(self, cond, total)
        lat = jnp.zeros((total, lh, lw, self.family.latent_channels),
                        jnp.float32)
        if mesh is not None:
            lat = coll.shard_batch(lat, mesh)
            context = coll.shard_batch(context, mesh)
            uncond = coll.shard_batch(uncond, mesh)
            if y is not None:
                y = coll.shard_batch(y, mesh)
        t0 = _time.perf_counter()
        out = self.sample(lat, context, uncond, seeds,
                          steps=int(steps), cfg=float(cfg),
                          sampler_name=str(sampler_name),
                          scheduler=str(scheduler), denoise=float(denoise),
                          y=y, donate_latents=True, keys=keys)
        jax.block_until_ready(out)
        timings["sample_s"] = _time.perf_counter() - t0

        if with_vae:
            t0 = _time.perf_counter()
            jax.block_until_ready(self.vae_decode(out))
            timings["vae_s"] = _time.perf_counter() - t0
        timings["total_s"] = _time.perf_counter() - t_all
        log(f"warmup {self.name}: {total}x{width}x{height} "
            f"{sampler_name}x{steps}"
            + (f" sharded over data={rt.num_participants}"
               if mesh is not None else "")
            + f" in {timings['total_s']:.2f}s "
            f"(clip {timings['clip_s']:.2f}s, "
            f"sample {timings['sample_s']:.2f}s)")
        return timings

    # --- internals ----------------------------------------------------------

    def _jitted(self, key, fn):
        return self._cache_get_or_make(key, lambda: jax.jit(fn))

    def _cache_get_or_make(self, key, make):
        with self._lock:
            if key in self._jit_cache:
                self._jit_cache.move_to_end(key)
                return self._jit_cache[key]
            fn = self._jit_cache[key] = make()
            while len(self._jit_cache) > self._jit_cache_cap:
                old_key, _ = self._jit_cache.popitem(last=False)
                log(f"jit cache: evicting {old_key!r} "
                    f"(cap {self._jit_cache_cap})")
            return fn


def _uncached(key, make):
    return make()


def sampler_inputs(pipe, total: int, seeds=None, sample_idx=None,
                   vectors: Sequence = (), contexts: Sequence = ()):
    """What a request's denoise takes beside its latent, made by ONE
    cached jitted program fed with host values (made eagerly it was
    twenty tiny programs an SD1.5 request and fifty an SDXL one, each
    enqueued from Python with the device dry between them).

    ``seeds`` (host, 64-bit ok) and ``sample_idx`` (default: the batch
    position) give ``keys [total, 2]`` (``samplers.fold_keys``).  Each of
    ``vectors``, ``(pooled [1, P] or None, sizes)``, gives an SDXL ADM
    vector ``[total, adm_in_channels]``: the pooled text embedding and
    the size scalars' embeddings, zero-padded or cut to the UNet's
    width.  Each of ``contexts`` comes back at ``total`` rows; one that
    has them already is passed as it is.

    The keys ride in the same program unless a device input is committed
    (a mesh's towers made it): then they get a call of their own, host
    values in, so they stay uncommitted, which is what ``core`` was
    compiled to take beside a batch laid over the mesh.

    The program lives in the pipeline's LRU under a key of the row count,
    the ADM width and every input's shape.  A ``pipe`` without that cache
    (a test's stand-in) is served uncached.
    Returns ``(keys or None, [y, ...], [context, ...])``."""
    cached = getattr(pipe, "_cache_get_or_make", _uncached)
    want = pipe.family.unet.adm_in_channels
    pooled = [np.zeros((1, 1280), np.float32) if p is None else p
              for p, _ in vectors]
    embs = [_size_embedding(cached, sizes) for _, sizes in vectors]
    out_ctx = list(contexts)
    short = [i for i, c in enumerate(out_ctx) if c.shape[0] != total]
    ctx_in = [out_ctx[i] for i in short]
    words = None
    if seeds is not None:
        lo, hi = smp.seed_words(seeds)
        words = (lo, hi, np.arange(lo.shape[0], dtype=np.uint32)
                 if sample_idx is None else sample_idx)

    def run(words, pooled, embs, ctx_in):
        if words is None and not pooled and not ctx_in:
            return None, [], []
        key = ("sampler_inputs", total, want, words is not None,
               tuple(tuple(np.shape(a)) for a in pooled + embs + ctx_in))
        return cached(key, lambda: _make_sampler_inputs(total, want))(
            words, pooled, embs, ctx_in)

    if words is not None and any(getattr(a, "committed", False)
                                 for a in pooled + ctx_in):
        keys = run(words, [], [], [])[0]
        _, ys, ctx_out = run(None, pooled, embs, ctx_in)
    else:
        keys, ys, ctx_out = run(words, pooled, embs, ctx_in)
    for i, c in zip(short, ctx_out):
        out_ctx[i] = c
    return keys, ys, out_ctx


def _size_embedding(cached, sizes: Tuple[float, ...]):
    """SDXL's size conditioning, ``[1, 256 * len(sizes)]``: the scalars'
    sinusoidal embeddings side by side.  Computed as it always was, a
    dozen eager operations, but once a tuple of scalars and kept (a
    server sees a handful of sizes): under the inputs' jit XLA would fold
    ``exp`` of the constant frequencies on the compiling host, whose
    ``exp`` is not the chip's, and the image would move by a last bit."""
    def make():
        from comfyui_distributed_tpu.models.layers import timestep_embedding
        s = jnp.asarray([list(sizes)], jnp.float32)
        return timestep_embedding(s.reshape(-1), 256).reshape(1, -1)
    return cached(("size_embedding", sizes), make)


def _make_sampler_inputs(total: int, want: Optional[int]):
    def rows(x):
        return x if x.shape[0] == total else jnp.repeat(x, total, axis=0)

    def sampler_inputs(words, pooled, embs, contexts):
        keys = None if words is None else smp.fold_keys(*words)
        ys = []
        for p, e in zip(pooled, embs):
            vec = jnp.concatenate([p, e], axis=-1)
            if vec.shape[-1] < want:
                vec = jnp.pad(vec, ((0, 0), (0, want - vec.shape[-1])))
            ys.append(rows(vec[:, :want]))
        return keys, ys, [rows(c) for c in contexts]

    return jax.jit(sampler_inputs)


# The previous denoise's output, of any pipeline (the device is one
# queue).  The TPU runtime lets the host run only so far ahead: some
# enqueue call of the NEXT request returns only when the previous
# request's denoise has finished, and which call depends on how many
# programs the request enqueues first.  While a request's sampler inputs
# were made eagerly (fifty tiny programs an SDXL request) that was the
# jnp.repeat of the ADM vector (3.4 s of every 3.6 s SDXL request; PR 24),
# and everything enqueued behind it ran with the device dry.  Since PR 52
# a request enqueues a dozen programs (``sampler_inputs`` is ONE), the
# runtime holds the host at none of them, and the host meets the device
# by name (trace.device_wait) at the last call before the denoise, here
# in ``sample``, for SD1.5 and SDXL alike; a fan-out request meets it
# once more, before its first shard placement, where the runtime held
# the host anyway (1.77 s of a 2.6 s four-chip cycle), with its programs
# already enqueued.  Behind the wait there is only ``core``'s own call,
# so ``dispatch`` is the host's own seconds.
#
# Both are plain calls beside the jitted call, never a wrapper around it:
# two Python frames (a helper and its lambda) between ``sample`` and
# ``core(...)`` made the SD1.5 denoise program's first call 5 s slower on
# the v5e host (trace 23.7 -> 26.3 s, lowering 9.4 -> 12.9 s over a
# set-up; PERF.md, PR 24), which is what refused PR 23.
_last_denoise: Any = None


def wait_previous_denoise() -> None:
    prev = _last_denoise
    # (a buffer donated onward reads as deleted: nothing to wait on)
    if prev is not None and not prev.is_deleted():
        with trace_mod.device_wait():
            jax.block_until_ready(prev)


def note_denoise(out) -> None:
    """``out`` is the next request's denoise to wait on (a tracer, under
    someone's jit or make_jaxpr, is nothing to wait on)."""
    global _last_denoise
    _last_denoise = None if isinstance(out, jax.core.Tracer) else out


def _virtual_params(module, seed: int, *shaped_args,
                    storage_dtype: Any = None) -> Any:
    """Deterministic random init WITHOUT compiling the model's init graph.

    ``module.init`` traces the full forward pass — for SDXL that is a
    multi-minute XLA compile before a single weight exists.  Virtual
    checkpoints only need *deterministic, sanely-scaled* weights, so we
    eval_shape the init (trace only, no compile) and fill each leaf with
    seeded numpy: fan-in-scaled normals for kernels, zeros for biases, ones
    for norm scales.  Per-leaf streams are keyed by crc32 of the tree path —
    stable across processes and hosts, so every mesh host materializes
    identical weights (the reference's "same models on all machines"
    requirement, ``README.md:189-193``).

    ``storage_dtype`` (bf16 weight storage) casts each float32 leaf on
    the HOST before the transfer: SDXL's towers are 13.6 GB as fp32, and
    a 16 GB chip cannot hold them next to their own bf16 copy."""
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), *shaped_args)
    leaf = _virtual_leaf(seed, storage_dtype)
    return jax.tree_util.tree_map_with_path(leaf, shapes)["params"]


def _virtual_leaf(seed: int, storage_dtype: Any = None):
    """The ONE copy of the virtual-init fill rules (shared with partial
    initializers like gligen_attach's missing-leaf graft)."""
    import zlib

    def leaf(path, sd):
        name = jax.tree_util.keystr(path)
        leaf_name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        rng = np.random.default_rng(
            (np.uint64(seed), np.uint64(zlib.crc32(name.encode()))))
        shape = tuple(sd.shape)
        dtype = sd.dtype
        if leaf_name in ("scale",):
            arr = np.ones(shape, np.float32)
        elif leaf_name in ("bias",) or len(shape) <= 1:
            arr = np.zeros(shape, np.float32)
        else:
            fan_in = int(np.prod(shape[:-1])) or 1
            arr = rng.standard_normal(shape, dtype=np.float32) \
                / np.sqrt(fan_in)
        if storage_dtype is not None and dtype == jnp.float32:
            dtype = storage_dtype
        return jnp.asarray(arr.astype(dtype))

    return leaf


# pipelines under plain names, (module, params) tuples under "cn:" keys,
# standalone-VAE pipelines under "vae:" keys — one model-asset cache, all
# cleared together by clear_pipeline_cache
_pipeline_cache: Dict[str, Any] = {}
_pipeline_lock = threading.Lock()


def load_pipeline(ckpt_name: str, models_dir: Optional[str] = None,
                  family_name: Optional[str] = None) -> DiffusionPipeline:
    """Load or virtually-initialize the named checkpoint (cached)."""
    # models_dir is part of the identity: it decides both which file loads
    # AND which tokenizer assets (vocab/merges) the pipeline picks up
    key = f"{ckpt_name}:{family_name or ''}:{models_dir or ''}"
    with _pipeline_lock:
        if key in _pipeline_cache:
            return _pipeline_cache[key]
    # making or reading the weights and placing them on the device, to
    # the moment the device holds them
    with trace_mod.stage("load_weights"):
        pipe = _make_pipeline(ckpt_name, models_dir, family_name)
        jax.block_until_ready((pipe.unet_params, pipe.clip_params,
                               pipe.vae_params))
    pipe.cache_token = key
    with _pipeline_lock:
        _pipeline_cache[key] = pipe
    return pipe


def _make_pipeline(ckpt_name: str, models_dir: Optional[str],
                   family_name: Optional[str]) -> DiffusionPipeline:
    fam = FAMILIES[family_name or detect_family(ckpt_name)]
    path = None
    if models_dir:
        cand = os.path.join(models_dir, ckpt_name.replace("\\", "/"))
        if os.path.exists(cand):
            path = cand

    from comfyui_distributed_tpu.runtime.checkpointing import (
        is_native_checkpoint, load_pipeline_checkpoint)
    if path is not None and is_native_checkpoint(path):
        # native orbax directory checkpoint (runtime/checkpointing.py) —
        # its manifest carries the family, overriding name heuristics
        native_family, unet_p, clip_ps, vae_p = load_pipeline_checkpoint(path)
        fam = FAMILIES[family_name or native_family]
    elif path is not None:
        from comfyui_distributed_tpu.models.checkpoints import load_checkpoint
        unet_p, clip_ps, vae_p = load_checkpoint(path, fam)
        log(f"loaded checkpoint {ckpt_name} ({fam.name}) from {path}")
    else:
        seed = _name_seed(ckpt_name)
        ds = fam.vae.downscale
        h = w = 8 * ds
        ctx_dim = fam.unet.context_dim
        # the UNet's input width, not the latent width: inpaint models
        # consume [latent, mask, masked-latent] = 9 channels
        x = jnp.zeros((1, h // ds, w // ds, fam.unet.in_channels))
        ts = jnp.zeros((1,))
        ctx = jnp.zeros((1, 77, ctx_dim))
        store = _storage_dtype(fam)
        unet_p = _virtual_params(unet_mod.UNet(fam.unet), seed, x, ts, ctx,
                                 storage_dtype=store)
        clip_ps = []
        for i, ccfg in enumerate(fam.clips):
            tok = jnp.zeros((1, ccfg.max_length), jnp.int32)
            clip_ps.append(_virtual_params(
                clip_mod.CLIPTextModel(ccfg), seed + 1 + i, tok,
                storage_dtype=store))
        img = jnp.zeros((1, h, w, 3))
        vae_p = _virtual_params(vae_mod.VAE(fam.vae), seed + 100, img)
        log(f"virtual checkpoint {ckpt_name!r} ({fam.name}): no file on disk, "
            f"deterministic init (seed {seed})")

    if _bf16_weights_enabled(fam):
        # bf16 WEIGHT STORAGE for the compute towers (UNet + CLIP): the
        # UNet computes in bf16 anyway, so fp32 storage only doubles the
        # HBM weight traffic every denoise step (and fp32 SDXL weights
        # would crowd a 16 GB v5e chip).  The VAE stays fp32 — its
        # GroupNorm/attention decode path is the one place bf16 weights
        # visibly cost quality.  Opt out: DTPU_BF16_WEIGHTS=0.
        unet_p = _cast_bf16(unet_p)
        clip_ps = [_cast_bf16(p) for p in clip_ps]
        log(f"{ckpt_name}: UNet/CLIP weights stored bf16 "
            f"(DTPU_BF16_WEIGHTS=0 for fp32)")

    return DiffusionPipeline(ckpt_name, fam, unet_p, clip_ps, vae_p,
                             prediction_type=fam.unet.prediction_type,
                             assets_dir=models_dir)


def _bf16_weights_enabled(fam: ModelFamily) -> bool:
    """bf16 weight storage default: on for the real families (their UNet
    dtype is bf16), off for 'tiny' (fp32 module — deterministic CPU
    tests)."""
    env = os.environ.get("DTPU_BF16_WEIGHTS")
    if env is not None:
        return env not in ("0", "false", "")
    return fam.unet.dtype == jnp.bfloat16


def _storage_dtype(fam: ModelFamily) -> Any:
    """Storage dtype for virtually-initialised compute towers: bf16 where
    `_bf16_weights_enabled`, else None (leave each leaf's own dtype)."""
    return jnp.bfloat16 if _bf16_weights_enabled(fam) else None


def _cast_bf16(tree):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if hasattr(a, "dtype") and a.dtype == jnp.float32 else a, tree)


def clear_pipeline_cache() -> None:
    """Free model memory (feeds the control plane's clear_memory route —
    the reference's VRAM-clear endpoint, ``distributed.py:383-426``)."""
    with _pipeline_lock:
        _pipeline_cache.clear()
        _derived_cache.clear()
        _cn_family_cache.clear()
        _embedding_cache.clear()
        _clip_vision_cache.clear()
    from comfyui_distributed_tpu.models import hypernetwork as hn_mod
    from comfyui_distributed_tpu.models import lora as lora_mod
    lora_mod.clear_lora_cache()
    hn_mod.clear_hypernetwork_cache()
    from comfyui_distributed_tpu.models import gligen as gg_mod
    from comfyui_distributed_tpu.models import style_model as sm_mod
    sm_mod.clear_style_model_cache()
    gg_mod.clear_gligen_cache()


# --- language models (models/looplm.py, models/mla_moe.py, models/swa_moe.py,
# models/ssm_hybrid.py, models/dsa_moe.py, models/sambay.py,
# models/mla_scmoe.py) -------------------------------------------------------
#
# A LANGUAGE_MODEL is resident beside the diffusion checkpoints in the one
# model-asset cache (``clear_pipeline_cache`` frees both).  Nothing of it is
# imported, made or traced until a graph names it.

# the text a prompt expander is asked to continue; an operator's
# ``instructions`` (few-shot examples, a house style), the same in every
# request, stand in front of it
EXPAND_TEMPLATE = ("Rewrite this image prompt with more visual detail. "
                   "Prompt: {text} Detailed prompt:")

# The row counts ``lm_generate`` is compiled for, all of them when the
# first request of a length meets the model (no shape is built later:
# a served window compiles nothing).  An execution is padded to the next
# count with copies of its first row; the last count also bounds what is
# kept for requests still in the queue (server/lm_handover.py): three
# results.  Argued per family (the comments in `LM_FAMILIES`); all seven
# take these.
LM_ROW_COUNTS = (1, 4)


@dataclasses.dataclass(frozen=True)
class LMFamily:
    """What the serving path needs of a language-model architecture: the
    ONE place a model file is named.  ``module`` (under ``models/``) is
    imported when a graph first names a model of the family, and gives
    ``CONFIGS`` (``full`` and ``tiny``), ``param_count``,
    ``seeded_params(cfg, seed)``, ``load_checkpoint(path, cfg)``,
    ``make_program(cfg, new_tokens)`` (the jitted ``lm_generate`` ->
    ids, logits, ``aux`` arrays per position, ``stats`` to count from:
    `lm_decode.make_program` around the family's ``generate``, which is
    its ``prefill`` and ``step`` closures, its own state and ONE call of
    `lm_decode.generate`; the loop, the sampler and the prefix writer
    are there, not in the family),
    ``kv_cache_bytes(cfg, rows, positions)`` (the state indexed by
    POSITION: keys and values, a latent), ``window_counters(cfg, stats,
    real_rows, steps)`` and ``few_rows_here`` (`looplm`'s); where it
    keeps a RECURRENT state as well, overwritten in place and no function
    of the positions, ``state_bytes(cfg, rows)``; where its state is of
    more than one geometry or kind, ``kv_cache_bytes_by_kind`` -> the
    parts by name (a ring and a full cache; recurrent and positional;
    keys and values and the index keys that choose among them;
    recurrent, rings and the one cache that several layers read);
    where the state behind a prompt's first ids can stand for them
    (nothing in it depends on where in the buffer a row lies),
    ``make_prefix_program(cfg)`` (the jitted ``lm_prefix_state``: ids
    ``[K]`` -> the snapshot, a dict whose ``keys`` are ``[L, K, ...]``
    (`lm_decode.prefix_length`), which ``lm_generate`` then takes as a
    sixth argument with the ids behind the prefix as its prompt: the
    family's ``from_prefix`` hook writes it at each row's own offset
    with `lm_decode.write_at_offsets`) and ``prefix_bytes(cfg, K)``.
    Its config gives ``vocab_size`` and ``layer_applications`` (per
    token)."""
    module: str
    names: Tuple[str, ...]          # what a model name of it contains
    what: str

    def load(self):
        import importlib
        return importlib.import_module(
            f"comfyui_distributed_tpu.models.{self.module}")


LM_FAMILIES = {
    # Why 4 rows and no more: a decode step streams 20 GB of weights for
    # all rows and 0.2 GB of cache for each, the cache is 0.2 GB a row
    # beside 8 GB resident on a 16 GB chip, and every count is one more
    # program to trace, lower and load at set-up.  Why no count between:
    # what sharing costs is the step from one row to more than one, not
    # the rows.  On the chip an execution takes 1.93 s at 1 row and 2.18 s
    # at 4, all of the difference in the attention sub-layer (from two
    # rows up XLA lowers its projections another way; the MLP's stream
    # takes the same 1.18 s), and with (1, 2, 4) the four-caller cell,
    # whose executions carry two real rows, gained 0.5% in images a second
    # for a third program at set-up: about 2.17 s at 2 rows (PERF.md
    # section 6, PR 28).
    "ouro": LMFamily(
        "looplm", ("ouro",),
        "Ouro-2.6B: a dense looped decoder, a per-head KV cache with a "
        "slot per loop and layer"),
    # The latent cache is 5.8 KB a position a row (0.7 MB a row of 128
    # positions, where Ouro's is 200 MB), so MEMORY no longer argues for
    # 4: a hundred rows would fit.  What still does: the rows come from
    # the requests waiting in one server's queue (four callers in the
    # cell: more rows than callers are padding), every count is a program
    # to compile at set-up, and each further row routes 8 more pairs a
    # layer, so the experts a step reads grow with the rows (1.9 distinct
    # local experts a layer at 4 rows) where a dense model's bytes do
    # not.  Rows past 4 cost 0.7 MB each and are ROADMAP B7's to measure.
    "pangu": LMFamily(
        "mla_moe", ("pangu",),
        "openPangu-Ultra-MoE-718B, one chip's share: latent attention "
        "(MLA) with a latent cache, 16 of 256 routed experts held"),
    # A row's cache is 2.3 MB at 576 positions (four rings of 128 slots and
    # one full layer, 4 KiB a key and value a layer) beside 7.4 GB
    # resident: memory argues for 4 as little as openPangu's latent cache
    # does.  What does: the rows are the requests waiting in one server's
    # queue (four callers in the cell), every count is a program to compile
    # at set-up (this family's prefill of 512 positions is the costliest
    # to compile here), each further row routes 8 more pairs a layer (2.75
    # distinct local experts a layer at 3 rows, 75 MB each, where the
    # dense part's 2.4 GB a step does not grow), and the PREFILL is
    # compute-bound: a row adds its 512 positions' FLOPs whole, so rows
    # past 4 would buy the decode's shared stream with prefill seconds
    # that nobody shares.  ROADMAP B7's to measure.
    "exaone": LMFamily(
        "swa_moe", ("exaone",),
        "K-EXAONE-236B-A23B, one chip's share: window and full attention "
        "layers in one stack (a 128-slot ring beside a full cache, GQA "
        "64/8), 16 of 128 routed experts held"),
    # A row's state is 76.4 MB whatever its length (36 Mamba layers x 2.1
    # MB of float32 and a 26 KB tail) and 17.3 MB of keys and values at
    # 2112 positions (4 attention layers): 0.37 GB at 4 rows beside 6.4 GB
    # resident, so memory would take a dozen rows.  What argues for 4 is
    # what argues for it in the other three: the rows are the requests
    # waiting in one server's queue (four callers in the cell), every
    # count is a program to compile at set-up, and the PREFILL (2048
    # positions a row here, as much of an execution as the decode) is
    # compute-bound, so a row adds its positions' FLOPs whole while only
    # the decode's weight stream is shared.  The state a step reads and
    # writes grows with the rows (151 MB a row a step, where a dense
    # model's cache at 128 positions is a few MB): at 4 rows 0.6 GB beside
    # the weights' 6.4, at 16 it would be a third of the step.  (Since
    # PR 41 rows that share their instructions start from a snapshot of
    # the state behind them and prefill what follows: 97 positions a row
    # in the cell.  The argument stands for prompts that share nothing.)
    "granite": LMFamily(
        "ssm_hybrid", ("granite",),
        "granite-4.0-h-micro, whole: Mamba-2 state-space layers with an "
        "attention layer every ten (a recurrent state beside a key-value "
        "cache), a tied embedding"),
    # A row's caches are 13,056 B a position over the six blocks (keys and
    # values 2 KiB a block, the index key 128 B): 107.8 MB at 8,256
    # positions, 0.43 GB at 4 rows beside 8.75 GB resident and SD1.5, and a
    # prefill of 4 x 8192 positions holds a gigabyte of index scores and
    # attention blocks at a time besides: here MEMORY argues for 4 and no
    # more, as it did for Ouro.  So does what argued in the others: the
    # rows are the requests waiting in one server's queue (four callers in
    # the cell), every count is a program to compile at set-up (this
    # family's prefill, twelve selecting chunks a block, is the costliest
    # of all), the PREFILL is compute-bound (a row adds its positions'
    # FLOPs whole) and each further row routes 8 more pairs a block over
    # 128 held experts, 9.4 MB each: a step's bytes grow with the rows
    # where a dense model's do not.  Why not fewer: one weight stream
    # (0.9 GB of non-expert weights and the head a step) serves every row.
    "keye": LMFamily(
        "dsa_moe", ("keye",),
        "Keye-VL-2.0-30B-A3B's language model, one pipeline stage of 8: a "
        "learned index picks 2,048 keys a query (an index-key cache beside "
        "the key-value cache, GQA 32/4), all 128 routed experts held; the "
        "vision tower is not"),
    # A row's state at 8,256 positions is 66.5 MB (the ONE cache that
    # eight layers read, 5,120 B a position: 42.3 MB; eight rings of 512
    # slots: 21.0 MB; nine float32 states and tails: 3.2 MB) where 32
    # layers of the same heads would hold 1.35 GB: 0.27 GB at 4 rows
    # beside 7.71 GB resident and SD1.5, so memory would take dozens of
    # rows.  What argues for 4 is what argued in the others: the rows are
    # the requests waiting in one server's queue (four callers in the
    # cell), every count is a program to compile at set-up, and the
    # PREFILL's front half (17 layers over every one of a row's 8,192
    # positions, the selective scan 8,192 sequential steps a layer
    # whatever the rows) is compute- and latency-bound: a row adds its
    # positions' FLOPs whole, while only the decode's 7.7 GB weight
    # stream is shared.  A step also reads the one cache once for each of
    # its eight readers: 0.34 GB a row at 8,200 positions, 1.34 GB at 4
    # rows beside the weights' 7.7, at 16 rows two thirds of the step.
    "phi4flash": LMFamily(
        "sambay", ("phi-4-mini-flash", "phi4flash"),
        "Phi-4-mini-flash-reasoning, whole: a decoder-hybrid-decoder, "
        "Mamba-1 and window-512 differential attention in front (float32 "
        "states, rings), ONE key-value cache and ONE state-space memory "
        "shared by the 14 layers behind, a tied embedding"),
    # A row's cache is 9,216 B a position over the eight attentions (two
    # latent slots a layer, 576 bf16 values each): 19.5 MB at 2,112
    # positions, 78 MB at 4 rows beside 10.35 GB resident and SD1.5, so the
    # CACHE would take a hundred rows.  What argues for 4 and no more here
    # is the prefill: 2,048 positions a row of compute-bound work through
    # 5.1 GB of dense weights (11 TFLOP a row, a row adds it whole) whose
    # float32 temporaries stand beside 13 GB resident on a 16 GB chip: the
    # fifth row would not fit.  And what argued in the others: the rows
    # are the requests waiting in one server's queue (four callers in the
    # cell), every count is a program to compile at set-up, each further
    # row routes 12 more pairs a layer (of which a third are zero experts
    # and cost nothing, and 0.25 hit one of the 16 experts held, 75 MB
    # each).  Why not fewer: one stream of 5.3 GB of non-expert weights a
    # step serves every row.
    "longcat": LMFamily(
        "mla_scmoe", ("longcat",),
        "LongCat-Flash-Omni's language model, one chip's share of 32: a "
        "layer of two latent attentions (two latent cache slots) and two "
        "dense MLPs with a shortcut-connected expert layer between them, "
        "16 of 512 routed experts held, 256 zero-compute experts in the "
        "router's 768 outputs; the audio and vision towers are not held"),
}


def detect_lm_family(name: str) -> Tuple[str, str]:
    """``(family, size)`` of a model name: the family of `LM_FAMILIES`
    whose name it contains, at size ``tiny`` under
    ``DTPU_DEFAULT_FAMILY=tiny`` (tests, rehearsals) or by name
    (``tiny`` / ``test``; with no family named that is the first
    family's tiny model), else ``full`` (the published config).  A name
    of no known family is refused: it used to be served as Ouro."""
    lowered = name.lower()
    tiny = os.environ.get(FAMILY_ENV, "").startswith("tiny") \
        or "tiny" in lowered or "test" in lowered
    for family, entry in LM_FAMILIES.items():
        if any(n in lowered for n in entry.names):
            return family, "tiny" if tiny else "full"
    if tiny:
        return next(iter(LM_FAMILIES)), "tiny"
    known = "; ".join(f"{'/'.join(e.names)} ({e.what})"
                      for e in LM_FAMILIES.values())
    raise ValueError(
        f"language model {name!r} is of no family this server knows: a "
        f"model name has to contain one of: {known}")


@dataclasses.dataclass
class LMOutput:
    """What a generation leaves on the device: the prompt's real ids, and
    of the execution that served it the new ids ``[B, N]``, the float32
    logits each was drawn from ``[B, N, V]`` and ``aux``, the family's
    other per-position arrays ``[B, N, ...]`` (a looped model's exit
    probabilities, an expert model's router scores and choices);
    ``row`` is this request's."""
    prompt_ids: np.ndarray
    tokens: Any
    logits: Any
    aux: Dict[str, Any]
    row: int = 0


@dataclasses.dataclass(frozen=True)
class LMRow:
    """One request's call of the generate node: a row of an execution.
    ``instructions`` is the operator's text in front of the template
    (rows of one execution may carry different ones)."""
    text: str
    seed: int = 0
    temperature: float = 0.0
    instructions: str = ""


class LanguageModel:
    """A decoder of one of `LM_FAMILIES`, its tokenizer and its jitted
    program."""
    row_counts = LM_ROW_COUNTS

    def __init__(self, name: str, cfg: Any, params: Any, tokenizer: Any,
                 family: str = "ouro"):
        self.name, self.cfg, self.params = name, cfg, params
        self.tokenizer = tokenizer
        self.family = family
        self._arch = LM_FAMILIES[family].load()
        # (new tokens, prompt positions, of them a snapshot's) -> {rows:
        # compiled lm_generate}
        self._programs: Dict[Tuple[int, int, int], Dict[int, Any]] = {}
        # positions -> compiled lm_prefix_state
        self._prefix_makers: Dict[int, Any] = {}
        # a prefix's ids -> its snapshot on the device, least recently
        # used first: as many as an execution has rows (each could bring
        # its own), 76.4 MB + 8 KiB a position each at the published size
        self._prefixes: "collections.OrderedDict[bytes, Any]" = \
            collections.OrderedDict()
        # what the tokenizer has made, least recently used first: a set of
        # instructions' ids under its text (as many sets as `_prefixes`
        # keeps snapshots) and a row's under everything they are a function
        # of (text, prompt_tokens, instructions; the rows of four
        # executions), so that the hand-over and `generate_rows` may ask
        # for a request's ids as often as they like and the tokenizer
        # walks them once.  Under a lock of their own: `_lock` is held
        # through a compilation
        self._instruction_ids: "collections.OrderedDict[str, np.ndarray]" = \
            collections.OrderedDict()
        self._row_ids: "collections.OrderedDict[tuple, np.ndarray]" = \
            collections.OrderedDict()
        self._ids_lock = threading.RLock()
        self._mesh = None
        self._lock = threading.Lock()

    def _ensure_laid_out(self) -> None:
        """Under a multi-device mesh: the weights replicated over ``data``
        (column-split over a live ``tensor`` axis by the shape rule every
        tower takes), once per mesh, so that the program runs where the
        rest of the graph does.  No-op on one device."""
        from comfyui_distributed_tpu.parallel.mesh import get_live_runtime
        mesh = getattr(get_live_runtime(), "mesh", None)
        if mesh is None or mesh.size <= 1 or self._mesh is mesh:
            return
        with self._lock, trace_mod.stage("load_weights"):
            self.params = shd.apply_shardings(
                self.params, shd.params_shardings(self.params, mesh))
            jax.block_until_ready(self.params)
            self._mesh = mesh
            self._programs.clear()
            self._prefix_makers.clear()
            self._prefixes.clear()

    def _pass(self, encode: Callable[[str], Sequence[int]], text: str
              ) -> np.ndarray:
        """One pass of the tokenizer over ``text``, counted."""
        ids = np.asarray(encode(text), np.int32)
        trace_mod.GLOBAL_COUNTERS.bump("lm.prompt_encodes")
        trace_mod.GLOBAL_COUNTERS.bump("lm.prompt_encode_ids", len(ids))
        return ids

    def _kept_ids(self, memo: "collections.OrderedDict", key: Any, room: int,
                  make: Callable[[], np.ndarray]) -> np.ndarray:
        """``memo[key]``: kept from an earlier call, else what ``make``
        gives, with the least recently used entry beyond ``room`` let go.
        Made under the lock and read-only: whoever asks, and two threads
        that ask at once, get the one array."""
        with self._ids_lock:
            ids = memo.get(key)
            if ids is not None:
                memo.move_to_end(key)
                return ids
            ids = memo[key] = make()
            ids.setflags(write=False)
            while len(memo) > room:
                memo.popitem(last=False)
        return ids

    def instruction_ids(self, instructions: str) -> np.ndarray:
        """The tokenizer's ids of an operator's ``instructions`` alone,
        made once per set of instructions."""
        return self._kept_ids(
            self._instruction_ids, instructions, self.row_counts[-1],
            lambda: self._pass(self.tokenizer.encode, instructions))

    def prompt_ids(self, text: str, prompt_tokens: int,
                   instructions: str = "") -> np.ndarray:
        """The real ids of ``instructions`` and, behind them, ``text``
        under the expander's template, cut to ``prompt_tokens``: what
        ``tokenizer.encode`` gives for the whole, to the id, made once per
        request (where the tokenizer says what a text adds behind a space,
        from the instructions' kept ids and a pass over the rest alone).
        Refused here, at every call, where the device would clamp an index
        out of range in silence."""
        def make() -> np.ndarray:
            asked = EXPAND_TEMPLATE.format(text=text)
            behind = getattr(self.tokenizer, "encode_behind_space", None)
            if instructions and behind is not None:
                ids = np.concatenate([self.instruction_ids(instructions),
                                      self._pass(behind, asked)])
            else:
                ids = self._pass(
                    self.tokenizer.encode,
                    f"{instructions} {asked}" if instructions else asked)
            return ids[:prompt_tokens]

        ids = self._kept_ids(
            self._row_ids, (text, int(prompt_tokens), instructions),
            4 * self.row_counts[-1], make)
        if not len(ids) or ids.min() < 0 or ids.max() >= self.cfg.vocab_size:
            raise ValueError(
                f"{self.name}: a prompt of {len(ids)} ids in "
                f"[{ids.min(initial=0)}, {ids.max(initial=0)}] cannot be "
                f"generated from a vocabulary of {self.cfg.vocab_size}")
        return ids

    def shared_prefix(self, rows: Sequence[LMRow], prompt_tokens: int,
                      ids: Optional[Sequence[np.ndarray]] = None
                      ) -> Optional[np.ndarray]:
        """The ids a snapshot can stand for in an execution of ``rows``,
        or None: the family can start a row from the state behind a
        prefix, every row carries the same non-empty ``instructions``,
        and their ids are a true prefix of each row's ``ids``
        (`prompt_ids`' where None) with at least one id behind them."""
        instructions = rows[0].instructions
        if not hasattr(self._arch, "make_prefix_program") \
                or not instructions \
                or any(r.instructions != instructions for r in rows):
            return None
        prefix = self.instruction_ids(instructions)
        if ids is None:
            ids = [self.prompt_ids(r.text, prompt_tokens, r.instructions)
                   for r in rows]
        K = len(prefix)
        if any(len(i) <= K or not np.array_equal(i[:K], prefix)
               for i in ids):
            return None
        return prefix

    def _snapshot(self, prefix: np.ndarray) -> Any:
        """The state behind ``prefix`` on the device: kept from an earlier
        execution, else made now (the maker compiled at a length's first
        use) with the least recently used one let go."""
        bump, key = trace_mod.GLOBAL_COUNTERS.bump, prefix.tobytes()
        with self._lock:
            snapshot = self._prefixes.get(key)
            if snapshot is not None:
                self._prefixes.move_to_end(key)
                return snapshot
            maker = self._prefix_makers.get(len(prefix))
            if maker is None:
                maker = self._prefix_makers[len(prefix)] = \
                    self._arch.make_prefix_program(self.cfg).lower(
                        self.params, jax.ShapeDtypeStruct(prefix.shape,
                                                          np.int32)).compile()
            with trace_mod.stage("lm_prefix_state"):
                snapshot = self._prefixes[key] = jax.block_until_ready(
                    maker(self.params, prefix))
            bump("lm.prefix_misses")
            while len(self._prefixes) > self.row_counts[-1]:
                self._prefixes.popitem(last=False)
                bump("lm.prefix_evictions")
            trace_mod.GLOBAL_GAUGES.set("lm.prefix_bytes", sum(
                self._arch.prefix_bytes(self.cfg, len(k) // prefix.itemsize)
                for k in self._prefixes))
        return snapshot

    def _compiled(self, n: int, prompt_tokens: int, *snapshot: Any
                  ) -> Dict[int, Any]:
        """``lm_generate`` for ``n`` new tokens behind ``prompt_tokens``
        positions, compiled for every count of ``row_counts`` at once;
        with a ``snapshot``, for rows that start from one of its length
        and prefill the positions behind it."""
        from comfyui_distributed_tpu.models import lm_decode
        held = lm_decode.prefix_length(snapshot[0]) if snapshot else 0
        with self._lock:
            programs = self._programs.get((n, prompt_tokens, held))
            if programs is None:
                jitted = self._arch.make_program(self.cfg, n)

                def row(dtype, *shape):
                    return jax.ShapeDtypeStruct(shape, dtype)

                programs = self._programs[(n, prompt_tokens, held)] = {
                    b: jitted.lower(
                        self.params, row(np.int32, b, prompt_tokens - held),
                        row(np.int32, b), row(np.uint32, b),
                        row(np.float32, b), *snapshot).compile()
                    for b in self.row_counts}
        return programs

    def generate_rows(self, rows: Sequence[LMRow], max_new_tokens: int = 64,
                      prompt_tokens: int = 64,
                      spans: Sequence[Any] = (),
                      ids: Optional[Sequence[np.ndarray]] = None
                      ) -> List[Tuple[str, LMOutput]]:
        """The continuations of the rows' texts under the expander's
        template: ONE execution of ``lm_generate`` for all of them (each
        prompt padded to ``prompt_tokens``, then exactly
        ``max_new_tokens`` decode steps; the end-of-text id does not stop
        it, so one shape runs), each row with its own length, seed and
        temperature.  The host meets the device here, in the middle of a
        graph: the ids have to be words before the text encoder can be
        enqueued.  What the family counts of an execution (its ``stats``)
        comes over in the same read.

        Where `shared_prefix` finds one, every row starts from its
        snapshot (made at the first execution that brings these
        instructions, kept for the next) and the program prefills the ids
        behind it only; every other execution runs the whole prompt, as
        it did.

        Counted per request served, so that tokens over stages stays the
        steps of one execution: the first row is the caller's and its
        ``lm_generate`` stage lies on the current span; row ``i`` behind
        it is a request still in the queue whose root span is
        ``spans[i - 1]``, and gets the same interval there.  ``ids``: the
        rows' `prompt_ids`, where the caller holds them already."""
        self._ensure_laid_out()
        n, real = int(max_new_tokens), len(rows)
        if n < 1 or not 1 <= real <= self.row_counts[-1]:
            raise ValueError(
                f"{self.name}: {n} new tokens for {real} row(s) cannot be "
                f"generated (at least 1 token, 1 to {self.row_counts[-1]} "
                f"rows)")
        if ids is None:
            ids = [self.prompt_ids(r.text, prompt_tokens, r.instructions)
                   for r in rows]
        count = next(b for b in self.row_counts if b >= real)
        prefix = self.shared_prefix(rows, prompt_tokens, ids)
        held = 0 if prefix is None else len(prefix)
        # the program's sixth argument, where its rows start from one
        snapshot = () if prefix is None else (self._snapshot(prefix),)
        # a padded row repeats the first
        source = [*range(real), *[0] * (count - real)]
        padded = np.full((count, prompt_tokens - held),
                         self.tokenizer.pad_id, np.int32)
        for b, i in enumerate(source):
            padded[b, :len(ids[i]) - held] = ids[i][held:]
        program = self._compiled(n, prompt_tokens, *snapshot)[count]
        t0 = time.time()
        with trace_mod.stage("lm_generate"):
            tokens, logits, aux, stats = program(
                self.params, padded,
                np.asarray([len(ids[i]) - held for i in source], np.int32),
                np.asarray([rows[i].seed & 0xFFFFFFFF for i in source],
                           np.uint32),
                np.asarray([rows[i].temperature for i in source],
                           np.float32), *snapshot)
            with trace_mod.device_wait():
                # dtpu-lint: ignore[spine-host-fetch] ids must be words before CLIP can run
                host_tokens, stats = jax.device_get((tokens, stats))
        t1 = time.time()
        trace_mod.mark_instant("lm_ids_ready", at=t1)
        for span in spans:
            trace_mod.record_stage("lm_generate", t0, t1, parent=span)
            trace_mod.mark_instant("lm_ids_ready", span, t1)
        out = []
        for b in range(real):
            with trace_mod.stage("detokenize"):
                words = self.tokenizer.decode(host_tokens[b])
            out.append((words, LMOutput(ids[b], tokens, logits, aux, b)))
        bump = trace_mod.GLOBAL_COUNTERS.bump
        bump("lm.prompt_tokens", sum(len(i) for i in ids))
        bump("lm.tokens_decoded", n * real)
        bump("lm.layer_applications", n * real * self.cfg.layer_applications)
        bump("lm.executions")
        # the program of ``count`` rows was built with the few-row kernel
        # where its decode step is such a call (0 is written, so the pair
        # is there whenever a model has run)
        bump("lm.executions_fewrow", int(self._arch.few_rows_here(count)))
        bump("lm.rows", real)
        bump("lm.padded_rows", count - real)
        if prefix is not None:
            bump("lm.prefix_hits", real)
            bump("lm.prefix_positions_served", real * held)
        for name, value in self._arch.window_counters(
                self.cfg, stats, real, n).items():
            bump(name, value)
        shape = (self.cfg, count, prompt_tokens + n)
        trace_mod.GLOBAL_GAUGES.set("lm.kv_cache_bytes",
                                    self._arch.kv_cache_bytes(*shape))
        # a family with caches of more than one geometry says each part
        by_kind = getattr(self._arch, "kv_cache_bytes_by_kind", None)
        for kind, nbytes in (by_kind(*shape) if by_kind else {}).items():
            trace_mod.GLOBAL_GAUGES.set(f"lm.kv_cache_bytes_{kind}", nbytes)
        # and one with a recurrent state, what that takes beside the cache
        recurrent = getattr(self._arch, "state_bytes", None)
        if recurrent is not None:
            trace_mod.GLOBAL_GAUGES.set("lm.state_bytes",
                                        recurrent(self.cfg, count))
        return out

    def generate(self, text: str, seed: int = 0, max_new_tokens: int = 64,
                 prompt_tokens: int = 64, temperature: float = 0.0
                 ) -> Tuple[str, LMOutput]:
        """One request alone: `generate_rows` of one row."""
        return self.generate_rows([LMRow(text, int(seed), temperature)],
                                  max_new_tokens, prompt_tokens)[0]


def _device_free_bytes() -> Optional[int]:
    """Bytes the first device's allocator can still give; None where it
    does not say (the CPU)."""
    stats = jax.local_devices()[0].memory_stats() or {}
    if "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


def load_language_model(name: str, models_dir: Optional[str] = None
                        ) -> LanguageModel:
    """Load or virtually-initialize the named language model (cached
    beside the pipelines).  A file of that name under ``models_dir`` is
    read as the model's Hugging Face safetensors; without one the
    weights are seeded from the name and drawn ON THE DEVICE (billions
    of values: numpy on the host would take a minute).

    Two graphs that name two models keep BOTH resident (nothing here
    evicts: a model that left would be made again, tens of seconds, at
    its next request).  Where the second cannot fit beside what is
    resident (Ouro-2.6B's 5.3 GB and openPangu's 9.8 GB share do not
    share one 16 GB chip with a checkpoint, nor K-EXAONE's 7.4 GB share
    with openPangu's, nor granite-4.0-h-micro's 6.4 GB with either
    share, nor Keye-VL-2.0's 8.75 GB stage with any) it is refused BY
    NAME, with
    what it needs and what is resident, before the allocator fails with
    an error that names nothing."""
    from comfyui_distributed_tpu.models.tokenizer import make_lm_tokenizer
    key = f"lm:{name}:{models_dir or ''}"
    with _pipeline_lock:
        if key in _pipeline_cache:
            return _pipeline_cache[key]
    family, size = detect_lm_family(name)
    arch = LM_FAMILIES[family].load()
    cfg = arch.CONFIGS[size]
    path = os.path.join(models_dir, name) if models_dir else None
    need = arch.param_count(cfg) * jnp.dtype(cfg.dtype).itemsize
    free = _device_free_bytes()
    if free is not None and need > free:
        with _pipeline_lock:
            resident = sorted(k.split(":")[1] for k in _pipeline_cache
                              if k.startswith("lm:"))
        raise ValueError(
            f"language model {name!r} needs {need / 1e9:.2f} GB for its "
            f"weights and the device has {free / 1e9:.2f} GB free; "
            f"resident language models: {resident or 'none'} (none is "
            f"evicted: serve one language model a chip)")
    with trace_mod.stage("load_weights"):
        if path is not None and os.path.exists(path):
            params = arch.load_checkpoint(path, cfg)
            log(f"loaded language model {name} from {path}")
        else:
            seed = _name_seed(name)
            params = arch.seeded_params(cfg, np.uint32(seed))
            log(f"virtual language model {name!r} ({family}): no file on "
                f"disk, {arch.param_count(cfg) / 1e9:.3f} B seeded values "
                f"made on the device (seed {seed})")
        jax.block_until_ready(params)
    model = LanguageModel(name, cfg, params,
                          make_lm_tokenizer(models_dir, cfg.vocab_size),
                          family)
    with _pipeline_lock:
        _pipeline_cache[key] = model
    return model


# derived pipelines (clip-skip variants, external VAEs): param trees are
# SHARED with the base — only configs/modules differ — but each clone
# carries its own jit caches, so keep identity stable across runs
_derived_cache: "collections.OrderedDict[Tuple, DiffusionPipeline]" = \
    collections.OrderedDict()
_DERIVED_CACHE_CAP = 8

# ControlNet file -> inferred family name (load_controlnet): lets the
# repeat call hit the pipeline cache without re-reading the file
_cn_family_cache: Dict[str, str] = {}


def derived_cached(base: DiffusionPipeline,
                   tag: str) -> Optional[DiffusionPipeline]:
    """Cache probe for derive_pipeline — ops that pay a real cost to
    BUILD their derivation inputs (weight-space merges) check this
    first instead of recomputing a tree the cache would discard."""
    with _pipeline_lock:
        return _derived_cache.get((base.cache_token, tag))


def copy_sampler_patches(src: DiffusionPipeline,
                         dst: DiffusionPipeline) -> None:
    """Sampler-visible patches that must ride EVERY derivation chain
    (derive_pipeline AND the LoRA loader's direct construction):
    RescaleCFG's rescale, a zsnr-patched schedule, and every attr ever
    applied via derive_pipeline(extra_attrs=...) (PerpNeg's empty cond +
    scale, ...)."""
    dst.cfg_rescale = getattr(src, "cfg_rescale", 0.0)
    dst.schedule = src.schedule
    riding = set(getattr(src, "_riding_attrs", ()))
    for attr in riding:
        if hasattr(src, attr):
            setattr(dst, attr, getattr(src, attr))
    dst._riding_attrs = frozenset(riding)


def derive_pipeline(base: DiffusionPipeline, tag: str,
                    family: Optional[ModelFamily] = None,
                    vae_params: Any = None,
                    cfg_rescale: Optional[float] = None,
                    prediction_type: Optional[str] = None,
                    schedule: Any = None,
                    extra_attrs: Optional[Dict[str, Any]] = None,
                    unet_params: Any = None,
                    clip_params: Any = None) -> DiffusionPipeline:
    """Cached clone of ``base`` with a replacement family (e.g. clip-skip
    configs), VAE params, and/or sampling patches; everything else shared
    by reference."""
    key = (base.cache_token, tag)
    with _pipeline_lock:
        if key in _derived_cache:
            _derived_cache.move_to_end(key)
            return _derived_cache[key]
    clone = DiffusionPipeline(
        f"{base.name}|{tag}", family or base.family,
        unet_params if unet_params is not None else base.unet_params,
        clip_params if clip_params is not None else base.clip_params,
        vae_params if vae_params is not None else base.vae_params,
        prediction_type=prediction_type or base.prediction_type,
        assets_dir=base.assets_dir)
    # sampling patches ride derivation chains (RescaleCFG -> clip-skip
    # -> LoRA must keep the rescale); set BEFORE the clone is published
    # to the cache so a concurrent sampler can't observe the default
    copy_sampler_patches(base, clone)
    if cfg_rescale is not None:
        clone.cfg_rescale = cfg_rescale
    # a patched schedule (ModelSamplingDiscrete zsnr) must also survive
    # further derivations (LoRA/clip-skip after the patch)
    if schedule is not None:
        clone.schedule = schedule
    # new patch attrs join the riding set (see copy_sampler_patches)
    if extra_attrs:
        for k, v in extra_attrs.items():
            setattr(clone, k, v)
        clone._riding_attrs = frozenset(
            set(clone._riding_attrs) | set(extra_attrs))
    with _pipeline_lock:
        _derived_cache[key] = clone
        while len(_derived_cache) > _DERIVED_CACHE_CAP:
            _derived_cache.popitem(last=False)
    return clone


_embedding_cache: Dict[tuple, Optional[np.ndarray]] = {}


def load_textual_embedding(name: str, assets_dir: Optional[str],
                           width: int, tower_idx: int = 0,
                           ) -> Optional[np.ndarray]:
    """Textual-inversion vectors for ``embedding:name`` prompt refs:
    ``<assets_dir>/embeddings/<name>[.safetensors]``.  SDXL-style files
    carry per-tower ``clip_l``/``clip_g`` keys (tower 0 / 1); SD1.x
    A1111 exports carry a single ``emb_params`` tensor.  Returns
    [K, width] float32, or None (missing file / width mismatch) — the
    tokenizer drops the reference with a log, like ComfyUI's warning."""
    if not assets_dir:
        return None
    key = (assets_dir, name, width, tower_idx)
    if key in _embedding_cache:
        return _embedding_cache[key]
    base = os.path.join(assets_dir, "embeddings")
    path = None
    for cand in (name, name + ".safetensors"):
        p = os.path.join(base, cand.replace("\\", "/"))
        if os.path.isfile(p):
            path = p
            break
    result = None
    if path is not None and path.endswith(".safetensors"):
        from safetensors import safe_open
        with safe_open(path, framework="numpy") as f:
            keys = set(f.keys())
            per_tower = {0: "clip_l", 1: "clip_g"}
            if keys & {"clip_l", "clip_g"}:
                chosen = per_tower.get(tower_idx)
                chosen = chosen if chosen in keys else None
            elif "emb_params" in keys:
                chosen = "emb_params"
            else:
                chosen = next(iter(sorted(keys)), None)
            if chosen is not None:
                arr = np.asarray(f.get_tensor(chosen), np.float32)
                arr = arr.reshape(-1, arr.shape[-1])
                if arr.shape[-1] == width:
                    result = arr
                else:
                    log(f"textual inversion {name!r}: width "
                        f"{arr.shape[-1]} != tower width {width}; "
                        "dropping")
    _embedding_cache[key] = result
    return result


def load_controlnet(cn_name: str, models_dir: Optional[str] = None,
                    family_name: Optional[str] = None):
    """ControlNetLoader equivalent -> (module, params); virtual when no
    file exists (deterministic from the name, zero-convs start at zero so
    a fresh virtual ControlNet is an exact no-op on the UNet).

    When a file IS on disk the family comes from the checkpoint itself
    (cross-attention width), not from env/default — an SDXL workflow
    must not build a 768-context sd15 net just because the default says
    so (parity with the reference ecosystem's infer-from-file loaders)."""
    fam = FAMILIES[family_name or os.environ.get(FAMILY_ENV) or "sd15"]
    path = None
    sd = None
    if models_dir:
        cand = os.path.join(models_dir, cn_name.replace("\\", "/"))
        if os.path.exists(cand):
            path = cand
    if path is not None and family_name is None:
        # inferred family memoized per path: the repeat call must hit the
        # pipeline cache below without re-reading a multi-GB file
        with _pipeline_lock:
            cached_fam = _cn_family_cache.get(path)
        if cached_fam is not None:
            fam = FAMILIES[cached_fam]
        else:
            from comfyui_distributed_tpu.models.checkpoints import (
                controlnet_context_dim, load_state_dict)
            sd = load_state_dict(path)
            ctx_dim = controlnet_context_dim(sd)
            if ctx_dim is not None and ctx_dim != fam.unet.context_dim:
                for cand_fam in ("sd15", "sd21", "sdxl", "tiny"):
                    if FAMILIES[cand_fam].unet.context_dim == ctx_dim:
                        fam = FAMILIES[cand_fam]
                        break
            with _pipeline_lock:
                _cn_family_cache[path] = fam.name

    key = f"cn:{cn_name}:{fam.name}:{models_dir or ''}"
    with _pipeline_lock:
        if key in _pipeline_cache:
            return _pipeline_cache[key]

    from comfyui_distributed_tpu.models.controlnet import ControlNet
    module = ControlNet(fam.unet)
    if path is not None:
        from comfyui_distributed_tpu.models.checkpoints import (
            load_controlnet as load_cn_file)
        params = load_cn_file(path, fam.unet, state_dict=sd)
        log(f"loaded ControlNet {cn_name} ({fam.name}) from {path}")
    else:
        seed = _name_seed(cn_name)
        x = jnp.zeros((1, 8, 8, fam.latent_channels))
        ts = jnp.zeros((1,))
        ctx = jnp.zeros((1, 77, fam.unet.context_dim))
        hint = jnp.zeros((1, 64, 64, 3))
        params = _virtual_params(module, seed, x, ts, ctx, hint)
        # restore the untrained-ControlNet invariant _virtual_params'
        # random fill breaks: zero projections make a fresh net an exact
        # UNet no-op (the property real zero-init checkpoints have)
        from comfyui_distributed_tpu.models.controlnet import HINT_CHANNELS
        final_hint = f"hint_conv_{len(HINT_CHANNELS)}"
        for name in list(params):
            if name.startswith("zero_conv_") or name in ("mid_out",
                                                         final_hint):
                params[name] = jax.tree_util.tree_map(
                    lambda a: np.zeros_like(a), params[name])
        log(f"virtual ControlNet {cn_name!r} ({fam.name}): no file on "
            f"disk, deterministic init (seed {seed}, zero projections)")

    entry = (module, params)
    with _pipeline_lock:
        _pipeline_cache[key] = entry
    return entry


_clip_vision_cache: Dict[str, Any] = {}


def load_clip_vision(clip_name: str, models_dir: Optional[str] = None,
                     config_name: Optional[str] = None):
    """CLIPVisionLoader equivalent: ``<models_dir>/clip_vision/<name>``
    in the HF CLIPVisionModel safetensors layout; virtual-initializes
    when no file exists.  The config is inferred from the file's hidden
    width (ViT-H vs ViT-L), or forced by ``config_name``
    ('vit_h' | 'vit_l' | 'tiny')."""
    from comfyui_distributed_tpu.models import clip_vision as cv
    key = f"{clip_name}:{config_name or ''}:{models_dir or ''}"
    with _pipeline_lock:
        if key in _clip_vision_cache:
            return _clip_vision_cache[key]
    cfgs = {"vit_h": cv.VIT_H_CONFIG, "vit_l": cv.VIT_L_CONFIG,
            "tiny": cv.TINY_VISION_CONFIG}
    path = None
    if models_dir:
        for cand in (clip_name,
                     os.path.join("clip_vision", clip_name)):
            p = os.path.join(models_dir, cand.replace("\\", "/"))
            if os.path.isfile(p):
                path = p
                break
    if path is not None:
        from comfyui_distributed_tpu.models.checkpoints import (
            _LoadMapper, _run_clip_vision, load_state_dict)
        sd = load_state_dict(path)
        if config_name:
            cfg = cfgs[config_name]
        else:
            w = sd.get("vision_model.embeddings.class_embedding")
            width = int(w.shape[-1]) if w is not None else 1280
            cfg = cv.VIT_H_CONFIG if width >= 1280 else cv.VIT_L_CONFIG
        params = _run_clip_vision(_LoadMapper(sd, ""), cfg)
        log(f"loaded CLIP vision {clip_name} (width {cfg.width}) "
            f"from {path}")
    else:
        lowered = clip_name.lower()
        cfg = cfgs.get(config_name or "", None)
        if cfg is None:
            cfg = cv.TINY_VISION_CONFIG if ("tiny" in lowered
                                            or "test" in lowered) \
                else cv.VIT_H_CONFIG
        seed = _name_seed(clip_name)
        px = jnp.zeros((1, cfg.image_size, cfg.image_size, 3))
        params = _virtual_params(cv.CLIPVisionModel(cfg), seed, px)
        log(f"virtual CLIP vision {clip_name!r} (width {cfg.width}): "
            f"no file on disk, deterministic init (seed {seed})")
    tower = cv.CLIPVisionTower(name=clip_name, cfg=cfg, params=params)
    with _pipeline_lock:
        _clip_vision_cache[key] = tower
    return tower


def load_vae(vae_name: str, models_dir: Optional[str] = None,
             family_name: Optional[str] = None) -> DiffusionPipeline:
    """VAELoader equivalent: a standalone VAE usable wherever a pipeline's
    VAE output is (VAEDecode/VAEEncode/tiled).  Accepts both serialization
    forms real VAE files use — full-checkpoint style (``first_stage_model.
    encoder...``) and bare (``encoder...``, e.g. vae-ft-mse-840000) —
    and virtually initializes when no file exists."""
    # 'tiny' only — a broader 'test' substring would match real names
    # like 'latest' and map a real VAE onto tiny geometry
    default = "tiny" if "tiny" in vae_name.lower() else "sd15"
    fam = FAMILIES[family_name or os.environ.get(FAMILY_ENV) or default]
    key = f"vae:{vae_name}:{fam.name}:{models_dir or ''}"
    with _pipeline_lock:
        if key in _pipeline_cache:
            return _pipeline_cache[key]

    path = None
    if models_dir:
        cand = os.path.join(models_dir, vae_name.replace("\\", "/"))
        if os.path.exists(cand):
            path = cand
    if path is not None:
        from comfyui_distributed_tpu.models.checkpoints import (
            VAE_PREFIX, _LoadMapper, _run_vae, load_state_dict)
        sd = load_state_dict(path)
        prefix = VAE_PREFIX if any(k.startswith(VAE_PREFIX) for k in sd) \
            else ""
        vae_p = _run_vae(_LoadMapper(sd, prefix), fam.vae)
        log(f"loaded VAE {vae_name} ({fam.name}) from {path}")
    else:
        seed = _name_seed(vae_name)
        ds = fam.vae.downscale
        img = jnp.zeros((1, 8 * ds, 8 * ds, 3))
        vae_p = _virtual_params(vae_mod.VAE(fam.vae), seed, img)
        log(f"virtual VAE {vae_name!r} ({fam.name}): no file on disk, "
            f"deterministic init (seed {seed})")

    pipe = DiffusionPipeline(f"vae:{vae_name}", fam, {}, [{}], vae_p)
    with _pipeline_lock:
        _pipeline_cache[key] = pipe
    return pipe


# ComfyUI CLIPLoader/DualCLIPLoader "type" widget -> model family whose
# text-tower geometry the file(s) must match
CLIP_TYPE_FAMILIES = {
    "stable_diffusion": "sd15",
    "sd1": "sd15",
    "sd2": "sd21",
    "sdxl": "sdxl",
    "tiny": "tiny",    # test geometry (same convention as the other
                       # standalone loaders' tiny-name detection)
}


def load_clip(clip_names: List[str], models_dir: Optional[str] = None,
              family_name: Optional[str] = None) -> DiffusionPipeline:
    """CLIPLoader/DualCLIPLoader equivalent: standalone text tower(s)
    usable wherever a checkpoint's CLIP output is (CLIPTextEncode and
    friends).  Accepts each tower's in-checkpoint prefix (as CLIPSave
    writes), an HF-standalone ``text_model.`` prefix, or bare keys; one
    file per tower (DualCLIPLoader: [clip_l, clip_g] for sdxl); virtual
    init per missing file."""
    fam = FAMILIES[family_name or os.environ.get(FAMILY_ENV) or "sd15"]
    if len(clip_names) != len(fam.clips):
        raise ValueError(
            f"family {fam.name} has {len(fam.clips)} text tower(s), got "
            f"{len(clip_names)} file name(s) — use "
            f"{'DualCLIPLoader' if len(fam.clips) == 2 else 'CLIPLoader'}")
    key = f"clip:{':'.join(clip_names)}:{fam.name}:{models_dir or ''}"
    with _pipeline_lock:
        if key in _pipeline_cache:
            return _pipeline_cache[key]

    from comfyui_distributed_tpu.models.checkpoints import (
        _clip_prefixes, _clip_runner, _LoadMapper, load_state_dict)
    clip_ps = []
    for i, (name, ccfg) in enumerate(zip(clip_names, fam.clips)):
        path = None
        if models_dir:
            for sub in (name, os.path.join("clip", name),
                        os.path.join("text_encoders", name)):
                cand = os.path.join(models_dir, sub.replace("\\", "/"))
                if os.path.exists(cand):
                    path = cand
                    break
        if path is not None:
            sd = load_state_dict(path)
            in_ckpt = _clip_prefixes(fam)[i]
            prefix = next((p for p in (in_ckpt, "text_model.")
                           if any(k.startswith(p) for k in sd)), "")
            clip_ps.append(_clip_runner(ccfg)(_LoadMapper(sd, prefix),
                                              ccfg))
            log(f"loaded CLIP tower {i} from {path} (prefix {prefix!r})")
        else:
            seed = _name_seed(name) + i
            tok = jnp.zeros((1, ccfg.max_length), jnp.int32)
            clip_ps.append(_virtual_params(
                clip_mod.CLIPTextModel(ccfg), seed, tok,
                storage_dtype=_storage_dtype(fam)))
            log(f"virtual CLIP tower {name!r} ({fam.name}[{i}]): no file "
                f"on disk, deterministic init (seed {seed})")

    if _bf16_weights_enabled(fam):
        # same storage policy as load_pipeline: CLIP towers loaded here
        # must not diverge (dtype or HBM traffic) from the identical
        # towers arriving via CheckpointLoaderSimple
        clip_ps = [_cast_bf16(p) for p in clip_ps]
    pipe = DiffusionPipeline(f"clip:{':'.join(clip_names)}", fam, {},
                             clip_ps, {}, assets_dir=models_dir)
    with _pipeline_lock:
        _pipeline_cache[key] = pipe
    return pipe


def load_unet(unet_name: str, models_dir: Optional[str] = None,
              family_name: Optional[str] = None) -> DiffusionPipeline:
    """UNETLoader equivalent: a standalone diffusion model (family
    detected from the filename unless given).  Accepts full-checkpoint
    ``model.diffusion_model.`` keys or bare UNet keys; text/VAE towers
    virtually initialize so the result is a complete MODEL wire (swap
    them via CLIPLoader/VAELoader outputs downstream)."""
    fam_name = family_name or detect_family(unet_name)
    key = f"unet:{unet_name}:{fam_name}:{models_dir or ''}"
    with _pipeline_lock:
        if key in _pipeline_cache:
            return _pipeline_cache[key]
    fam = FAMILIES[fam_name]

    seed = _name_seed(unet_name)
    path = None
    if models_dir:
        for sub in (unet_name, os.path.join("unet", unet_name),
                    os.path.join("diffusion_models", unet_name)):
            cand = os.path.join(models_dir, sub.replace("\\", "/"))
            if os.path.exists(cand):
                path = cand
                break
    if path is not None:
        from comfyui_distributed_tpu.models.checkpoints import (
            UNET_PREFIX, _LoadMapper, _run_unet, load_state_dict)
        sd = load_state_dict(path)
        prefix = UNET_PREFIX if any(k.startswith(UNET_PREFIX)
                                    for k in sd) else ""
        unet_p = _run_unet(_LoadMapper(sd, prefix), fam.unet)
        log(f"loaded UNet {unet_name} ({fam.name}) from {path}")
    else:
        x = jnp.zeros((1, 8, 8, fam.unet.in_channels))
        unet_p = _virtual_params(
            unet_mod.UNet(fam.unet), seed, x, jnp.zeros((1,)),
            jnp.zeros((1, 77, fam.unet.context_dim)),
            storage_dtype=_storage_dtype(fam))
        log(f"virtual UNet {unet_name!r} ({fam.name}): no file on disk, "
            f"deterministic init (seed {seed})")

    clip_ps = []
    for i, ccfg in enumerate(fam.clips):
        tok = jnp.zeros((1, ccfg.max_length), jnp.int32)
        clip_ps.append(_virtual_params(
            clip_mod.CLIPTextModel(ccfg), seed + 1 + i, tok,
            storage_dtype=_storage_dtype(fam)))
    img = jnp.zeros((1, 8 * fam.vae.downscale, 8 * fam.vae.downscale, 3))
    vae_p = _virtual_params(vae_mod.VAE(fam.vae), seed + 100, img)
    if _bf16_weights_enabled(fam):
        unet_p = _cast_bf16(unet_p)
        clip_ps = [_cast_bf16(p) for p in clip_ps]
    pipe = DiffusionPipeline(f"unet:{unet_name}", fam, unet_p, clip_ps,
                             vae_p, prediction_type=fam.unet.prediction_type,
                             assets_dir=models_dir)
    pipe.cache_token = key
    with _pipeline_lock:
        _pipeline_cache[key] = pipe
    return pipe


# --- upscalers --------------------------------------------------------------

_upscaler_cache: Dict[str, Tuple[RRDBNet, Any]] = {}


def load_upscaler(model_name: str, models_dir: Optional[str] = None):
    """UpscaleModelLoader equivalent: RRDB net + params (virtual when the
    .pth is absent).  Returns (module, params, scale)."""
    with _pipeline_lock:
        if model_name in _upscaler_cache:
            return _upscaler_cache[model_name]
    lowered = model_name.lower()
    if "tiny" in lowered or os.environ.get(FAMILY_ENV) == "tiny":
        cfg = TINY_RRDB_CONFIG
    else:
        scale = 4
        for s in (8, 4, 2, 1):
            if f"{s}x" in lowered:
                scale = s
                break
        cfg = dataclasses.replace(ESRGAN_4X_CONFIG, scale=scale)
    net = RRDBNet(cfg)
    path = None
    if models_dir:
        cand = os.path.join(models_dir, model_name.replace("\\", "/"))
        if os.path.exists(cand):
            path = cand
    if path is not None:
        from comfyui_distributed_tpu.models.checkpoints import load_upscaler_checkpoint
        params = load_upscaler_checkpoint(path, cfg)
    else:
        params = _virtual_params(net, _name_seed(model_name),
                                 jnp.zeros((1, 16, 16, 3)))
        log(f"virtual upscaler {model_name!r} (scale {cfg.scale})")
    entry = (net, params, cfg.scale)
    with _pipeline_lock:
        _upscaler_cache[model_name] = entry
    return entry
