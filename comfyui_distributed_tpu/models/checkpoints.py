"""Checkpoint interop: torch SD/SDXL single-file weights <-> flax param trees.

The reference delegates checkpoint loading to ComfyUI's CheckpointLoaderSimple
(node 4 in ``/root/reference/workflows/distributed-txt2img.json``) and simply
requires the same files on every machine (``/root/reference/README.md:
189-193``).  Here the equivalent is a bidirectional converter for the
standard single-file SD checkpoint layout (safetensors or torch pickle):

- ``model.diffusion_model.*``            <-> :class:`..models.unet.UNet`
- ``first_stage_model.*``                <-> :class:`..models.vae.VAE`
- ``cond_stage_model.transformer.*``     <-> CLIP-L (SD1.x, HF layout)
- ``cond_stage_model.model.*``           <-> OpenCLIP ViT-H (SD2.x)
- ``conditioner.embedders.0.transformer.*`` <-> CLIP-L (SDXL)
- ``conditioner.embedders.1.model.*``    <-> OpenCLIP bigG (SDXL)

Conversions are pure layout transforms: conv kernels OIHW <-> HWIO, linear
weights transposed, norm ``weight`` <-> ``scale``, OpenCLIP's packed
``in_proj_weight`` split into q/k/v.  The same mapping tables drive both
directions (one ``_run_*`` walk per model, load/export mappers), so
round-tripping is exact by construction.  Weights load as fp32 numpy; dtype
policy (bf16 compute) is applied by the modules at apply time — EXCEPT
that ``registry.load_pipeline`` may then drop UNet/CLIP STORAGE to bf16
(``DTPU_BF16_WEIGHTS``, HBM bandwidth); an export after that is bf16, not
a bit-exact round-trip of an fp32/fp16 source (CheckpointSave warns).
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from comfyui_distributed_tpu.models.clip import CLIPConfig
from comfyui_distributed_tpu.models.unet import UNetConfig, mid_depth
from comfyui_distributed_tpu.models.vae import VAEConfig
from comfyui_distributed_tpu.utils.logging import debug_log, log

Params = Dict[str, Any]


# --- state-dict IO ----------------------------------------------------------

def load_state_dict(path: str) -> Dict[str, np.ndarray]:
    """Read a checkpoint file into {torch_key: fp32 numpy}."""
    if path.endswith(".safetensors"):
        from safetensors import safe_open
        out: Dict[str, np.ndarray] = {}
        with safe_open(path, framework="np") as f:
            for k in f.keys():
                out[k] = _to_f32_np(f.get_tensor(k))
        return out
    import torch
    sd = torch.load(path, map_location="cpu", weights_only=True)
    if isinstance(sd, dict) and "state_dict" in sd:
        sd = sd["state_dict"]
    return {k: _to_f32_np(v) for k, v in sd.items()}


def save_state_dict(sd: Dict[str, np.ndarray], path: str) -> None:
    from safetensors.numpy import save_file
    save_file({k: np.ascontiguousarray(v) for k, v in sd.items()}, path)


def _to_f32_np(t: Any) -> np.ndarray:
    try:
        import torch
        if isinstance(t, torch.Tensor):
            return t.detach().to(torch.float32).cpu().numpy()
    except ImportError:  # pragma: no cover
        pass
    arr = np.asarray(t)
    if arr.dtype == np.float16 or str(arr.dtype) == "bfloat16":
        arr = arr.astype(np.float32)
    return arr


# --- tensor layout transforms ----------------------------------------------

def t_conv(w: np.ndarray) -> np.ndarray:
    """torch conv OIHW -> flax HWIO."""
    return np.transpose(w, (2, 3, 1, 0))


def t_conv_inv(w: np.ndarray) -> np.ndarray:
    return np.transpose(w, (3, 2, 0, 1))


def t_lin(w: np.ndarray) -> np.ndarray:
    """torch linear [out, in] <-> flax kernel [in, out]."""
    return np.transpose(w)


def _set(tree: Params, path: str, value: np.ndarray) -> None:
    parts = path.split("/")
    node = tree
    for p in parts[:-1]:
        node = node.setdefault(p, {})
    node[parts[-1]] = value


def _get_path(tree: Params, path: str) -> Optional[np.ndarray]:
    node: Any = tree
    for p in path.split("/"):
        if not isinstance(node, dict) or p not in node:
            return None
        node = node[p]
    return np.asarray(node)


# --- mappers: one mapping walk, two directions ------------------------------

class _LoadMapper:
    """torch state dict -> flax tree."""

    def __init__(self, sd: Dict[str, np.ndarray], prefix: str,
                 consumed: Optional[set] = None):
        self.sd = sd
        self.prefix = prefix
        self.tree: Params = {}
        self.missing: List[str] = []
        # torch keys actually read — lets callers detect unexpected keys
        self.consumed = consumed if consumed is not None else set()

    def _get(self, key: str) -> Optional[np.ndarray]:
        full = self.prefix + key
        if full in self.sd:
            self.consumed.add(full)
            return self.sd[full]
        return None

    def _pair(self, tkey: str, fpath: str, wtrans, wname: str = "kernel",
              bias: bool = True, required: bool = True) -> None:
        w = self._get(tkey + ".weight")
        if w is None:
            if required:
                self.missing.append(self.prefix + tkey)
            return
        _set(self.tree, f"{fpath}/{wname}", wtrans(w))
        if bias:
            b = self._get(tkey + ".bias")
            if b is not None:
                _set(self.tree, fpath + "/bias", b)

    def conv(self, tkey, fpath):
        self._pair(tkey, fpath, t_conv)

    def conv_optional(self, tkey, fpath):
        self._pair(tkey, fpath, t_conv, required=False)

    def conv_as_dense(self, tkey, fpath, export_conv=False):
        # export_conv is export-side metadata; loading accepts both forms
        def tr(w):
            return t_lin(w[:, :, 0, 0] if w.ndim == 4 else w)
        self._pair(tkey, fpath, tr)

    def linear(self, tkey, fpath, bias=True):
        self._pair(tkey, fpath, t_lin, bias=bias)

    def norm(self, tkey, fpath):
        self._pair(tkey, fpath, lambda w: w, wname="scale")

    def raw(self, tkey, fpath, transform=None):
        w = self._get(tkey)
        if w is None:
            self.missing.append(self.prefix + tkey)
            return
        _set(self.tree, fpath, transform(w) if transform else w)

    def packed_qkv(self, tkey: str, fpath: str, width: int) -> None:
        """OpenCLIP ``attn.in_proj_weight`` [3W, W] -> q/k/v Dense."""
        w = self._get(tkey + ".in_proj_weight")
        b = self._get(tkey + ".in_proj_bias")
        if w is None:
            self.missing.append(self.prefix + tkey + ".in_proj_weight")
            return
        for j, name in enumerate(("q", "k", "v")):
            _set(self.tree, f"{fpath}/{name}/kernel",
                 t_lin(w[j * width:(j + 1) * width]))
            if b is not None:
                _set(self.tree, f"{fpath}/{name}/bias",
                     b[j * width:(j + 1) * width])

    def projection(self, tkey: str, fpath: str) -> None:
        """OpenCLIP text_projection: plain [W, P] param (x @ P) or nn.Linear."""
        if self._get(tkey + ".weight") is not None:
            self.linear(tkey, fpath, bias=False)
        else:
            self.raw(tkey, fpath + "/kernel")

    def finish(self, what: str) -> Params:
        if self.missing:
            raise KeyError(f"{what} checkpoint missing {len(self.missing)} "
                           f"keys, first: {self.missing[:5]}")
        return self.tree


class _ExportMapper:
    """flax tree -> torch state dict (inverse transforms, same walk)."""

    def __init__(self, tree: Params, prefix: str):
        self.tree = tree
        self.prefix = prefix
        self.sd: Dict[str, np.ndarray] = {}
        self.missing: List[str] = []

    def _pair(self, tkey, fpath, wtrans, wname="kernel", bias=True,
              required=True):
        w = _get_path(self.tree, f"{fpath}/{wname}")
        if w is None:
            if required:
                self.missing.append(fpath)
            return
        self.sd[self.prefix + tkey + ".weight"] = wtrans(w)
        if bias:
            b = _get_path(self.tree, fpath + "/bias")
            if b is not None:
                self.sd[self.prefix + tkey + ".bias"] = b

    def conv(self, tkey, fpath):
        self._pair(tkey, fpath, t_conv_inv)

    def conv_optional(self, tkey, fpath):
        self._pair(tkey, fpath, t_conv_inv, required=False)

    def conv_as_dense(self, tkey, fpath, export_conv=False):
        """Dense kernel [in, out] -> torch linear [out, in], or — when the
        canonical torch layout is a 1x1 conv (VAE attention always, SD1.x
        transformer proj) — [out, in, 1, 1] so strict-shape torch loaders
        accept the export."""
        if export_conv:
            self._pair(tkey, fpath, lambda w: t_lin(w)[:, :, None, None])
        else:
            self._pair(tkey, fpath, t_lin)

    def linear(self, tkey, fpath, bias=True):
        self._pair(tkey, fpath, t_lin, bias=bias)

    def norm(self, tkey, fpath):
        self._pair(tkey, fpath, lambda w: w, wname="scale")

    def raw(self, tkey, fpath, transform=None):
        w = _get_path(self.tree, fpath)
        if w is None:
            self.missing.append(fpath)
            return
        self.sd[self.prefix + tkey] = transform(w) if transform else w

    def packed_qkv(self, tkey, fpath, width):
        ws, bs = [], []
        for name in ("q", "k", "v"):
            w = _get_path(self.tree, f"{fpath}/{name}/kernel")
            if w is None:
                self.missing.append(f"{fpath}/{name}")
                return
            ws.append(t_lin(w))
            b = _get_path(self.tree, f"{fpath}/{name}/bias")
            if b is not None:
                bs.append(b)
        self.sd[self.prefix + tkey + ".in_proj_weight"] = np.concatenate(ws, 0)
        if len(bs) == 3:
            self.sd[self.prefix + tkey + ".in_proj_bias"] = np.concatenate(bs, 0)

    def projection(self, tkey, fpath):
        self.raw(tkey, fpath + "/kernel")

    def finish(self, what: str) -> Dict[str, np.ndarray]:
        if self.missing:
            raise KeyError(f"{what} export missing {len(self.missing)} "
                           f"params, first: {self.missing[:5]}")
        return self.sd


def _groupnorm(m, tkey: str, fpath: str) -> None:
    # GroupNorm32 wraps an anonymous nn.GroupNorm
    m.norm(tkey, fpath + "/GroupNorm_0")


# --- UNet walk ---------------------------------------------------------------

def _map_resblock(m, tkey: str, fpath: str) -> None:
    _groupnorm(m, f"{tkey}.in_layers.0", f"{fpath}/in_norm")
    m.conv(f"{tkey}.in_layers.2", f"{fpath}/in_conv")
    m.linear(f"{tkey}.emb_layers.1", f"{fpath}/emb_proj")
    _groupnorm(m, f"{tkey}.out_layers.0", f"{fpath}/out_norm")
    m.conv(f"{tkey}.out_layers.3", f"{fpath}/out_conv")
    m.conv_optional(f"{tkey}.skip_connection", f"{fpath}/skip")


def _map_spatial_transformer(m, tkey: str, fpath: str, depth: int,
                             linear_proj: bool = False) -> None:
    _groupnorm(m, f"{tkey}.norm", f"{fpath}/norm")
    m.conv_as_dense(f"{tkey}.proj_in", f"{fpath}/proj_in",
                    export_conv=not linear_proj)
    for j in range(depth):
        b = f"{tkey}.transformer_blocks.{j}"
        fb = f"{fpath}/blocks_{j}"
        for attn in ("attn1", "attn2"):
            m.linear(f"{b}.{attn}.to_q", f"{fb}/{attn}/to_q", bias=False)
            m.linear(f"{b}.{attn}.to_k", f"{fb}/{attn}/to_k", bias=False)
            m.linear(f"{b}.{attn}.to_v", f"{fb}/{attn}/to_v", bias=False)
            m.linear(f"{b}.{attn}.to_out.0", f"{fb}/{attn}/to_out")
        m.norm(f"{b}.norm1", f"{fb}/norm1")
        m.norm(f"{b}.norm2", f"{fb}/norm2")
        m.norm(f"{b}.norm3", f"{fb}/norm3")
        m.linear(f"{b}.ff.net.0.proj", f"{fb}/ff/geglu/proj")
        m.linear(f"{b}.ff.net.2", f"{fb}/ff/out")
    m.conv_as_dense(f"{tkey}.proj_out", f"{fpath}/proj_out",
                    export_conv=not linear_proj)


def _run_unet(m, cfg: UNetConfig):
    """Walk the LDM UNet layout (torch ``input_blocks.N`` enumeration) against
    this framework's level/index names (``models/unet.py``)."""
    m.linear("time_embed.0", "time_fc1")
    m.linear("time_embed.2", "time_fc2")
    if cfg.adm_in_channels is not None:
        m.linear("label_emb.0.0", "label_fc1")
        m.linear("label_emb.0.2", "label_fc2")
    m.conv("input_blocks.0.0", "conv_in")

    L = cfg.num_levels
    idx = 1
    for level in range(L):
        for i in range(cfg.num_res_blocks):
            _map_resblock(m, f"input_blocks.{idx}.0", f"down_{level}_res_{i}")
            if cfg.transformer_depth[level] > 0:
                _map_spatial_transformer(
                    m, f"input_blocks.{idx}.1", f"down_{level}_attn_{i}",
                    cfg.transformer_depth[level],
                    linear_proj=cfg.use_linear_in_transformer)
            idx += 1
        if level != L - 1:
            m.conv(f"input_blocks.{idx}.0.op", f"down_{level}_ds/conv")
            idx += 1

    _map_resblock(m, "middle_block.0", "mid_res_0")
    _map_spatial_transformer(m, "middle_block.1", "mid_attn",
                             mid_depth(cfg),
                             linear_proj=cfg.use_linear_in_transformer)
    _map_resblock(m, "middle_block.2", "mid_res_1")

    idx = 0
    for level in reversed(range(L)):
        for i in range(cfg.num_res_blocks + 1):
            _map_resblock(m, f"output_blocks.{idx}.0", f"up_{level}_res_{i}")
            sub = 1
            if cfg.transformer_depth[level] > 0:
                _map_spatial_transformer(
                    m, f"output_blocks.{idx}.{sub}", f"up_{level}_attn_{i}",
                    cfg.transformer_depth[level],
                    linear_proj=cfg.use_linear_in_transformer)
                sub += 1
            if level != 0 and i == cfg.num_res_blocks:
                m.conv(f"output_blocks.{idx}.{sub}.conv", f"up_{level}_us/conv")
            idx += 1

    _groupnorm(m, "out.0", "out_norm")
    m.conv("out.2", "conv_out")
    return m.finish("UNet")


def _run_controlnet(m, cfg: UNetConfig):
    """Walk the torch ControlNet layout (``control_model.*``): the UNet
    encoder enumeration plus input_hint_block / zero_convs /
    middle_block_out (models/controlnet.py mirrors the flax names)."""
    from comfyui_distributed_tpu.models.controlnet import HINT_CHANNELS
    m.linear("time_embed.0", "time_fc1")
    m.linear("time_embed.2", "time_fc2")
    if cfg.adm_in_channels is not None:
        m.linear("label_emb.0.0", "label_fc1")
        m.linear("label_emb.0.2", "label_fc2")
    m.conv("input_blocks.0.0", "conv_in")

    # hint encoder: torch Sequential with SiLU between convs — conv
    # modules sit at even indices 0,2,4,...,14
    for i in range(len(HINT_CHANNELS) + 1):
        m.conv(f"input_hint_block.{2 * i}", f"hint_conv_{i}")

    L = cfg.num_levels
    idx, zi = 1, 1
    m.conv("zero_convs.0.0", "zero_conv_0")
    for level in range(L):
        for i in range(cfg.num_res_blocks):
            _map_resblock(m, f"input_blocks.{idx}.0", f"down_{level}_res_{i}")
            if cfg.transformer_depth[level] > 0:
                _map_spatial_transformer(
                    m, f"input_blocks.{idx}.1", f"down_{level}_attn_{i}",
                    cfg.transformer_depth[level],
                    linear_proj=cfg.use_linear_in_transformer)
            m.conv(f"zero_convs.{zi}.0", f"zero_conv_{zi}")
            idx += 1
            zi += 1
        if level != L - 1:
            m.conv(f"input_blocks.{idx}.0.op", f"down_{level}_ds/conv")
            m.conv(f"zero_convs.{zi}.0", f"zero_conv_{zi}")
            idx += 1
            zi += 1

    _map_resblock(m, "middle_block.0", "mid_res_0")
    _map_spatial_transformer(m, "middle_block.1", "mid_attn",
                             mid_depth(cfg),
                             linear_proj=cfg.use_linear_in_transformer)
    _map_resblock(m, "middle_block.2", "mid_res_1")
    m.conv("middle_block_out.0", "mid_out")
    return m.finish("ControlNet")


CONTROLNET_PREFIX = "control_model."


def load_controlnet(path: str, cfg: UNetConfig, state_dict=None):
    """ControlNet ``.pth``/``.safetensors`` -> flax params."""
    sd = state_dict if state_dict is not None else load_state_dict(path)
    prefix = CONTROLNET_PREFIX if any(
        k.startswith(CONTROLNET_PREFIX) for k in sd) else ""
    return _run_controlnet(_LoadMapper(sd, prefix), cfg)


def controlnet_context_dim(sd) -> Optional[int]:
    """Cross-attention width of a ControlNet state dict — the one
    dimension that discriminates the SD families (768/1024/2048), used to
    infer the right UNet config from the file itself (the reference
    ecosystem infers ControlNet configs from the checkpoint, not from
    whatever model the user happens to have loaded)."""
    for k, v in sd.items():
        if k.endswith("attn2.to_k.weight"):
            return int(v.shape[-1])
    return None


def export_controlnet(params, cfg: UNetConfig):
    return _run_controlnet(_ExportMapper(params, CONTROLNET_PREFIX), cfg)


# --- VAE walk ----------------------------------------------------------------

def _map_vae_resblock(m, tkey: str, fpath: str) -> None:
    _groupnorm(m, f"{tkey}.norm1", f"{fpath}/norm1")
    m.conv(f"{tkey}.conv1", f"{fpath}/conv1")
    _groupnorm(m, f"{tkey}.norm2", f"{fpath}/norm2")
    m.conv(f"{tkey}.conv2", f"{fpath}/conv2")
    m.conv_optional(f"{tkey}.nin_shortcut", f"{fpath}/skip")


def _map_vae_attn(m, tkey: str, fpath: str) -> None:
    _groupnorm(m, f"{tkey}.norm", f"{fpath}/norm")
    # torch stores q/k/v/proj_out as 1x1 convs; our block uses Dense.
    # Exports MUST be 4D [O, I, 1, 1] — strict torch VAE loaders
    # shape-check and drop 2D tensors here.
    for name in ("q", "k", "v", "proj_out"):
        m.conv_as_dense(f"{tkey}.{name}", f"{fpath}/{name}",
                        export_conv=True)


def _run_vae(m, cfg: VAEConfig):
    L = len(cfg.channel_mult)
    m.conv("encoder.conv_in", "encoder/conv_in")
    for level in range(L):
        for i in range(cfg.num_res_blocks):
            _map_vae_resblock(m, f"encoder.down.{level}.block.{i}",
                              f"encoder/down_{level}_res_{i}")
        if level != L - 1:
            m.conv(f"encoder.down.{level}.downsample.conv",
                   f"encoder/down_{level}_ds")
    _map_vae_resblock(m, "encoder.mid.block_1", "encoder/mid_res_0")
    _map_vae_attn(m, "encoder.mid.attn_1", "encoder/mid_attn")
    _map_vae_resblock(m, "encoder.mid.block_2", "encoder/mid_res_1")
    _groupnorm(m, "encoder.norm_out", "encoder/out_norm")
    m.conv("encoder.conv_out", "encoder/conv_out")

    m.conv("decoder.conv_in", "decoder/conv_in")
    _map_vae_resblock(m, "decoder.mid.block_1", "decoder/mid_res_0")
    _map_vae_attn(m, "decoder.mid.attn_1", "decoder/mid_attn")
    _map_vae_resblock(m, "decoder.mid.block_2", "decoder/mid_res_1")
    # torch decoder.up is indexed by resolution level (up.0 = full res)
    for level in range(L):
        for i in range(cfg.num_res_blocks + 1):
            _map_vae_resblock(m, f"decoder.up.{level}.block.{i}",
                              f"decoder/up_{level}_res_{i}")
        if level != 0:
            m.conv(f"decoder.up.{level}.upsample.conv",
                   f"decoder/up_{level}_us")
    _groupnorm(m, "decoder.norm_out", "decoder/out_norm")
    m.conv("decoder.conv_out", "decoder/conv_out")

    m.conv("quant_conv", "quant_conv")
    m.conv("post_quant_conv", "post_quant_conv")
    return m.finish("VAE")


# --- CLIP walks --------------------------------------------------------------

def _run_clip_hf(m, cfg: CLIPConfig):
    """HF CLIPTextModel layout (SD1.x ``cond_stage_model.transformer`` and
    SDXL's first embedder)."""
    m.raw("embeddings.token_embedding.weight", "token_embedding/embedding")
    m.raw("embeddings.position_embedding.weight", "position_embedding")
    for i in range(cfg.layers):
        t, f = f"encoder.layers.{i}", f"layers_{i}"
        m.norm(f"{t}.layer_norm1", f"{f}/ln1")
        m.linear(f"{t}.self_attn.q_proj", f"{f}/q")
        m.linear(f"{t}.self_attn.k_proj", f"{f}/k")
        m.linear(f"{t}.self_attn.v_proj", f"{f}/v")
        m.linear(f"{t}.self_attn.out_proj", f"{f}/proj")
        m.norm(f"{t}.layer_norm2", f"{f}/ln2")
        m.linear(f"{t}.mlp.fc1", f"{f}/fc1")
        m.linear(f"{t}.mlp.fc2", f"{f}/fc2")
    m.norm("final_layer_norm", "ln_final")
    return m.finish("CLIP")


def _run_clip_vision(m, cfg):
    """HF CLIPVisionModel layout (the ``clip_vision/*.safetensors``
    exports the reference ecosystem's CLIPVisionLoader consumes).
    Note HF's actual key spelling ``pre_layrnorm``."""
    m.raw("vision_model.embeddings.class_embedding", "class_embedding")
    m.raw("vision_model.embeddings.position_embedding.weight",
          "position_embedding")
    m.conv("vision_model.embeddings.patch_embedding", "patch_embed")
    m.norm("vision_model.pre_layrnorm", "pre_ln")
    for i in range(cfg.layers):
        t = f"vision_model.encoder.layers.{i}"
        f = f"layers_{i}"
        m.norm(f"{t}.layer_norm1", f"{f}/ln1")
        m.linear(f"{t}.self_attn.q_proj", f"{f}/q")
        m.linear(f"{t}.self_attn.k_proj", f"{f}/k")
        m.linear(f"{t}.self_attn.v_proj", f"{f}/v")
        m.linear(f"{t}.self_attn.out_proj", f"{f}/proj")
        m.norm(f"{t}.layer_norm2", f"{f}/ln2")
        m.linear(f"{t}.mlp.fc1", f"{f}/fc1")
        m.linear(f"{t}.mlp.fc2", f"{f}/fc2")
    m.norm("vision_model.post_layernorm", "post_ln")
    m.linear("visual_projection", "visual_projection", bias=False)
    return m.finish("CLIPVision")


def _run_openclip(m, cfg: CLIPConfig):
    """OpenCLIP text-tower layout (SDXL's bigG embedder)."""
    m.raw("token_embedding.weight", "token_embedding/embedding")
    m.raw("positional_embedding", "position_embedding")
    for i in range(cfg.layers):
        t, f = f"transformer.resblocks.{i}", f"layers_{i}"
        m.norm(f"{t}.ln_1", f"{f}/ln1")
        m.packed_qkv(f"{t}.attn", f, cfg.width)
        m.linear(f"{t}.attn.out_proj", f"{f}/proj")
        m.norm(f"{t}.ln_2", f"{f}/ln2")
        m.linear(f"{t}.mlp.c_fc", f"{f}/fc1")
        m.linear(f"{t}.mlp.c_proj", f"{f}/fc2")
    m.norm("ln_final", "ln_final")
    if cfg.projection_dim is not None:
        m.projection("text_projection", "text_projection")
    return m.finish("OpenCLIP")


# --- top level ---------------------------------------------------------------

UNET_PREFIX = "model.diffusion_model."
VAE_PREFIX = "first_stage_model."
CLIP_PREFIX_SD15 = "cond_stage_model.transformer.text_model."
# SD2.x: FrozenOpenCLIPEmbedder stores the OpenCLIP text tower directly
CLIP_PREFIX_SD2 = "cond_stage_model.model."
CLIP_PREFIXES_SDXL = ("conditioner.embedders.0.transformer.text_model.",
                      "conditioner.embedders.1.model.")


def _clip_prefixes(family) -> List[str]:
    declared = getattr(family, "clip_prefixes", None)
    if declared is not None:   # layout fact lives ON the family (e.g.
        return list(declared)  # sdxl_refiner's SGM embedder-0 bigG)
    if len(family.clips) == 1:
        layout = getattr(family.clips[0], "layout", "hf")
        return [CLIP_PREFIX_SD2 if layout == "openclip" else CLIP_PREFIX_SD15]
    return list(CLIP_PREFIXES_SDXL)


def _clip_runner(ccfg):
    return _run_openclip if getattr(ccfg, "layout", "hf") == "openclip" \
        else _run_clip_hf


def convert_state_dict(sd: Dict[str, np.ndarray], family,
                       consumed: Optional[set] = None,
                       include_vae: bool = True,
                       ) -> Tuple[Params, List[Params], Optional[Params]]:
    unet = _run_unet(_LoadMapper(sd, UNET_PREFIX, consumed), family.unet)
    vae = _run_vae(_LoadMapper(sd, VAE_PREFIX, consumed), family.vae) \
        if include_vae else None
    clips: List[Params] = []
    for ccfg, prefix in zip(family.clips, _clip_prefixes(family)):
        clips.append(_clip_runner(ccfg)(_LoadMapper(sd, prefix, consumed),
                                        ccfg))
    return unet, clips, vae


# non-parameter keys real checkpoints carry that no model weight maps to:
# diffusion schedule buffers, EMA copies, CLIP position ids / logit scale
EXPECTED_NONPARAM_KEYS = (
    "betas", "alphas_cumprod", "alphas_cumprod_prev",
    "sqrt_alphas_cumprod", "sqrt_one_minus_alphas_cumprod",
    "log_one_minus_alphas_cumprod", "sqrt_recip_alphas_cumprod",
    "sqrt_recipm1_alphas_cumprod", "posterior_variance",
    "posterior_log_variance_clipped", "posterior_mean_coef1",
    "posterior_mean_coef2", "logvar",
    "model_ema.",
    "cond_stage_model.transformer.text_model.embeddings.position_ids",
    "conditioner.embedders.0.transformer.text_model.embeddings.position_ids",
    "conditioner.embedders.1.model.logit_scale",
    # refiner: the bigG tower is embedder 0
    "conditioner.embedders.0.model.logit_scale",
    "cond_stage_model.logit_scale",
    # SD2.x OpenCLIP tower buffers (FrozenOpenCLIPEmbedder keeps the
    # causal mask and logit scale in the state dict)
    "cond_stage_model.model.attn_mask",
    "cond_stage_model.model.logit_scale",
)


def unconsumed_keys(sd: Dict[str, np.ndarray], family) -> List[str]:
    """Checkpoint keys that map onto no model parameter (after dropping the
    known non-parameter buffers) — a loader-coverage check: non-empty means
    either an unexpected checkpoint layout or a mapping gap."""
    consumed: set = set()
    convert_state_dict(sd, family, consumed=consumed)
    leftover = []
    for k in sd:
        if k in consumed:
            continue
        if any(k == e or k.startswith(e) for e in EXPECTED_NONPARAM_KEYS):
            continue
        leftover.append(k)
    return sorted(leftover)


def load_checkpoint(path: str, family) -> Tuple[Params, List[Params], Params]:
    """Load a single-file SD checkpoint into (unet, [clips], vae) param trees
    matching ``registry.ModelFamily`` module layouts."""
    sd = load_state_dict(path)
    debug_log(f"checkpoint {os.path.basename(path)}: {len(sd)} tensors")
    unet, clips, vae = convert_state_dict(sd, family)
    log(f"converted checkpoint {os.path.basename(path)} "
        f"({family.name}): unet/vae/{len(clips)} clip towers")
    return unet, clips, vae


def export_state_dict(unet: Params, clips: List[Params], vae: Params,
                      family, include_vae: bool = True
                      ) -> Dict[str, np.ndarray]:
    """flax param trees -> torch-layout state dict (interop back to the
    reference's ecosystem: a checkpoint exported here loads in ComfyUI).
    ``include_vae=False`` skips the VAE walk (LoRA patching never touches
    it — no point copying it through torch layout)."""
    sd: Dict[str, np.ndarray] = {}
    sd.update(_run_unet(_ExportMapper(unet, UNET_PREFIX), family.unet))
    if include_vae:
        sd.update(_run_vae(_ExportMapper(vae, VAE_PREFIX), family.vae))
    for ccfg, tree, prefix in zip(family.clips, clips, _clip_prefixes(family)):
        sd.update(_clip_runner(ccfg)(_ExportMapper(tree, prefix), ccfg))
    return sd


def save_checkpoint(path: str, unet: Params, clips: List[Params], vae: Params,
                    family) -> None:
    save_state_dict(export_state_dict(unet, clips, vae, family), path)


# --- ESRGAN/RRDB upscalers ---------------------------------------------------
#
# The ``4x*.pth`` files the reference's UpscaleModelLoader consumes
# (``workflows/distributed-upscale.json`` node 14) ship in three naming
# schemes; all normalize onto models/upscalers.py's layout
# (conv_first / rrdb_{i}/db{j}/conv{k} / trunk_conv / up_{i} / hr_conv /
# conv_last).

def _rrdb_key_norm(sd: Dict[str, np.ndarray]) -> Dict[str, str]:
    """Map torch keys -> canonical Real-ESRGAN-style names."""
    if any(k.startswith("model.1.sub.") for k in sd):  # old ESRGAN arch
        out = {}
        nb = max(int(k.split(".")[3]) for k in sd
                 if k.startswith("model.1.sub.") and k.split(".")[3].isdigit())
        # The tail layout depends on scale (one upconv per 2x plus HRconv
        # and conv_last, interleaved with param-free Upsample/LeakyReLU):
        # 4x = model.{3,6,8,10}, 2x = model.{3,5,7}, 1x = model.{2,4}.
        # Detect the parameterized indices instead of hardcoding 4x.
        tail = sorted({int(p[1]) for p in (k.split(".") for k in sd)
                       if p[0] == "model" and p[1].isdigit()
                       and int(p[1]) >= 2})
        names = ([f"upconv{i + 1}" for i in range(len(tail) - 2)]
                 + ["HRconv", "conv_last"])
        tail_map = dict(zip(tail, names))
        for k in sd:
            parts = k.split(".")
            if k.startswith("model.0."):
                out[k] = f"conv_first.{parts[-1]}"
            elif k.startswith(f"model.1.sub.{nb}."):
                out[k] = f"trunk_conv.{parts[-1]}"
            elif k.startswith("model.1.sub."):
                i, rdb, conv = parts[3], parts[4], parts[5]
                out[k] = f"body.{i}.{rdb}.{conv}.{parts[-1]}"
            elif parts[0] == "model" and parts[1].isdigit() \
                    and int(parts[1]) in tail_map:
                out[k] = f"{tail_map[int(parts[1])]}.{parts[-1]}"
        return out
    # new-arch (xinntao ESRGAN: RRDB_trunk) and Real-ESRGAN (body/conv_body)
    out = {}
    for k in sd:
        nk = (k.replace("RRDB_trunk.", "body.")
               .replace("conv_body.", "trunk_conv.")
               .replace("conv_up1.", "upconv1.")
               .replace("conv_up2.", "upconv2.")
               .replace("conv_hr.", "HRconv."))
        out[k] = nk
    return out


def load_upscaler_checkpoint(path: str, cfg) -> Params:
    """ESRGAN/RRDB ``.pth``/``.safetensors`` -> RRDBNet flax params."""
    sd = load_state_dict(path)
    norm = _rrdb_key_norm(sd)
    canon = {norm[k]: v for k, v in sd.items() if k in norm}
    tree: Params = {}

    def conv(tkeys, fpath: str) -> None:
        """Map the first present torch-key variant onto ``fpath``."""
        tkeys = (tkeys,) if isinstance(tkeys, str) else tkeys
        for tkey in tkeys:
            w = canon.get(tkey + ".weight")
            if w is not None:
                _set(tree, fpath + "/kernel", t_conv(w))
                b = canon.get(tkey + ".bias")
                if b is not None:
                    _set(tree, fpath + "/bias", b)
                return
        raise KeyError(f"upscaler checkpoint missing any of {tkeys} "
                       f"(have e.g. {sorted(canon)[:3]})")

    conv("conv_first", "conv_first")
    for i in range(cfg.num_blocks):
        for j in range(3):
            for k in range(5):
                # Real-ESRGAN uses rdb1, xinntao/old-arch use RDB1
                conv((f"body.{i}.rdb{j + 1}.conv{k + 1}",
                      f"body.{i}.RDB{j + 1}.conv{k + 1}"),
                     f"rrdb_{i}/db{j}/conv{k}")
    conv("trunk_conv", "trunk_conv")
    n_up = {1: 0, 2: 1, 4: 2, 8: 3}[cfg.scale]
    for i in range(n_up):
        conv(f"upconv{i + 1}", f"up_{i}")
    conv("HRconv", "hr_conv")
    conv("conv_last", "conv_last")
    log(f"loaded upscaler checkpoint {os.path.basename(path)} "
        f"(scale {cfg.scale}, {cfg.num_blocks} blocks)")
    return tree


# --- the looped language model (models/looplm.py) ----------------------------

_LOOPLM_LAYER_KEYS = {
    "input_layernorm": "input_layernorm.weight",
    "input_layernorm_2": "input_layernorm_2.weight",
    "post_attention_layernorm": "post_attention_layernorm.weight",
    "post_attention_layernorm_2": "post_attention_layernorm_2.weight",
    "q_proj": "self_attn.q_proj.weight", "k_proj": "self_attn.k_proj.weight",
    "v_proj": "self_attn.v_proj.weight", "o_proj": "self_attn.o_proj.weight",
    "gate_proj": "mlp.gate_proj.weight", "up_proj": "mlp.up_proj.weight",
    "down_proj": "mlp.down_proj.weight",
}


def load_looplm_checkpoint(path: str, cfg) -> Params:
    """The model's Hugging Face state dict (``model.layers.<l>...``,
    linear weights ``[out, in]``) as the tree ``models/looplm.py`` serves:
    kernels ``[in, out]``, the layers' leaves stacked on a leading axis,
    everything in the model's dtype."""
    import jax
    import jax.numpy as jnp
    sd = load_state_dict(path)

    def leaf(key: str):
        w = sd[key]
        return t_lin(w) if w.ndim == 2 and "embed_tokens" not in key else w

    layers = {
        name: np.stack([leaf(f"model.layers.{l}.{key}")
                        for l in range(cfg.num_hidden_layers)])
        for name, key in _LOOPLM_LAYER_KEYS.items()}
    tree = {"embed_tokens": leaf("model.embed_tokens.weight"),
            "layers": layers, "norm": leaf("model.norm.weight"),
            "early_exit_gate": {
                "kernel": sd["model.early_exit_gate.weight"].reshape(-1),
                "bias": sd["model.early_exit_gate.bias"].reshape(())},
            "lm_head": leaf("lm_head.weight")}
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, cfg.dtype), tree)


# --- the state-space / attention hybrid (models/ssm_hybrid.py) -----------------

_GRANITE_BLOCK_KEYS = {
    "input_layernorm": "input_layernorm.weight",
    "post_attention_layernorm": "post_attention_layernorm.weight",
    "input_linear": "shared_mlp.input_linear.weight",
    "output_linear": "shared_mlp.output_linear.weight",
}
_GRANITE_ATTENTION_KEYS = {
    "q_proj": "self_attn.q_proj.weight", "k_proj": "self_attn.k_proj.weight",
    "v_proj": "self_attn.v_proj.weight", "o_proj": "self_attn.o_proj.weight",
}
_GRANITE_MAMBA_KEYS = {
    "conv1d_bias": "mamba.conv1d.bias", "dt_bias": "mamba.dt_bias",
    "A_log": "mamba.A_log", "D": "mamba.D", "norm": "mamba.norm.weight",
    "out_proj": "mamba.out_proj.weight",
}


def load_granite_hybrid_checkpoint(path: str, cfg) -> Params:
    """The model's Hugging Face state dict (``granitemoehybrid``:
    ``model.layers.<l>.mamba...`` / ``.self_attn...`` /
    ``.shared_mlp...``, linear weights ``[out, in]``) as the tree
    ``models/ssm_hybrid.py`` serves: kernels ``[in, out]``, the blocks of
    each kind stacked on a leading axis in the order ``layer_types``
    gives them, ``mamba.in_proj`` split behind its z | xBC columns into
    ``in_proj_zx`` and ``in_proj_dt``, the convolution's ``[channels, 1,
    taps]`` as ``[taps, channels]``, no ``lm_head`` (tied), everything in
    the model's dtype."""
    import jax
    import jax.numpy as jnp
    sd = load_state_dict(path)

    def leaf(key: str):
        w = sd[key]
        return t_lin(w) if w.ndim == 2 and "embed_tokens" not in key else w

    def stacked(kind: str, keys: Dict[str, str]):
        at = [l for l, k in enumerate(cfg.layer_types) if k == kind]
        return {name: np.stack([leaf(f"model.layers.{l}.{key}") for l in at])
                for name, key in keys.items()}, at

    mamba, at = stacked("mamba", {**_GRANITE_BLOCK_KEYS,
                                  **_GRANITE_MAMBA_KEYS})
    in_proj = np.stack([leaf(f"model.layers.{l}.mamba.in_proj.weight")
                        for l in at])
    split = cfg.d_inner + cfg.conv_dim
    mamba["in_proj_zx"], mamba["in_proj_dt"] = \
        in_proj[..., :split], in_proj[..., split:]
    mamba["conv1d_weight"] = np.stack([
        sd[f"model.layers.{l}.mamba.conv1d.weight"][:, 0, :].T for l in at])
    attention, _ = stacked("attention", {**_GRANITE_BLOCK_KEYS,
                                         **_GRANITE_ATTENTION_KEYS})
    tree = {"embed_tokens": leaf("model.embed_tokens.weight"),
            "mamba_layers": mamba, "attention_layers": attention,
            "norm": leaf("model.norm.weight")}
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, cfg.dtype), tree)
