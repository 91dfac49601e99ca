"""A latent-attention decoder with routed experts (openPangu-Ultra-MoE,
``model_type`` ``pangu_ultra_moe``: a DeepSeek-V3-shaped stack with
sandwich norms), served as ONE chip's share of a deployment.

    x = E[ids]
    for l in 0..L-1:                                     # every block
      h = x + N2_l(Attn_l(N1_l(x)))                      # sandwich norms
      x = h + N4_l(MLP_l(N3_l(h)))
    logits = W_head N(x)

    Attn (MLA), n = N1(x):
      c_q  = RMSNorm(n W_qa)                             # rank 1536
      q    = c_q W_qb            -> H x (d_nope + d_rope)
      [c_kv, k_r] = n W_kva      -> 512 + 64;  c_kv = RMSNorm(c_kv)
      [k_nope, v] = c_kv W_kvb   -> H x (d_nope + d_v)
      q_r, k_r rotated (k_r is ONE key, shared by every head)
      s    = (q_nope . k_nope + q_r . k_r) / sqrt(d_nope + d_rope)
      out  = W_o concat_h(softmax(s; causal) v)

    MLP of the leading dense blocks:  W_down (silu(W_gate n) * W_up n)
    MLP of the expert blocks, n = N3(h):
      s = sigmoid(n W_g) over ALL routed experts;  top-k;
      w = s_topk / sum(s_topk) * routed_scaling_factor
      y = Shared(n) + sum over the chosen experts e HELD HERE of w_e Expert_e(n)

**The cache holds the latent**: ``c_kv`` and the rotated ``k_r``, 576
values a position a layer, never the 128 heads' keys and values.  The
prefill expands this call's latent to per-head keys and values (the
equations as written); a decode step runs the same mathematics
ABSORBED, directly on the cache: ``q_lat = q_nope W_UK^T`` (per head,
512 wide; ``W_UK`` is ``W_kvb``'s key half), scores against the cached
``c_kv`` and ``k_r``, the weighted latent through ``W_UV`` (its value
half) and ``W_o``.  Two paths for one layer; tests hold them together.

**The share.**  No chip holds an expert layer of the published model
(24.7 GB).  ``cfg.experts_first`` / ``cfg.experts_held`` say which of
the ``n_routed_experts`` this chip holds (expert parallelism: 16 chips
share each layer, 16 experts to a chip); the router keeps its published
width and its top-k, and the chip computes its own experts' part for
the token-expert pairs routed to them, plus the shared expert.  A pair
routed to an absent expert adds nothing here (its chip would add it);
nothing stands in for the absent chips or their exchange, and that
partial result goes on to the next layer.  **No pair is dropped**:
every expert with at least one pair runs over all tokens of the call
with the pairs' weights (zero for the tokens not routed to it), however
uneven the routing; an expert with no pair is skipped (``lax.cond``),
so a decode step reads only the experts it hits.

Served as one jitted program, ``lm_generate``, exactly as
``models/looplm.py``'s: the prefill of the padded prompts, then
``max_new_tokens`` decode steps in a ``lax.scan``; rows are requests of
different people, moved to the end of the prompt buffer so that all
write the cache at one shared index.  The leading dense blocks and the
expert blocks are two stacks of leaves with a leading layer axis, two
``lax.scan`` bodies over one cache.

Routing is discontinuous, so the program returns beside the logits what
it routed by: the router's scores and its choices at every decoded
position and expert layer (``aux``), and what the decode steps'
routing came to (``stats``: local pairs per row, distinct local experts
hit, pairs dropped).

Precision: weights, cache and matmul operands in ``cfg.dtype``; the
residual stream, every RMSNorm, RoPE, the softmax and the logits in
float32; the ROUTER in float32 at the highest precision (the family's
convention: DeepSeek-V3's gate is a float32 linear), so that a choice
flips only on what the layers before it rounded.

Every operation lies under a ``jax.named_scope`` of the published
module's name (``PanguUltraMoE/moe_layers/self_attn/q_a_proj`` ...),
read by ``utils/trace.KERNEL_CLASSES``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from comfyui_distributed_tpu.models import lm_decode, looplm
from comfyui_distributed_tpu.models.layers import ATTENTION_PATHS, \
    attention_path, xla_attention
from comfyui_distributed_tpu.models.looplm import _dense, _embed, _head, \
    _rms_norm, _sandwich, dense_each, dense_path, few_rows_here, matrix, \
    scan_layers
from comfyui_distributed_tpu.ops.pallas.fewrow_dense import fewrow_grouped
from comfyui_distributed_tpu.ops.pallas.row_scatter_add import LANES, \
    row_scatter_add
from comfyui_distributed_tpu.parallel import sharding as shd


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    """The shape keys of the model's ``config.json``, under its names,
    AS HELD: ``num_hidden_layers`` and ``first_k_dense_replace`` count
    the blocks of this share, ``vocab_size`` its slice.
    ``n_routed_experts`` is the router's width (the published count);
    ``experts_first`` / ``experts_held`` name this chip's experts."""
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25.6e6
    experts_first: int = 0
    experts_held: int = -1          # -1: all of them
    dtype: Any = jnp.bfloat16       # weights, cache, matmul operands

    def __post_init__(self):
        if self.experts_held < 0:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        if not 0 <= self.experts_first <= self.experts_first \
                + self.experts_held <= self.n_routed_experts:
            raise ValueError(
                f"experts {self.experts_first}..{self.experts_first}+"
                f"{self.experts_held} are not among the router's "
                f"{self.n_routed_experts}")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(
                f"{self.first_k_dense_replace} dense blocks of "
                f"{self.num_hidden_layers}")

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def latent_dim(self) -> int:
        """What the cache holds a position a layer: ``c_kv`` and ``k_r``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def layer_applications(self) -> int:
        """Blocks one token passes through."""
        return self.num_hidden_layers


# FreedomIntelligence/openPangu-Ultra-MoE-718B config.json, every width as
# published, cut to ONE chip's share of a 16-chip expert-parallel
# deployment (benchmarks/chip/configs/pangu-ultra-moe-expand-sd15-512.json
# has the arithmetic): 1 of the 3 leading dense blocks and 4 of the 58
# expert blocks (further blocks lie on further chips, as pipeline
# stages), experts 48..63 of the 256 (chip 3 of the 16), an eighth of the
# 153,600-row vocabulary.  The multi-token-prediction module is not held.
OPENPANGU_ULTRA_MOE_SHARE = MlaMoeConfig(
    vocab_size=19200, hidden_size=7680, num_hidden_layers=5,
    first_k_dense_replace=1, num_attention_heads=128, q_lora_rank=1536,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, intermediate_size=18432, moe_intermediate_size=2048,
    n_routed_experts=256, num_experts_per_tok=8, n_shared_experts=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-5,
    rope_theta=25.6e6, experts_first=48, experts_held=16)

# the CPU tests' and the rehearsal's size (fp32: deterministic
# comparisons): a dense block and two expert blocks, experts 4..7 of 16
TINY_MLA_MOE = MlaMoeConfig(
    vocab_size=512, hidden_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=32,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, intermediate_size=160, moe_intermediate_size=48,
    n_routed_experts=16, num_experts_per_tok=4, n_shared_experts=1,
    routed_scaling_factor=2.5, experts_first=4, experts_held=4,
    dtype=jnp.float32)

CONFIGS = {"full": OPENPANGU_ULTRA_MOE_SHARE, "tiny": TINY_MLA_MOE}

# N1..N4 of a block; the second and the fourth stand on a sub-layer's
# OUTPUT (``sandwich_norm: true``), and their seeded gains are smaller
NORMS = ("input_layernorm", "post_attention_layernorm",
         "pre_mlp_layernorm", "post_mlp_layernorm")
SANDWICH_NORMS = ("post_attention_layernorm", "post_mlp_layernorm")
LATENT_NORMS = ("q_a_layernorm", "kv_a_layernorm")
SANDWICH_GAIN = 0.5


def param_shapes(cfg: MlaMoeConfig) -> Dict[str, Any]:
    """The parameter tree's shapes, kernels ``[in, out]``: two stacks of
    blocks (``dense_layers``, ``moe_layers``), each leaf with a leading
    layer axis; an expert block's routed experts are ``experts/*`` with
    the axis of the experts HELD behind it, ``[L, E_here, in, out]``."""
    d, H = cfg.hidden_size, cfg.num_attention_heads
    dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    dkv = cfg.qk_nope_head_dim + cfg.v_head_dim

    def mlp(width, *lead):
        return {"gate_proj": (*lead, d, width), "up_proj": (*lead, d, width),
                "down_proj": (*lead, width, d)}

    def block(L):
        layers = {n: (L, d) for n in NORMS}
        layers.update(
            q_a_proj=(L, d, cfg.q_lora_rank),
            q_a_layernorm=(L, cfg.q_lora_rank),
            q_b_proj=(L, cfg.q_lora_rank, H * dq),
            kv_a_proj_with_mqa=(L, d, cfg.latent_dim),
            kv_a_layernorm=(L, cfg.kv_lora_rank),
            kv_b_proj=(L, cfg.kv_lora_rank, H * dkv),
            o_proj=(L, H * cfg.v_head_dim, d))
        return layers

    Ld, Le = cfg.first_k_dense_replace, cfg.moe_layers
    dense = {**block(Ld), **mlp(cfg.intermediate_size, Ld)}
    moe = {**block(Le), "gate": (Le, d, cfg.n_routed_experts),
           "shared_experts": mlp(
               cfg.moe_intermediate_size * cfg.n_shared_experts, Le),
           "experts": mlp(cfg.moe_intermediate_size, Le, cfg.experts_held)}
    return {"embed_tokens": (cfg.vocab_size, d), "dense_layers": dense,
            "moe_layers": moe, "norm": (d,), "lm_head": (d, cfg.vocab_size)}


def shape_leaves(shapes):
    return jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))


def count_values(shapes) -> int:
    return sum(math.prod(s) for _, s in shape_leaves(shapes)[0])


def param_count(cfg: MlaMoeConfig) -> int:
    return count_values(param_shapes(cfg))


def _norm_gain(name: str):
    """A norm's seeded gain scale, None for a leaf that is no norm: the
    sandwich norms' SANDWICH_GAIN of the others'."""
    if name in SANDWICH_NORMS:
        return SANDWICH_GAIN
    return 1.0 if name in NORMS + LATENT_NORMS or name == "norm" else None


def _seeded_leaf(name: str, shape: tuple, dtype, gain, own=None):
    """The jitted maker of one leaf from its key: kernels normals scaled
    by fan-in, embeddings unit normals, norm gains ``gain`` x (1 + 0.1 N)
    (a gain of exactly 1 would hide a norm applied without its gain);
    ``own(key, shape)`` -> float32 where the family draws the leaf its
    own way.  A
    stacked leaf is drawn slice by slice along its leading axis, so the
    float32 normals of the largest (the experts', 1.0 B values) never
    stand whole beside it."""
    def made(key, shape):
        if own is not None:
            return own(key, shape).astype(dtype)
        x = jax.random.normal(key, shape, jnp.float32)
        if gain is not None:
            x = gain * (1.0 + 0.1 * x)
        elif name != "embed_tokens":
            x = x / math.sqrt(shape[-2])
        return x.astype(dtype)

    def leaf(key):
        if len(shape) < 3:
            return made(key, shape)
        return jax.lax.map(lambda k: made(k, shape[1:]),
                           jax.random.split(key, shape[0]))

    return jax.jit(leaf)


def seeded_tree(shapes, seed, dtype, norm_gain, draws=None
                ) -> Dict[str, Any]:
    """Seeded random weights of a tree of ``shapes``, made on the device
    LEAF BY LEAF (one small jitted call a leaf): the 4.9 B values of the
    published share are 9.8 GB on a 16 GB chip, and one program that drew
    them all could hold several leaves' float32 normals at once.
    ``norm_gain(name)`` says which leaves are norms (`_norm_gain`);
    ``draws`` maps the names of leaves that are neither norms nor
    matrices to their own makers (`_seeded_leaf`)."""
    flat, tree = shape_leaves(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    return jax.tree_util.tree_unflatten(
        tree, [_seeded_leaf(p[-1].key, s, jnp.dtype(dtype),
                            norm_gain(p[-1].key),
                            (draws or {}).get(p[-1].key))(k)
               for (p, s), k in zip(flat, keys)])


def seeded_params(cfg: MlaMoeConfig, seed) -> Dict[str, Any]:
    return seeded_tree(param_shapes(cfg), seed, cfg.dtype, _norm_gain)


def load_checkpoint(path: str, cfg: MlaMoeConfig):
    raise NotImplementedError(
        f"{path}: no reader for a pangu_ultra_moe state dict yet (this "
        f"family is served from seeded weights: a share of 718 B "
        f"parameters is not a file anybody has); remove the file or "
        f"serve another model")


# --- the layer ------------------------------------------------------------

def _rope(x, positions, theta):
    """Rotary embedding over INTERLEAVED pairs ``(2i, 2i + 1)``, in
    float32: ``x [B, N, H, D]``, ``positions [B, N]`` (each row's own)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _queries(cfg, lp, n, positions, scale: float = 1.0):
    """``q_nope [B, N, H, d_nope]`` and the rotated ``q_rope
    [B, N, H, d_rope]``, float32, through the rank-``q_lora_rank``
    bottleneck; both halves times ``scale`` where a family scales the
    expanded query (`models/mla_scmoe.py`; 1: no operation is added)."""
    B, N, _ = n.shape
    with jax.named_scope("q_a_proj"):
        c_q = _dense(n, lp["q_a_proj"], cfg)
    with jax.named_scope("q_a_layernorm"):
        c_q = _rms_norm(c_q, lp["q_a_layernorm"], cfg.rms_norm_eps)
    with jax.named_scope("q_b_proj"):
        q = _dense(c_q, lp["q_b_proj"], cfg).reshape(
            B, N, cfg.num_attention_heads, -1)
        if scale != 1.0:
            q = q * scale
    q = shd.constrain(q, "batch", None, "heads", None)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    with jax.named_scope("rotary"):
        q_rope = _rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _latent(cfg, lp, n, positions, scale: float = 1.0):
    """What the cache holds of this call's positions: the normed latent
    ``c_kv`` (times ``scale`` where a family scales it: the cache then
    holds the SCALED latent, which both ways of attending read; the
    rotary key is never scaled) and the rotated shared key ``k_r``,
    ``[B, N, 576]`` in the model's dtype."""
    with jax.named_scope("kv_a_proj_with_mqa"):
        c_kv, k_r = jnp.split(_dense(n, lp["kv_a_proj_with_mqa"], cfg),
                              [cfg.kv_lora_rank], axis=-1)
    with jax.named_scope("kv_a_layernorm"):
        c_kv = _rms_norm(c_kv, lp["kv_a_layernorm"], cfg.rms_norm_eps)
        if scale != 1.0:
            c_kv = c_kv * scale
    with jax.named_scope("rotary"):
        k_r = _rope(k_r[:, :, None], positions, cfg.rope_theta)[:, :, 0]
    return jnp.concatenate([c_kv, k_r], axis=-1).astype(cfg.dtype)


def _kv_b(cfg: MlaMoeConfig, lp):
    """``W_kvb`` as ``[rank, H, d_nope + d_v]``: its key half is ``W_UK``,
    its value half ``W_UV``."""
    return matrix(lp["kv_b_proj"]).reshape(cfg.kv_lora_rank,
                                           cfg.num_attention_heads, -1)


def _attend_expanded(cfg: MlaMoeConfig, lp, q_nope, q_rope, latent, index,
                     first):
    """The equations as written: this call's latent expanded to every
    head's keys and values, the shared ``k_r`` beside each head's
    ``k_nope``.  -> ``[B, N, H, d_v]``."""
    B, N, H, _ = q_nope.shape
    c_kv, k_r = jnp.split(latent, [cfg.kv_lora_rank], axis=-1)
    with jax.named_scope("kv_b_proj"):
        kv = jnp.einsum("bnr,rhd->bnhd", c_kv, _kv_b(cfg, lp),
                        preferred_element_type=jnp.float32)
    k_nope, v = jnp.split(kv, [cfg.qk_nope_head_dim], axis=-1)
    k_r = jnp.broadcast_to(k_r[:, :, None], (B, N, H, k_r.shape[-1]))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_r.astype(jnp.float32)], axis=-1)
    q, k, v = (shd.constrain(t.astype(cfg.dtype), "batch", None, "heads",
                             None) for t in (q, k, v))
    ATTENTION_PATHS.bump(attention_path(jax.default_backend(), B, N, N, H,
                                        masked=True))
    return xla_attention(q, k, v, 1.0 / math.sqrt(q.shape[-1]), index, first)


def _attend_absorbed(cfg: MlaMoeConfig, lp, q_nope, q_rope, latent, index,
                     first, own=None):
    """The same mathematics on the latent cache ``latent [B, T, 576]``:
    ``W_UK`` folded into the query, ``W_UV`` into the output.  The heads'
    queries meet ONE key and one value a position (the latent has no
    head axis), so they go to `xla_attention` as the queries of a single
    head.  With ``own [B, N]`` (a call behind a shared prefix) a query
    that is not its row's own sees nothing, as a padded one: a selection,
    so `xla_attention` normalises behind the product with the values and
    the ``[B, N H, T]`` scores' row maximum is no ``reduce-window`` (2.31
    ms against 2.97 at 4 x 97 queries on 2,112 latents: PERF.md section
    6, PR 50).  -> ``[B, N, H, d_v]``."""
    B, N, H, _ = q_nope.shape
    w_uk, w_uv = jnp.split(_kv_b(cfg, lp), [cfg.qk_nope_head_dim], axis=-1)
    with jax.named_scope("absorb_q"):
        q_lat = jnp.einsum("bnhd,rhd->bnhr", q_nope.astype(cfg.dtype), w_uk,
                           preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_lat, q_rope], axis=-1).astype(cfg.dtype)
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    ATTENTION_PATHS.bump("xla_decode" if N == 1 else "xla_causal")
    mask = {} if own is None \
        else {"selected": jnp.repeat(own, H, axis=1)[..., None]}
    o_lat = xla_attention(
        q.reshape(B, N * H, 1, -1), latent[:, :, None],
        latent[:, :, None, :cfg.kv_lora_rank], scale,
        jnp.repeat(index, H), first, **mask).reshape(B, N, H, -1)
    with jax.named_scope("absorb_v"):
        return jnp.einsum("bnhr,rhd->bnhd", o_lat.astype(cfg.dtype), w_uv,
                          preferred_element_type=jnp.float32)


def _self_attn(cfg, lp, n, positions, index, first, cache, l,
               absorbed: bool, q_scale: float = 1.0, kv_scale: float = 1.0,
               prefix: int = 0):
    """Latent attention of the normed ``n [B, N, d]`` at ``positions
    [B, N]``, and the cache with this call's latent written into slot
    ``l`` at the buffer indices ``index``.  With ``absorbed`` the queries
    attend to the cache (a decode step), without to this call's own
    latent, expanded (the prefill).  Behind ``prefix`` positions a row that
    the cache holds already (a family's ``from_prefix``; 0: no operation
    is added) a call is ``absorbed`` (its keys are the cache's), writes a
    row's OWN entries alone (`lm_decode.own_entries`) and lets only a
    row's own queries see.  -> ``W_o``'s output ``[B, N, d]``."""
    B, N, _ = n.shape
    q_nope, q_rope = _queries(cfg, lp, n, positions, q_scale)
    latent = _latent(cfg, lp, n, positions, kv_scale)
    own = index[None, :] >= first[:, None] + prefix if prefix else None
    with jax.named_scope("kv_cache"):
        if prefix:
            latent = lm_decode.own_entries(own, latent, cache, l, index[0])
        cache = jax.lax.dynamic_update_slice(
            cache, latent[None].astype(cache.dtype), (l, 0, index[0], 0))
        if absorbed:
            latent = jax.lax.dynamic_index_in_dim(
                cache, l, keepdims=False).astype(cfg.dtype)
    if absorbed:
        a = _attend_absorbed(cfg, lp, q_nope, q_rope, latent, index, first,
                             own)
    else:
        a = _attend_expanded(cfg, lp, q_nope, q_rope, latent, index, first)
    with jax.named_scope("o_proj"):
        return _dense(a.reshape(B, N, -1), lp["o_proj"], cfg), cache


def _attention(cfg: MlaMoeConfig, lp, x, index, first, cache, l,
               absorbed: bool):
    """``h = x + N2(Attn(N1(x)))`` and the cache (`_self_attn`)."""
    positions = index[None, :] - first[:, None]
    with jax.named_scope("input_layernorm"):
        n = _rms_norm(x, lp["input_layernorm"], cfg.rms_norm_eps)
    with jax.named_scope("self_attn"):
        a, cache = _self_attn(cfg, lp, n, positions, index, first, cache, l,
                              absorbed)
    with jax.named_scope("post_attention_layernorm"):
        return _sandwich(x, a, lp["post_attention_layernorm"],
                         cfg.rms_norm_eps), cache


def _gated_mlp(cfg: MlaMoeConfig, weights, n, scope=jax.named_scope):
    """``W_down (silu(W_gate n) * W_up n)``: the dense blocks' MLP, the
    shared expert and every routed expert (whose projections carry no
    scope of their own: a trace classes them with ``experts``)."""
    g, u = dense_each(n, weights, ("gate_proj", "up_proj"), cfg, scope)
    rows = ("batch", None) if n.ndim == 3 else (None,)     # [B, N] or [t]
    h = shd.constrain(jax.nn.silu(g) * u, *rows, "mlp")
    with scope("down_proj"):
        return _dense(h, weights["down_proj"], cfg)


def route(cfg, gate, n, bias=None):
    """The router over ALL its outputs, in float32 at the highest
    precision: the scores ``[t, E]`` (``scoring_func``: each expert's
    ``sigmoid``, or a ``softmax`` over them all), the chosen experts
    ``[t, k]`` and their weights (with ``norm_topk_prob`` the chosen
    scores over their sum; times ``routed_scaling_factor``).  With a
    score-correction ``bias [E]`` the experts are chosen by ``scores +
    bias`` and weighted by the scores alone."""
    logits = jnp.dot(n.astype(jnp.float32), gate.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1) \
        if cfg.scoring_func == "softmax" else jax.nn.sigmoid(logits)
    if bias is None:
        top, chosen = jax.lax.top_k(scores, cfg.num_experts_per_tok)
    else:
        _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                  cfg.num_experts_per_tok)
        top = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return scores, chosen, top * cfg.routed_scaling_factor


# The rows of one product with a routed expert where a call's tokens are
# gathered by expert: one pass of the MXU's rows (with more, an expert with
# a few pairs too many pays for a second tile of mostly padding).  A call
# of up to TWO tiles' tokens is not gathered: each hit expert multiplies
# all of them, because up to the chip's ridge (a v5e's 197 TFLOP/s over
# 819 GB/s: 240 rows) that product is bound by the expert's bytes whatever
# its rows, and the sort, the gathers and the tiles could only add to it
# (openPangu's prefill of 4 x 64 positions: 13.4 ms an execution whole,
# 21.6 in tiles; PERF.md section 6, PR 35).
EXPERT_TILE = 128


def tile_sum_path(platform: str, d: int,
                  mesh_axes: Optional[dict] = None) -> str:
    """How a tile's results go back into the sum ``[t, d]``: ``kernel``
    (`row_scatter_add`: every row's read and write in flight at once) or
    ``xla`` (``.at[rows].add``, which walks the rows one by one).  As
    `looplm.dense_path`, a function of what is visible at trace time: on
    a TPU, rows of whole lanes, no multi-device mesh live (XLA cannot
    partition the custom call)."""
    if platform == "tpu" and d % LANES == 0 \
            and math.prod((mesh_axes or {}).values()) == 1:
        return "kernel"
    return "xla"


def routed_path(platform: str, t: int, d: int, width: int, held: int,
                k: int, itemsize: int = 2,
                mesh_axes: Optional[dict] = None) -> str:
    """How the hit experts of a call of ``t`` tokens (one tile of them)
    are walked: ``grouped`` (`fewrow_grouped`: the hit experts, ascending,
    through ONE weight stream) or ``loop`` (a conditional an expert held,
    three ``jnp.dot`` inside a hit one).  A function of what is visible
    at trace time: a call whose experts' matrices ``[d, width]`` and
    ``[width, d]`` `looplm.dense_path` would stream through the few-row
    kernel (a TPU, 2 to 8 rows, no multi-device mesh live, blocks that
    divide, worth a launch), where the ``held`` experts outnumber the
    ``t x k`` pairs the call can route.  Only there does the loop walk
    conditionals that no grouped slot stands for (Keye's 128 for at most
    32 hits: 18 us a hit where its bytes are 11.5).  With 16 held experts
    the loop's conditionals cost ~14 us a block and the grouped path's two
    launches, its slots behind the hits and its combine 40-57: even at the
    1.3-2.2 experts a block the two accepted shares hit (PERF.md section
    6, PR 44), so they keep the loop, as do the one-row program, every
    prefill and every other backend."""
    if held > t * k and all(
            dense_path(platform, t, a, b, itemsize, mesh_axes) == "fewrow"
            for a, b in ((d, width), (width, d))):
        return "grouped"
    return "loop"


def _expert(experts, l, e):
    """Expert ``e`` of expert block ``l`` out of the leaves
    ``[L, E_here, in, out]``."""
    return {name: jax.lax.dynamic_slice(
        w, (l, e, 0, 0), (1, 1, *w.shape[2:]))[0, 0]
        for name, w in experts.items()}


def _routed(cfg: MlaMoeConfig, experts, l, x, chosen, weights):
    """The part of the expert layer's result that THIS chip's experts
    give for the tokens ``x [t, d]``: every local (token, choice) pair
    through its expert, times its weight, added up in float32.  An expert
    nobody chose is not read.  ``experts`` holds every expert block's
    leaves ``[L, E_here, in, out]`` and is indexed in place by ``(l, e)``:
    a slice handed to the conditional would be a copy of the weights.

    A few-row call on a TPU (`routed_path`: the shared decode step) runs
    its hit experts, ascending, through `_grouped`: the same operands,
    the same float32 sum in the same order, one weight stream.  Every
    other call walks the experts held, a conditional each:

    An expert multiplies TILES of rows.  Where the call has no more
    tokens than two of `EXPERT_TILE` (a decode step, a short prefill) the
    one tile is the call's tokens, each with its weight for the expert
    (zero where it did not choose it), and nothing is sorted or gathered.
    Where it has more, the pairs are ordered by expert, and a hit expert
    walks ``ceil(its pairs / EXPERT_TILE)`` tiles: the tile's token rows
    gathered, the gated MLP on ``[tile, d]``, the result times each
    pair's weight (zero for the last tile's padding rows) added to the
    sum's rows: by the kernel `row_scatter_add` where `tile_sum_path`
    says so (a TPU), else by XLA's scatter.  No capacity: no pair is ever
    dropped.

    Returns the sum ``[t, d]`` and, int32, the local pairs of each token
    ``[t]``, the experts hit, the local pairs NOT computed (0) and the
    rows the experts multiplied (tiles x their rows)."""
    t, d = x.shape
    held, k = cfg.experts_held, cfg.num_experts_per_tok
    tile = EXPERT_TILE if t > 2 * EXPERT_TILE else t
    platform, mesh_axes = looplm._where()
    kernel = tile < t and tile_sum_path(platform, d, mesh_axes) == "kernel"

    def mlp(own, rows):
        return _gated_mlp(cfg, own, rows, contextlib.nullcontext)

    with jax.named_scope("dispatch"):
        local = chosen - cfg.experts_first
        # [t, k, E_here]: a pair to an absent expert matches no column
        onehot = local[..., None] == jnp.arange(held)
        pairs = jnp.sum(onehot, axis=(1, 2), dtype=jnp.int32)
        count = jnp.sum(onehot, axis=(0, 1), dtype=jnp.int32)
        if tile == t:
            combine = jnp.sum(jnp.where(onehot, weights[..., None], 0.0),
                              axis=1)
        else:
            # the pairs by expert, an expert's by token (a token chooses
            # an expert once: a tile's rows are distinct and ascending),
            # the absent experts' behind them all; a tile's worth of
            # padding, so that the last tile's slice never runs out
            _, token, weight = jax.lax.sort(
                (jnp.where(onehot.any(-1), local, held).reshape(-1),
                 jnp.repeat(jnp.arange(t, dtype=jnp.int32), k),
                 weights.reshape(-1)), num_keys=2)
            token, weight = (jnp.pad(a, (0, tile)) for a in (token, weight))
            start = jnp.cumsum(count) - count
            x = x.astype(cfg.dtype)

    if tile == t and routed_path(
            platform, t, d, experts["gate_proj"].shape[-1], held, k,
            jnp.dtype(cfg.dtype).itemsize, mesh_axes) == "grouped":
        looplm._count_sites("fewrow_grouped", t, len(experts))
        y, hits, done = _grouped(cfg, experts, l, x, count, combine)
        return y, pairs, hits, jnp.sum(count) - done, hits * t
    if tile == t:
        def tiles_of(e, own, y):
            """Expert ``e``'s one tile, the call's tokens, added to ``y``."""
            w_e = jax.lax.dynamic_index_in_dim(combine, e, axis=1)
            return 1, y + w_e * mlp(own, x)
    else:
        def tiles_of(e, own, y):
            """Expert ``e``'s tiles of its own tokens added to ``y``."""
            def add(i, y):
                at = i * tile + jnp.arange(tile, dtype=jnp.int32)
                mine, w = (jax.lax.dynamic_slice(
                    a, (start[e] + i * tile,), (tile,))
                    for a in (token, weight))
                # a padding row reads and writes nothing: a row past the
                # tokens, with no weight
                live = at < count[e]
                rows = jnp.where(live, mine, t + at)
                out = jnp.where(live, w, 0.0)[:, None] * mlp(
                    own, x.at[rows].get(
                        mode="fill", fill_value=0, indices_are_sorted=True,
                        unique_indices=True))
                if kernel:
                    return row_scatter_add(
                        y, mine, count[e] - i * tile,
                        out.reshape(tile, -1, LANES))
                return y.at[rows].add(out, mode="drop",
                                      indices_are_sorted=True,
                                      unique_indices=True)
            tiles = (count[e] + tile - 1) // tile
            return tiles, jax.lax.fori_loop(0, tiles, add, y)

    def one(e, carry):
        def run(carry):
            y, done, rows = carry
            tiles, y = tiles_of(e, _expert(experts, l, e), y)
            return y, done + count[e], rows + tiles * tile
        return jax.lax.cond(count[e] > 0, run, lambda carry: carry, carry)

    with jax.named_scope("experts"):
        # (the kernel's sum is laid out in rows of whole lanes)
        shape = (t, d // LANES, LANES) if kernel else (t, d)
        y, done, rows = jax.lax.fori_loop(
            0, held, one, (jnp.zeros(shape, jnp.float32),
                           jnp.int32(0), jnp.int32(0)))
    return y.reshape(t, d), pairs, jnp.sum(count > 0, dtype=jnp.int32), \
        jnp.sum(count) - done, rows


def _grouped(cfg: MlaMoeConfig, experts, l, x, count, combine):
    """`_routed`'s sum for a few rows ``x [t, d]`` by the grouped kernel:
    ``min(experts_held, t x num_experts_per_tok)`` static slots (no more
    experts can be hit), the hit experts in ascending order in the first
    of them; `gate_proj` and `up_proj` of every slot in one call,
    `down_proj` in a second over the slots' ``silu(g) * u`` in the
    model's dtype, each slot's result times its expert's column of
    ``combine [t, E_here]`` added up in float32 in that order, a dead
    slot's (never written) left out.  A call that hits nothing launches
    nothing.  -> the sum, the experts hit, the pairs they computed."""
    t, d = x.shape
    held = cfg.experts_held
    slots = min(held, t * cfg.num_experts_per_tok)
    with jax.named_scope("dispatch"):
        hit = count > 0
        hits = jnp.sum(hit, dtype=jnp.int32)
        # [slots, E_here]: slot s takes the s-th hit expert
        taken = hit & (jnp.cumsum(hit) - 1
                       == jnp.arange(slots, dtype=jnp.int32)[:, None])
        ids = jnp.sum(jnp.where(taken, jnp.arange(held, dtype=jnp.int32),
                                0), axis=1)
        weight = jnp.sum(jnp.where(taken[:, None], combine[None], 0.0),
                         axis=-1)
        done = jnp.sum(jnp.where(taken, count, 0))

    def run():
        g, u = fewrow_grouped(
            x.astype(cfg.dtype), [experts["gate_proj"], experts["up_proj"]],
            l, ids, hits, name="fewrow_grouped_gate_proj_up_proj")
        (o,) = fewrow_grouped(
            (jax.nn.silu(g) * u).astype(cfg.dtype), [experts["down_proj"]],
            l, ids, hits, name="fewrow_grouped_down_proj")
        y = jnp.zeros((t, d), jnp.float32)
        for s in range(slots):
            y = y + jnp.where(s < hits, weight[s][:, None] * o[s], 0.0)
        return y

    with jax.named_scope("experts"):
        y = jax.lax.cond(hits > 0, run,
                         lambda: jnp.zeros((t, d), jnp.float32))
    return y, hits, done


def _moe(cfg: MlaMoeConfig, lp, experts, l, n):
    """The expert block's MLP on ``n [B, N, d]``: the shared expert
    (where the block has one) plus this chip's routed part; and its
    routing: the router's ``(scores [B, N, E], choices [B, N, k])`` and
    `_routed`'s counts (local pairs per row ``[B]``, hits, dropped, rows
    computed)."""
    B, N, d = n.shape
    x = n.reshape(B * N, d)
    with jax.named_scope("gate"):
        scores, chosen, weights = route(cfg, matrix(lp["gate"]), x)
    y, pairs, *counts = _routed(cfg, experts, l, x, chosen, weights)
    shared = None
    if "shared_experts" in lp:
        with jax.named_scope("shared_experts"):
            shared = _gated_mlp(cfg, lp["shared_experts"], x)
    with jax.named_scope("combine"):
        out = (y if shared is None else shared + y).reshape(B, N, d)
    return out, ((scores.reshape(B, N, -1), chosen.reshape(B, N, -1)),
                 (pairs.reshape(B, N).sum(axis=1), *counts))


def _stack(cfg: MlaMoeConfig, params, x, index, first, cache,
           absorbed: bool):
    """Every block held: the leading dense ones, then the expert ones.
    ``cache`` is the ``[L, B, T, 576]`` latent buffer; each block writes
    this call's entries at the buffer indices ``index [N]`` (consecutive,
    the same for every row); row ``b``'s real entries start at
    ``first[b]``, its position 0.  A call of few rows on a TPU walks the
    layer index with the leaves closed over (`looplm.scan_layers`).
    Returns the normed last state, the
    cache, the routers' ``(scores [B, N, Le, E], choices [B, N, Le, k])``
    and the routing counts summed over the expert blocks (local pairs
    ``[B]``, hits, dropped, rows computed)."""
    Ld = cfg.first_k_dense_replace
    eps = cfg.rms_norm_eps
    stream = few_rows_here(math.prod(x.shape[:2]))

    def block(mlp, carry, lp, l):
        x, cache = carry
        h, cache = _attention(cfg, lp, x, index, first, cache, l, absorbed)
        with jax.named_scope("pre_mlp_layernorm"):
            n = _rms_norm(h, lp["pre_mlp_layernorm"], eps)
        with jax.named_scope("mlp"):
            m, routing = mlp(lp, l, n)
        with jax.named_scope("post_mlp_layernorm"):
            return (_sandwich(h, m, lp["post_mlp_layernorm"], eps),
                    cache), routing

    moe = dict(params["moe_layers"])
    experts = moe.pop("experts")
    with jax.named_scope("dense_layers"):
        carry, _ = scan_layers(
            lambda c, xs: block(
                lambda lp, l, n: (_gated_mlp(cfg, lp, n), None), c, *xs),
            (x, cache), params["dense_layers"], Ld, stream)
    with jax.named_scope("moe_layers"):
        (x, cache), (routed, counts) = scan_layers(
            lambda c, xs: block(
                lambda lp, l, n: _moe(cfg, lp, experts, l - Ld, n), c, *xs),
            carry, moe, cfg.moe_layers, stream, first=Ld)
    with jax.named_scope("final_norm"):
        x = _rms_norm(x, params["norm"], eps)
    return x, cache, tuple(jnp.moveaxis(r, 0, 2) for r in routed), \
        tuple(c.sum(axis=0) for c in counts)


def empty_cache(cfg: MlaMoeConfig, batch: int, length: int):
    """The latent cache: ``c_kv`` and ``k_r`` of every position of every
    block held, and no head axis."""
    return jnp.zeros((cfg.num_hidden_layers, batch, length, cfg.latent_dim),
                     cfg.dtype)


def kv_cache_bytes(cfg: MlaMoeConfig, batch: int, length: int) -> int:
    return cfg.num_hidden_layers * batch * length * cfg.latent_dim \
        * jnp.dtype(cfg.dtype).itemsize


# --- the served program ---------------------------------------------------

def generate(cfg: MlaMoeConfig, max_new_tokens: int, params, prompt_ids,
             prompt_len, seed, temperature
             ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array],
                        Dict[str, jax.Array]]:
    """Prefill, then ``max_new_tokens`` decode steps, for every row:
    `looplm.generate`'s contract (rows, lengths, seeds, temperatures,
    padding never attended to).  Returns the new ids ``[B, N]``, the
    float32 logits each was drawn from ``[B, N, V]``, ``aux`` (what the
    routers of the expert blocks scored ``router_scores [B, N, Le, E]``
    and chose ``expert_choices [B, N, Le, k]`` where those logits were
    computed, and what they chose over the prompt buffer,
    ``prompt_choices [B, P, Le, k]``, a row's real positions at its end)
    and ``stats``, int32, over the DECODE steps and the expert
    blocks: ``expert_pairs_local [B]`` (a row's pairs routed to experts
    held here), ``expert_hits`` (distinct local experts with at least one
    pair, over all rows of a step); over the PREFILL
    ``expert_pairs_local_prefill [B]`` (over the whole prompt buffer) and
    ``expert_rows_computed_prefill`` (the rows the routed experts
    multiplied for them: `_routed`'s tiles x their rows); over both
    ``expert_pairs_dropped`` (0)."""
    B, P = prompt_ids.shape
    first = P - jnp.broadcast_to(prompt_len, (B,))

    def prefill():
        with jax.named_scope("prefill"):
            # every row's last real id at P - 1
            ids = jax.vmap(jnp.roll)(prompt_ids, first)
            cache = empty_cache(cfg, B, P + max_new_tokens)
            x, cache, routed, counts = _stack(
                cfg, params, _embed(params, ids), jnp.arange(P), first,
                cache, absorbed=False)
            logits = _head(cfg, params, x[:, P - 1:])[:, 0]
            return (logits, tuple(r[:, P - 1] for r in routed), cache,
                    (jnp.zeros((B,), jnp.int32), jnp.int32(0), counts[2]),
                    (routed[1], counts))

    def step(token, i, cache):
        x, cache, routed, (*now, _) = _stack(
            cfg, params, _embed(params, token[:, None]), P + i[None], first,
            cache, absorbed=True)
        return (_head(cfg, params, x)[:, 0], tuple(r[:, 0] for r in routed),
                cache, now)

    tokens, logits, (scores, choices), (pairs, hits, dropped), \
        (prompt_choices, (prefill_pairs, _, _, prefill_rows)) = \
        lm_decode.generate("PanguUltraMoE", B, prefill, step,
                           max_new_tokens, seed, temperature)
    return (tokens, logits,
            {"router_scores": scores, "expert_choices": choices,
             "prompt_choices": prompt_choices},
            {"expert_pairs_local": pairs, "expert_hits": hits,
             "expert_pairs_dropped": dropped,
             "expert_pairs_local_prefill": prefill_pairs,
             "expert_rows_computed_prefill": prefill_rows})


def make_program(cfg: MlaMoeConfig, max_new_tokens: int):
    """The jitted program, named ``lm_generate`` (``jit_lm_generate`` in a
    device trace) like every language model's."""
    return lm_decode.make_program(
        functools.partial(generate, cfg, max_new_tokens))


def routing_counters(cfg, stats, real: int, steps: int) -> Dict[str, int]:
    """The routing part of the ``lm.*`` window counters of one execution
    from its fetched ``stats``, for every family whose blocks are
    `_moe`'s: the ``real`` rows' pairs (a padded row repeats the first and
    is nobody's request; it routes as the first does, so it adds no hit)
    and, over EVERY row of the program, what the prefill routed here and
    the rows its experts multiplied for that (their ratio is what the
    tiles waste)."""
    return {
        "lm.expert_pairs": real * steps * cfg.moe_layers
        * cfg.num_experts_per_tok,
        "lm.expert_pairs_local": int(stats["expert_pairs_local"][:real]
                                     .sum()),
        "lm.expert_hits": int(stats["expert_hits"]),
        "lm.expert_pairs_dropped": int(stats["expert_pairs_dropped"]),
        "lm.expert_pairs_local_prefill": int(
            stats["expert_pairs_local_prefill"].sum()),
        "lm.expert_rows_computed_prefill": int(
            stats["expert_rows_computed_prefill"])}


# the ``lm.*`` window counters of one execution (`registry.LMFamily`): this
# family counts its routing and nothing else
window_counters = routing_counters
