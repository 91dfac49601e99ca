"""A latent-attention decoder whose LAYER is a block of two attentions and
two dense MLPs with a shortcut-connected expert layer between them, and
whose router scores zero-compute experts beside the real ones
(LongCat-Flash-Omni's language model, ``model_type`` ``longcat_flash``;
the LongCat-Flash technical report, arXiv:2509.01322, and the family's
``modeling_longcat_flash.py``), served as ONE chip's share of a
deployment.  Every ``N`` and ``RMSNorm`` is an RMSNorm with a gain.

    x = E[ids]
    for l in 0..L-1:                                 # one published "layer"
      h  = x  + MLA_{l,0}(N_in0(x))                  # its own latent cache slot
      u  = N_post0(h)
      m  = MoE_l(u)                                  # the shortcut: taken HERE,
      h  = h  + Dense_{l,0}(u)                       #   added at the block's END
      h2 = h  + MLA_{l,1}(N_in1(h))                  # a second cache slot
      x  = h2 + Dense_{l,1}(N_post1(h2)) + m
    logits = W_head N(x)

    MLA(n):  c_q = RMSNorm(n W_qa);  q = (c_q W_qb) * sqrt(d / r_q)
             [c_kv, k_r] = n W_kva;  c_kv = RMSNorm(c_kv) * sqrt(d / r_kv)
             [k_nope, v] = c_kv W_kvb -> H x (d_nope + d_v)
             q_r, k_r rotated (interleaved pairs; k_r ONE key for all
             heads, NOT scaled)
             s = (q_nope . k_nope + q_r . k_r) / sqrt(d_nope + d_rope)
             out = W_o concat_h(softmax(s; causal) v)
    Dense(n): W_down (silu(W_gate n) * W_up n)
    MoE(u):  p = softmax(u W_r) over ALL E + Z outputs (E experts and Z
             zero experts), float32
             chosen = top-k of (p + b)          # b: selection ONLY
             w = routed_scaling_factor * p[chosen]      # NOT renormalised
             m = sum_{e chosen, e < E, HELD HERE} w_e Expert_e(u)
                 + (sum_{e chosen, e >= E} w_e) * u     # identity experts

**Two cache slots a layer.**  The cache is ``[2 L, B, T, 576]``: sub-layer
``s`` of layer ``l`` writes slot ``2 l + s``.  The MLA functions are
``models/mla_moe.py``'s (`_self_attn`: the prefill expanded, a decode step
absorbed on the cache), handed the two scale factors.  **The cache holds
the SCALED latent** ``c_kv * sqrt(d / r_kv)`` (bf16's rounding is relative,
so scaling before it loses nothing), which both ways of attending read as
it lies; the rotary key beside it is not scaled.

**The shortcut.**  The expert layer reads the FIRST sub-layer's normed
output and its result ``m`` is carried past a dense MLP and a whole
attention before it is added: in a deployment that is the time the
experts' exchange between chips has to hide in.  On one chip there is no
exchange and nothing stands in for it; ``m`` is a value that stays live.

**The share.**  32 chips share each layer: ``experts_first`` /
``experts_held`` name this chip's 16 of the 512 experts; the router keeps
its 768 outputs and its top-12.  The three kinds of pair part ways: a pair
to an expert held here is computed here (`mla_moe._routed`: no pair
dropped, an expert nobody chose not read); a pair to one of the 496
absent experts adds nothing (its chip would add it); **a pair to a zero
expert is the token's own chip's to add, whole**: nothing would be sent
anywhere for it, so every chip adds it for the tokens it serves, like a
shared expert.  The zero experts are ONE scaled add of the layer's input
(the sum of a token's chosen zero weights times ``u``) under the scope
``zero_experts``: no gather, no loop over experts, no product.

Served as one jitted program, ``lm_generate`` (`lm_decode.generate`
around the ``prefill`` and ``step`` closures below).  Rows whose prompts
start with the same ids (an operator's instructions) start from a resident
SNAPSHOT of what those ids leave in the cache slots and prefill what
follows them only (`make_prefix_program`, `from_prefix`).  Routing is
discontinuous, so the program returns beside the logits what it routed
by (``aux``): the scores it SELECTED by (``p + b``), its choices and the
weights it gave them.

Precision as the other families': weights, cache and matmul operands in
``cfg.dtype``; the residual stream, every RMSNorm, RoPE, the softmax and
the logits in float32; the router in float32 at the highest precision.

Scopes carry the published modules' names (``LongcatFlash/decode/layers/
self_attn_0/q_a_proj`` ..., ``mlps_1/down_proj``, ``mlp/router``,
``mlp/experts``, ``mlp/zero_experts``), read by
``utils/trace.KERNEL_CLASSES``.  The parameter tree's leaves carry them
too: ``sublayers/*`` stacks ``self_attn.{0,1}``, ``mlps.{0,1}``,
``input_layernorm.{0,1}`` and ``post_attention_layernorm.{0,1}`` of every
layer on ONE leading axis of ``2 L`` (``2 l + s``: a few-row product
streams its leaf in place by a scalar index), ``router/classifier`` and
``router/e_score_correction_bias`` are ``mlp.router.*``, ``experts/*``
``mlp.experts.N`` of the experts held.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from comfyui_distributed_tpu.models import lm_decode
from comfyui_distributed_tpu.models.layers import visible_keys
from comfyui_distributed_tpu.models.looplm import _embed, _head, \
    _rms_norm, few_rows_here, layer_of, matrix  # noqa: F401
from comfyui_distributed_tpu.models.mla_moe import _gated_mlp, _routed, \
    _self_attn, count_values, route, routing_counters, seeded_tree


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    """The shape keys of the model's ``config.json``, under its names, AS
    HELD: ``num_layers`` counts the blocks of this share, ``vocab_size``
    its slice.  ``n_routed_experts`` is the published count of real
    experts and ``zero_expert_num`` of identity experts: the router has
    an output for each; ``experts_first`` / ``experts_held`` name this
    chip's real experts."""
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    ffn_hidden_size: int
    expert_ffn_hidden_size: int
    n_routed_experts: int
    zero_expert_num: int
    moe_topk: int
    routed_scaling_factor: float = 1.0
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    experts_first: int = 0
    experts_held: int = -1          # -1: all of them
    dtype: Any = jnp.bfloat16       # weights, cache, matmul operands

    # the router as `mla_moe.route` reads it: a softmax over all outputs,
    # the chosen scores not renormalised (no key of the config: the
    # family's defaults)
    scoring_func = "softmax"
    norm_topk_prob = False

    def __post_init__(self):
        if self.experts_held < 0:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        if not 0 <= self.experts_first <= self.experts_first \
                + self.experts_held <= self.n_routed_experts:
            raise ValueError(
                f"experts {self.experts_first}..{self.experts_first}+"
                f"{self.experts_held} are not among the "
                f"{self.n_routed_experts} real ones")

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def num_experts_per_tok(self) -> int:
        return self.moe_topk

    @property
    def moe_layers(self) -> int:
        return self.num_layers

    @property
    def sublayers(self) -> int:
        """Attentions (cache slots, dense MLPs) held: two a layer."""
        return 2 * self.num_layers

    @property
    def latent_dim(self) -> int:
        """What a cache slot holds a position: ``c_kv`` and ``k_r``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def layer_applications(self) -> int:
        """Published layers one token passes through."""
        return self.num_layers

    @property
    def q_scale(self) -> float:
        return math.sqrt(self.hidden_size / self.q_lora_rank) \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self) -> float:
        return math.sqrt(self.hidden_size / self.kv_lora_rank) \
            if self.mla_scale_kv_lora else 1.0


# meituan-longcat/LongCat-Flash-Omni config.json (the language model's
# keys), every width as published, cut to ONE chip's share of a 32-chip
# expert-parallel deployment (benchmarks/chip/configs/
# longcat-flash-omni-expand-sd15-512.json has the arithmetic): 4 of the 28
# layers (the pattern's period is one layer and there is no leading dense
# one; further layers lie on further chips, as pipeline stages), experts
# 96..111 of the 512 (chip 6 of the 32) with all 256 zero experts (they
# hold nothing), an eighth of the 131,072-row vocabulary.  The audio and
# vision encoders, the codec decoder and the multi-token-prediction head
# are not held.
LONGCAT_FLASH_OMNI_SHARE = LongcatFlashConfig(
    vocab_size=16384, hidden_size=6144, num_layers=4, num_attention_heads=64,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, ffn_hidden_size=12288,
    expert_ffn_hidden_size=2048, n_routed_experts=512, zero_expert_num=256,
    moe_topk=12, routed_scaling_factor=6.0, mla_scale_q_lora=True,
    mla_scale_kv_lora=True, rms_norm_eps=1e-5, rope_theta=1e7,
    experts_first=96, experts_held=16)

# the CPU tests' and the rehearsal's size (fp32: deterministic
# comparisons): two layers, experts 4..7 of 16 and 8 zero experts, top-4
TINY_MLA_SCMOE = LongcatFlashConfig(
    vocab_size=512, hidden_size=64, num_layers=2, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, ffn_hidden_size=160,
    expert_ffn_hidden_size=48, n_routed_experts=16, zero_expert_num=8,
    moe_topk=4, routed_scaling_factor=6.0, experts_first=4, experts_held=4,
    dtype=jnp.float32)

CONFIGS = {"full": LONGCAT_FLASH_OMNI_SHARE, "tiny": TINY_MLA_SCMOE}

BLOCK_NORMS = ("input_layernorm", "post_attention_layernorm")
LATENT_NORMS = ("q_a_layernorm", "kv_a_layernorm")
# The seeded gain of the latent's norm.  ``c_kv`` leaves it times
# sqrt(6144 / 512) = 3.46: with a unit gain a head's seeded scores would be
# N(0, 33), a softmax of ONE key (bf16's rounding of a score of 20 moves
# its weight by a tenth), and each attention's update would have 12 times
# the embedding's variance, under which the expert layer's part of a logit
# is lost.  At 0.5 the scores are N(0, 9): a few keys carry a query's
# weight (Keye's head norms make N(0, 16) for the same reason, PERF.md
# section 6, PR 42), a flat softmax cannot pass for it, and an update is 3
# times the embedding's variance.  A trained model's gains do this work.
LATENT_GAIN = 0.5


def param_shapes(cfg: LongcatFlashConfig) -> Dict[str, Any]:
    """The parameter tree's shapes, kernels ``[in, out]``.  ``sublayers``:
    what the published model holds twice a layer (``self_attn.{0,1}.*``,
    ``mlps.{0,1}.*``, ``input_layernorm.{0,1}``,
    ``post_attention_layernorm.{0,1}``), each leaf with ONE leading axis of
    ``2 L``, sub-layer ``s`` of layer ``l`` at ``2 l + s``; ``router``
    (``mlp.router.classifier``, ``mlp.router.e_score_correction_bias``)
    with a leading layer axis; ``experts`` (``mlp.experts.N``) with the
    axis of the experts HELD behind it, ``[L, E_here, in, out]``."""
    d, H, L, S = cfg.hidden_size, cfg.num_attention_heads, cfg.num_layers, \
        cfg.sublayers
    dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    dkv = cfg.qk_nope_head_dim + cfg.v_head_dim

    def mlp(width, *lead):
        return {"gate_proj": (*lead, d, width), "up_proj": (*lead, d, width),
                "down_proj": (*lead, width, d)}

    sublayers = {n: (S, d) for n in BLOCK_NORMS}
    sublayers.update(
        q_a_proj=(S, d, cfg.q_lora_rank),
        q_a_layernorm=(S, cfg.q_lora_rank),
        q_b_proj=(S, cfg.q_lora_rank, H * dq),
        kv_a_proj_with_mqa=(S, d, cfg.latent_dim),
        kv_a_layernorm=(S, cfg.kv_lora_rank),
        kv_b_proj=(S, cfg.kv_lora_rank, H * dkv),
        o_proj=(S, H * cfg.v_head_dim, d), **mlp(cfg.ffn_hidden_size, S))
    return {"embed_tokens": (cfg.vocab_size, d), "sublayers": sublayers,
            "router": {"classifier": (L, d, cfg.router_outputs),
                       "e_score_correction_bias": (L, cfg.router_outputs)},
            "experts": mlp(cfg.expert_ffn_hidden_size, L, cfg.experts_held),
            "norm": (d,), "lm_head": (d, cfg.vocab_size)}


def param_count(cfg: LongcatFlashConfig) -> int:
    return count_values(param_shapes(cfg))


def published_param_count(cfg: LongcatFlashConfig, num_layers: int,
                          vocab_size: int) -> int:
    """The values of the UNCUT model of these widths: ``num_layers``
    layers with every real expert, the whole vocabulary."""
    return param_count(dataclasses.replace(
        cfg, num_layers=num_layers, vocab_size=vocab_size, experts_first=0,
        experts_held=cfg.n_routed_experts))


def _norm_gain(name: str):
    if name == "kv_a_layernorm":
        return LATENT_GAIN
    return 1.0 if name in BLOCK_NORMS + LATENT_NORMS or name == "norm" \
        else None


def _bias(key, shape):
    """``e_score_correction_bias``: a buffer the published model fills in
    training; seeded with a spread of the order of a score (one over the
    router's outputs), so that selecting by ``p + b`` and weighting by
    ``p`` are two things."""
    return jax.random.normal(key, shape, jnp.float32) / shape[-1]


def seeded_params(cfg: LongcatFlashConfig, seed) -> Dict[str, Any]:
    """`mla_moe.seeded_tree`: on the device, leaf by leaf."""
    return seeded_tree(param_shapes(cfg), seed, cfg.dtype, _norm_gain,
                       {"e_score_correction_bias": _bias})


def load_checkpoint(path: str, cfg: LongcatFlashConfig):
    raise NotImplementedError(
        f"{path}: no reader for a longcat_flash state dict yet (this "
        f"family is served from seeded weights: a share of 560 B "
        f"parameters is not a file anybody has); remove the file or "
        f"serve another model")


# --- the layer ------------------------------------------------------------

def _attention(cfg: LongcatFlashConfig, sp, s: int, x, positions, index,
               first, cache, slot, absorbed: bool, prefix: int = 0):
    """``x + MLA_s(N_in_s(x))`` and the cache with this call's latent in
    ``slot``: `mla_moe._self_attn` with this family's two scales."""
    with jax.named_scope(f"input_layernorm_{s}"):
        n = _rms_norm(x, sp["input_layernorm"], cfg.rms_norm_eps)
    with jax.named_scope(f"self_attn_{s}"):
        a, cache = _self_attn(cfg, sp, n, positions, index, first, cache,
                              slot, absorbed, cfg.q_scale, cfg.kv_scale,
                              prefix)
    return x + a, cache


def _moe(cfg: LongcatFlashConfig, rp, experts, l, u):
    """The expert layer on ``u [B, N, d]``: this chip's real experts'
    part (`mla_moe._routed`) plus the zero experts' (the token's chosen
    zero weights, summed, times ``u``); and its routing: what the router
    selected by ``[B, N, E + Z]``, chose and weighted ``[B, N, k]``, and
    the counts (a row's pairs to experts held here and to zero experts
    ``[B]``, then `_routed`'s hits, dropped, rows computed)."""
    B, N, d = u.shape
    x = u.reshape(B * N, d)
    with jax.named_scope("router"):
        bias = rp["e_score_correction_bias"].astype(jnp.float32)
        scores, chosen, weights = route(cfg, matrix(rp["classifier"]), x,
                                        bias)
        selected_by = scores + bias
    y, pairs, *counts = _routed(cfg, experts, l, x, chosen, weights)
    with jax.named_scope("zero_experts"):
        zero = chosen >= cfg.n_routed_experts
        y = y + jnp.sum(jnp.where(zero, weights, 0.0), axis=-1,
                        keepdims=True) * x
        zeros = jnp.sum(zero, axis=-1, dtype=jnp.int32)
    per_row = tuple(c.reshape(B, N).sum(axis=1) for c in (pairs, zeros))
    return y.reshape(B, N, d), (
        tuple(r.reshape(B, N, -1) for r in (selected_by, chosen, weights)),
        (*per_row, *counts))


def _stack(cfg: LongcatFlashConfig, params, x, index, first, cache,
           absorbed: bool, prefix: int = 0):
    """Every layer held.  ``cache`` is the ``[2 L, B, T, 576]`` latent
    buffer (behind a shared prefix `from_prefix`'s, which holds the first
    ``prefix`` of a row's real entries already: a call behind them writes a
    row's own entries alone); each attention writes this call's entries
    into its own slot at the buffer indices ``index [N]`` (consecutive, the
    same for every row); row ``b``'s real entries start at ``first[b]``,
    its position 0.
    The scan walks the layer INDEX with the stacked leaves closed over
    (`looplm.layer_of`: a few-row product streams its leaf in place; the
    two sub-layers of a layer are two indices into one leaf).  Returns the
    normed last state, the cache, the routers' ``(selected_by
    [B, N, L, E + Z], choices [B, N, L, k], weights [B, N, L, k])`` and
    the routing counts summed over the layers (local pairs ``[B]``, zero
    pairs ``[B]``, hits, dropped, rows computed)."""
    eps = cfg.rms_norm_eps
    positions = index[None, :] - first[:, None]

    def block(carry, l):
        x, cache = carry
        s0, s1 = (layer_of(params["sublayers"], 2 * l + s) for s in (0, 1))
        h, cache = _attention(cfg, s0, 0, x, positions, index, first, cache,
                              2 * l, absorbed, prefix)
        with jax.named_scope("post_attention_layernorm_0"):
            u = _rms_norm(h, s0["post_attention_layernorm"], eps)
        with jax.named_scope("mlp"):
            m, routing = _moe(cfg, layer_of(params["router"], l),
                              params["experts"], l, u)
        with jax.named_scope("mlps_0"):
            h = h + _gated_mlp(cfg, s0, u)
        h2, cache = _attention(cfg, s1, 1, h, positions, index, first, cache,
                               2 * l + 1, absorbed, prefix)
        with jax.named_scope("post_attention_layernorm_1"):
            n = _rms_norm(h2, s1["post_attention_layernorm"], eps)
        with jax.named_scope("mlps_1"):
            x = h2 + _gated_mlp(cfg, s1, n) + m
        return (x, cache), routing

    with jax.named_scope("layers"):
        (x, cache), (routed, counts) = jax.lax.scan(
            block, (x, cache), jnp.arange(cfg.num_layers))
    with jax.named_scope("final_norm"):
        x = _rms_norm(x, params["norm"], eps)
    return x, cache, tuple(jnp.moveaxis(r, 0, 2) for r in routed), \
        tuple(c.sum(axis=0) for c in counts)


def keys_seen(cfg: LongcatFlashConfig, length: int, index, first,
              prefix: int = 0):
    """The keys the mask of this call lets each row's queries at ``index``
    see among ``length``, summed over the attentions held: ``[B]``.
    Behind ``prefix`` positions the cache holds already, a row's OWN
    queries': a slot where the end of its prefix stands is nobody's."""
    seen = visible_keys(length, index, kv_start=first)
    if prefix:
        seen = seen & (index[None, :] >= first[:, None] + prefix)[..., None]
    return cfg.sublayers * seen.sum(axis=(1, 2), dtype=jnp.int32)


# The prompt positions ONE pass of the prefill's blocks takes.  A row's
# 2,048 positions hold 0.67 GB of float32 and bf16 temporaries at the
# published widths (the expanded keys and values of 64 heads, a 512 MB
# block of scores and its softmax, two 12,288-wide MLP halves): four rows
# at once are 2.70 GB beside 10.35 GB of weights and a resident SD1.5 on a
# 16.9 GB chip (compiled for a described v5e, PERF.md section 6, PR 49),
# two are 1.4.  Every product of a pass of 4,096 rows is compute-bound all
# the same (a v5e's ridge is 240 rows), so the rows of an execution go
# through the blocks in groups of at most this many positions, one group
# after another (`jax.lax.map`), each row's result what it is alone; the
# price is a second read of the weights the groups share.
PREFILL_POSITIONS = 4096


def rows_a_pass(rows: int, positions: int) -> int:
    """The rows of one pass of the prefill: the largest divisor of
    ``rows`` whose buffers stay within PREFILL_POSITIONS (one row where a
    single one is longer)."""
    return max(b for b in range(1, rows + 1) if rows % b == 0
               and (b == 1 or b * positions <= PREFILL_POSITIONS))


def empty_cache(cfg: LongcatFlashConfig, batch: int, length: int):
    """The latent cache: ``c_kv`` (scaled) and ``k_r`` of every position
    of every attention held, two slots a layer, and no head axis."""
    return jnp.zeros((cfg.sublayers, batch, length, cfg.latent_dim),
                     cfg.dtype)


def kv_cache_bytes(cfg: LongcatFlashConfig, batch: int, length: int) -> int:
    return cfg.sublayers * batch * length * cfg.latent_dim \
        * jnp.dtype(cfg.dtype).itemsize


# --- a prefix shared between requests ----------------------------------------
#
# What a prompt's first K ids leave behind is K latents and rotary keys in
# each of the ``2 L`` cache slots: the SNAPSHOT (`make_prefix_program`), the
# cache of one row with no axis of rows, beside what the routers CHOSE over
# those K positions (`generate`'s ``aux`` records the whole prompt's).
# Rows whose prompts start with those ids start from copies of it and
# prefill their own suffix only.  Legal because a token's position counts
# from its row's first real id (`_stack`'s ``positions``): a prefix's
# rotary key is rotated the same in every row, wherever the row's padding
# pushes it in the buffer, so the snapshot can stand at each row's own
# offset; and nothing else of the state depends on where an entry lies.

def prefix_bytes(cfg: LongcatFlashConfig, positions: int) -> int:
    """Bytes of the snapshot behind ``positions`` ids: the latent slots'
    part and the record of the experts chosen (int32)."""
    return kv_cache_bytes(cfg, 1, positions) \
        + positions * cfg.num_layers * cfg.moe_topk * 4


def make_prefix_program(cfg: LongcatFlashConfig):
    """The jitted maker of a snapshot, ``lm_prefix_state`` (NOT
    ``lm_generate``: what is counted and timed an execution is the served
    program's): ``prefix_ids [K]``, one row and no padding, through every
    layer as a prefill -> ``keys [2 L, K, 576]``, the scaled latents and
    the rotated keys as the cache slots hold them, and ``choices
    [K, L, k]``, the experts the routers chose there."""

    def lm_prefix_state(params, prefix_ids):
        K, = prefix_ids.shape
        # The row stands behind padding of its own, in a buffer of a
        # multiple of 128 positions: `xla_attention` walks its score block
        # in chunks that DIVIDE the buffer, and the cell's 1,951 ids are a
        # prime (one query a chunk: 281 ms an attention on a v5e where
        # 2,048 positions take 7.6, PERF.md section 6, PR 50).
        pad = -K % 128
        first = jnp.full((1,), pad, jnp.int32)
        ids = jnp.pad(prefix_ids, (pad, 0))[None]
        with jax.named_scope("LongcatFlash"), jax.named_scope("prefill"):
            _, cache, routed, _ = _stack(
                cfg, params, _embed(params, ids), jnp.arange(pad + K), first,
                empty_cache(cfg, 1, pad + K), absorbed=False)
        return {"keys": cache[:, 0, pad:], "choices": routed[1][0, pad:]}

    return jax.jit(lm_prefix_state)


def from_prefix(cache, prefix, first):
    """`empty_cache`'s ``cache`` with every row started from the snapshot
    ``prefix``: its K entries of every slot written at row ``b``'s own
    offset ``first[b]``, directly in front of where that row's suffix will
    be written (the padding lies in front of both, so the mask stays
    ``kv_start = first`` with no hole)."""
    with jax.named_scope("kv_cache"):
        return lm_decode.write_at_offsets(cache, prefix["keys"], first)


def _prefix_choices(prefix, first, chosen):
    """The routers' choices over the WHOLE prompt buffer behind a
    snapshot: ``chosen [B, S, L, k]`` of the suffix's call stands behind
    the snapshot's K positions, and row ``b``'s ``first[b] ... first[b] +
    K - 1`` hold the snapshot's own (over a shorter row's first suffix
    slots too: they are its prefix's end)."""
    K = prefix["choices"].shape[0]
    with jax.named_scope("router"):
        return lm_decode.write_at_offsets(
            jnp.pad(chosen, ((0, 0), (K, 0), (0, 0), (0, 0))),
            prefix["choices"], first, rows=0)


# --- the served program ---------------------------------------------------

def generate(cfg: LongcatFlashConfig, max_new_tokens: int, params,
             prompt_ids, prompt_len, seed, temperature, prefix=None
             ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array],
                        Dict[str, jax.Array]]:
    """Prefill, then ``max_new_tokens`` decode steps, for every row:
    `looplm.generate`'s contract (rows, lengths, seeds, temperatures,
    padding never attended to).  With a snapshot ``prefix`` of K ids
    (`make_prefix_program`) ``prompt_ids [B, S]`` holds what FOLLOWS them
    in every row: each row starts from the snapshot (`from_prefix`) and
    the layers run over the ``S`` positions behind it, their queries
    absorbed onto the cache slots as a decode step's are; the buffer of
    ``P = K + S`` positions is laid out ``padding | prefix | row's own
    ids``, a row's last id at ``P - 1`` as without one, so the decode
    steps and every buffer index below are what they are without.
    Returns the new ids ``[B, N]``, the
    float32 logits each was drawn from ``[B, N, V]``, ``aux`` (where those
    logits were computed: what the routers selected by, ``router_scores
    [B, N, L, E + Z]`` = ``p + b``, chose, ``expert_choices [B, N, L, k]``,
    and weighted, ``expert_weights``; and what they chose over the prompt
    buffer, ``prompt_choices [B, P, L, k]``, a row's real positions at its
    end) and ``stats``, int32.  Over the DECODE steps and the layers:
    ``expert_pairs_local [B]`` and ``expert_pairs_zero [B]`` (a row's
    pairs routed to experts held here and to zero experts; the rest of its
    ``k`` a token a layer went to absent experts), ``expert_hits``
    (distinct local experts with at least one pair, over all rows of a
    step), ``keys_attended [B]`` (what the steps' masks let a row's query
    see, over the ``2 L`` attentions).  Over the PREFILL:
    ``prefill_positions`` (every position of every row THE BLOCKS RAN
    OVER: of a prefix served from a snapshot nothing is computed and
    nothing counted, while its choices stand in ``aux``),
    ``expert_pairs_local_prefill [B]``, ``expert_pairs_zero_prefill [B]``
    (over the buffer the blocks ran over), ``expert_rows_computed_prefill``
    (the rows the experts multiplied: `_routed`'s tiles x their rows) and
    ``keys_attended_prefill [B]`` (behind a snapshot a row's own queries'
    keys: its prefix's and its own causal part).  Over both
    ``expert_pairs_dropped`` (0)."""
    B, S = prompt_ids.shape
    K = lm_decode.prefix_length(prefix)
    P = K + S
    first = S - jnp.broadcast_to(prompt_len, (B,))
    index = jnp.arange(K, P)

    def blocks(ids, first):
        """The prompt buffers ``ids [b, S]`` through the blocks: the state
        behind each row's last id, the rows' cache, what the routers
        recorded there and chose over the buffer, the counts."""
        cache = empty_cache(cfg, ids.shape[0], P + max_new_tokens)
        if prefix is not None:
            cache = from_prefix(cache, prefix, first)
        x, cache, routed, counts = _stack(
            cfg, params, _embed(params, ids), index, first, cache,
            absorbed=prefix is not None, prefix=K)
        return (x[:, S - 1:], cache, tuple(r[:, S - 1] for r in routed),
                routed[1], counts)

    def prefill():
        with jax.named_scope("prefill"):
            # every row's last real id at P - 1
            ids = jax.vmap(jnp.roll)(prompt_ids, first)
            b = rows_a_pass(B, S)
            if b == B:
                x, cache, chosen, prompt_choices, counts = blocks(ids, first)
            else:
                def rows(a):
                    return a.reshape(B, *a.shape[2:])
                x, cache, chosen, prompt_choices, counts = jax.lax.map(
                    lambda group: blocks(*group),
                    (ids.reshape(B // b, b, S), first.reshape(B // b, b)))
                x, prompt_choices = rows(x), rows(prompt_choices)
                chosen = tuple(rows(c) for c in chosen)
                cache = jnp.moveaxis(cache, 0, 1).reshape(
                    cfg.sublayers, B, *cache.shape[3:])
                counts = tuple(rows(c) if c.ndim == 2 else c.sum()
                               for c in counts)
            if prefix is not None:
                prompt_choices = _prefix_choices(prefix, first,
                                                 prompt_choices)
            zeros = jnp.zeros((B,), jnp.int32)
            return (_head(cfg, params, x)[:, 0], chosen, cache,
                    (zeros, zeros, jnp.int32(0), counts[3], zeros),
                    (prompt_choices, counts,
                     keys_seen(cfg, P, index, first, K)))

    def step(token, i, cache):
        index = P + i[None]
        x, cache, routed, (*now, _) = _stack(
            cfg, params, _embed(params, token[:, None]), index, first, cache,
            absorbed=True)
        return (_head(cfg, params, x)[:, 0], tuple(r[:, 0] for r in routed),
                cache, (*now, keys_seen(cfg, cache.shape[2], index, first)))

    tokens, logits, (scores, choices, weights), \
        (pairs, zeros, hits, dropped, keys), \
        (prompt_choices, (prefill_pairs, prefill_zeros, _, _, prefill_rows),
         prefill_keys) = lm_decode.generate(
            "LongcatFlash", B, prefill, step, max_new_tokens, seed,
            temperature)
    return (tokens, logits,
            {"router_scores": scores, "expert_choices": choices,
             "expert_weights": weights, "prompt_choices": prompt_choices},
            {"expert_pairs_local": pairs, "expert_pairs_zero": zeros,
             "expert_hits": hits, "expert_pairs_dropped": dropped,
             "keys_attended": keys,
             "prefill_positions": jnp.int32(B * S),
             "expert_pairs_local_prefill": prefill_pairs,
             "expert_pairs_zero_prefill": prefill_zeros,
             "expert_rows_computed_prefill": prefill_rows,
             "keys_attended_prefill": prefill_keys})


def make_program(cfg: LongcatFlashConfig, max_new_tokens: int):
    """The jitted program, named ``lm_generate`` (``jit_lm_generate`` in a
    device trace) like every language model's.  With a sixth argument,
    `make_prefix_program`'s snapshot, ``prompt_ids`` holds what follows
    the prefix."""
    return lm_decode.make_program(
        functools.partial(generate, cfg, max_new_tokens))


def window_counters(cfg: LongcatFlashConfig, stats, real: int, steps: int
                    ) -> Dict[str, int]:
    """The ``lm.*`` window counters of one execution from its fetched
    ``stats``: `mla_moe.routing_counters`' (``lm.expert_pairs`` is ``k`` a
    token a layer: the local, the zero and the absent ones), the ``real``
    rows' pairs to zero experts and the keys their decode steps attended
    to (a padded row repeats the first and is nobody's), and what the
    prefill computed for EVERY row of the program."""
    return {
        **routing_counters(cfg, stats, real, steps),
        "lm.expert_pairs_zero": int(stats["expert_pairs_zero"][:real].sum()),
        "lm.expert_pairs_zero_prefill": int(
            stats["expert_pairs_zero_prefill"].sum()),
        "lm.prefill_positions": int(stats["prefill_positions"]),
        "lm.keys_attended": int(stats["keys_attended"][:real].sum()),
        "lm.keys_attended_prefill": int(
            stats["keys_attended_prefill"].sum())}
