"""A decoder-hybrid-decoder (SambaY, arXiv:2507.06607; the cross-decoder
is YOCO's, arXiv:2405.05254): a FRONT of Mamba-1 (arXiv:2312.00752) and
window attention layers that sees every position, and a BACK whose layers
own no state: they gate ONE state-space memory and attend to ONE
key-value cache, both made in the middle of the stack
(microsoft/Phi-4-mini-flash-reasoning, ``model_type`` ``phi4flash``),
served WHOLE on one chip.  With ``n`` layers, layer ``l`` is

    l even, l < n/2       ``mamba``   Mamba-1
    l odd,  l < n/2       ``swa``     differential attention, window W
    l = n/2               ``memory``  Mamba-1; its scan output y, BEFORE
                                      the output gate, is the memory m
    l = n/2 + 1           ``full``    differential attention, causal; its
                                      keys and values are THE cache
    l even, l > n/2 + 1   ``gmu``     gated memory unit over m
    l odd,  l > n/2 + 1   ``cross``   differential attention, a query
                                      projection alone, over THE cache

    x = E[ids]                              # no multiplier, NO positions
    every layer:  h = x + Mixer(LN_1(x));  x = h + MLP(LN_2(h))
    MLP(u) = (b * silu(a)) W_2,  [a | b] = u W_1        # fc1, fc2
    logits = LN_f(x) E^T                    # tied; LN: gain and bias

    Mamba-1:  [u | z] = v W_in;  u = silu(conv(u))      # 4 taps, bias
      [r | B | C] = u W_x;  dt = softplus(r W_dt + b_dt);  A = -exp(A_log)
      s_t = exp(dt_t (x) A) * s_{t-1} + (dt_t * u_t) (x) B_t    # float32
      y_t = s_t C_t + D * u_t;  out = (y * silu(z)) W_out;  m_t = y_t
    GMU:  out = (m_t * silu(v W_1)) W_2                 # in_proj, out_proj
    differential attention (H query, G key-value heads of D; H / 2
    differential heads over G / 2 key-value PAIRS):  head j reads query
    heads 2j, 2j + 1 and, with g = j // 2, key heads 2g, 2g + 1 and the
    value V_g = [v_2g | v_2g+1]:
      o_j = RMSNorm_2D((P_1 - lam P_2) V_g) * gamma * (1 - lam_0),
      P_1 = softmax(q_2j k_2g^T / sqrt(D)),  P_2 = softmax(q_2j+1 k_2g+1^T
      / sqrt(D)),  lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_0,
      lam_0 = 0.8 - 0.6 exp(-0.3 l);  W_o concat_j(o_j) + b_o

**State shared across layers, of three geometries.**  A Mamba layer keeps
``s`` (float32, ``[L_m, B, N, d_inner]``: the channels on the last axis)
and the last ``d_conv - 1`` inputs of its convolution, both OVERWRITTEN
in place; a window layer a RING of ``W`` slots (`swa_moe.ring_positions`);
layer ``n/2 + 1`` ONE cache indexed by position, which it and every
``cross`` layer read.  The memory ``m`` is recomputed every step and kept
nowhere.

**Prefill and decode do not traverse the same depth.**  The front (layers
``0 .. n/2``) and layer ``n/2 + 1``'s key-value projection see every
prompt position; that layer's attention and everything behind it are
computed for each row's LAST position alone (no other position's output
is read: exact).  The front walks the positions in CHUNKS of
``prefill_chunk``, one ``lax.scan`` a layer: a Mamba layer carries its
state and tail from chunk to chunk (inside a chunk the recurrence
position by position, `selective_scan`: the decay is a (channel, state)
pair's own, so no matrix form exists), a window layer the last ``W`` keys
and values, so a chunk's queries meet ``W`` + chunk keys under the band's
mask and never the square.  A decode step is a chunk of one position on
the resident state.

**A differential head pair as one grouped call.**  Query head ``h`` = 2j
+ s meets key head 2 (h // 4) + s.  Its ``D`` values are placed in half
``s`` of a ``2 D``-wide query, zeros in the other: against the PAIR's
keys ``[k_2g | k_2g+1]`` that is its own score (the zeros add exactly
nothing), and ``softmax(.) V_g`` its map times the pair's value.  So one
`swa_moe._attend` with ``G / 2`` heads of ``2 D`` gives every map's
output; the head pair's difference, its RMSNorm and gain follow.  The
caches hold the pairs as they are read (``[.., G / 2, 2 D]``: a reshape
of the published heads).

**Padding** is `ssm_hybrid`'s rule: rows are right-aligned, a Mamba
mixer's input is zeroed at a row's padded positions and ``dt`` forced to
0 there; the attention masks hide padded keys.

**A prefix shared between requests.**  What a prompt's first ``K`` ids
leave behind is one state and tail a Mamba layer, the last ``W`` keys and
values a window layer and ``K`` of the one cache: the SNAPSHOT
(`make_prefix_program`), one row's and with no axis of rows.  Rows whose
prompts start with those ids start from it and the front walks what
follows them alone (`prefill`'s ``prefix``).  Legal because nothing here
reads a position's index: no positional encoding, and every mask counts
from a row's first real id, so the snapshot stands wherever a row's own
offset puts it.

Precision: weights, caches, tails and matmul operands in ``cfg.dtype``;
the residual stream, every norm, the softmax, the logits, ``dt``, every
``exp`` of a decay and the recurrent state in float32.

Scopes: the layer's kind under ``layers`` (``mamba``, ``swa``, ``memory``,
``full``, ``gmu``, ``cross``), its mixer ``attn`` (the published
attribute, whatever the kind) and the published modules' names below it
(``Phi4Flash/decode/layers/gmu/attn/in_proj`` ...); the work that is no
product with a weight under ``selective_scan``, ``inner_attn``,
``inner_cross_attn`` and ``gate``: read by ``utils/trace.KERNEL_CLASSES``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from comfyui_distributed_tpu.models import lm_decode, ssm_hybrid
from comfyui_distributed_tpu.models.layers import ATTENTION_PATHS, \
    attention_path, visible_keys
from comfyui_distributed_tpu.models.looplm import Stacked, _dense, \
    _rms_norm, dense_tied, few_rows_here, layer_of, matrix, \
    scan_layers  # noqa: F401
from comfyui_distributed_tpu.models.mla_moe import count_values, seeded_tree
from comfyui_distributed_tpu.models.ssm_hybrid import A_RANGE, _dt_bias, \
    _real_only, causal_conv
from comfyui_distributed_tpu.models.swa_moe import _attend, ring_positions

MAMBA, SWA, MEMORY, FULL, GMU, CROSS = \
    "mamba", "swa", "memory", "full", "gmu", "cross"
# the stacked leaves: a layer's kind -> its stack (``memory`` is the last
# of the Mamba layers, ``full`` a stack of one)
STACKS = {MAMBA: "mamba_layers", SWA: "swa_layers", MEMORY: "mamba_layers",
          FULL: "full_layers", GMU: "gmu_layers", CROSS: "cross_layers"}


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """The shape keys of the model's ``config.json``, under its names, and
    the four Mamba-1 sizes it does not carry (the family's defaults)."""
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    sliding_window: int
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    max_position_embeddings: int = 262144
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    prefill_chunk: int = 512        # positions a front layer walks at once
    dtype: Any = jnp.bfloat16       # weights, caches, tails, operands
    state_dtype: Any = jnp.float32  # the recurrent state

    def __post_init__(self):
        if self.num_hidden_layers % 4 or self.num_hidden_layers < 8 \
                or self.mb_per_layer != 2:
            raise ValueError(
                f"{self.num_hidden_layers} layers with a Mamba layer every "
                f"{self.mb_per_layer}: the front alternates Mamba and "
                f"window layers (a multiple of 4 layers, at least 8)")
        if self.num_attention_heads % 4 or self.num_key_value_heads * 2 \
                != self.num_attention_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads over "
                f"{self.num_key_value_heads} key-value heads are not "
                f"differential heads of two queries over pairs of keys")
        if not self.tie_word_embeddings or self.mlp_bias \
                or self.lm_head_bias:
            raise ValueError("an untied head or a bias on the MLP or the "
                             "head is not implemented")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_pairs(self) -> int:
        """Pairs of key-value heads: what the caches hold a position."""
        return self.num_key_value_heads // 2

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def layer_applications(self) -> int:
        """Layers one decoded token passes through."""
        return self.num_hidden_layers

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        half = self.num_hidden_layers // 2
        front = (MAMBA, SWA) * (half // 2)
        return front + (MEMORY, FULL) + (GMU, CROSS) * (half // 2 - 1)

    def layers_of(self, kind: str) -> int:
        return self.layer_kinds.count(kind)


# microsoft/Phi-4-mini-flash-reasoning config.json, nothing reduced
PHI_4_MINI_FLASH = Phi4FlashConfig(
    vocab_size=200064, hidden_size=2560, intermediate_size=10240,
    num_hidden_layers=32, num_attention_heads=40, num_key_value_heads=20,
    sliding_window=512, mb_per_layer=2, layer_norm_eps=1e-5,
    max_position_embeddings=262144)

# the CPU tests' and the rehearsal's size (fp32: deterministic
# comparisons): every kind occurs (layers 0..3 the front, 4 the memory, 5
# the cache, 6 and 7 the back); a window of 8 walked in chunks of 6
TINY_SAMBAY = Phi4FlashConfig(
    vocab_size=512, hidden_size=64, intermediate_size=96,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
    sliding_window=8, mamba_d_state=4, mamba_dt_rank=4, prefill_chunk=6,
    dtype=jnp.float32)

CONFIGS = {"full": PHI_4_MINI_FLASH, "tiny": TINY_SAMBAY}

NORMS = ("input_layernorm", "post_attention_layernorm", "final_layernorm",
         "subln")
LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
# No multiplier stands between the tied embedding and the stream: with
# unit normals the head would read x_0 = E[id] back out of it, the last
# id's own logit six standard deviations over the rest at every step.
EMBED_STD = 0.02
LAMBDA_STD = 0.1


def param_shapes(cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """The parameter tree's shapes, kernels ``[in, out]``: the layers of
    each kind stacked on a leading axis, each with its two LayerNorms and
    its MLP (``fc1``, ``fc2``).  No ``lm_head``: the head is
    ``embed_tokens``.  ``conv1d_weight`` is ``[L, taps, channels]`` as
    `ssm_hybrid`'s; ``A_log`` ``[L, d_inner, N]`` as published."""
    d, f, D = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    H, G = cfg.num_attention_heads, cfg.num_key_value_heads
    C, N, R = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank

    def layer(L):
        norms = {f"{n}{part}": (L, d)
                 for n in ("input_layernorm", "post_attention_layernorm")
                 for part in ("", "_bias")}
        return {**norms, "fc1": (L, d, 2 * f), "fc2": (L, f, d)}

    def attention(L, columns):
        return {**layer(L), "Wqkv": (L, d, columns),
                "Wqkv_bias": (L, columns), "out_proj": (L, H * D, d),
                "out_proj_bias": (L, d), "subln": (L, 2 * D),
                **{n: (L, D) for n in LAMBDAS}}

    def mamba(L):
        return {**layer(L), "in_proj": (L, d, 2 * C),
                "conv1d_weight": (L, cfg.mamba_d_conv, C),
                "conv1d_bias": (L, C), "x_proj": (L, C, R + 2 * N),
                "dt_proj": (L, R, C), "dt_proj_bias": (L, C),
                "A_log": (L, C, N), "D": (L, C), "out_proj": (L, C, d)}

    def gmu(L):
        return {**layer(L), "in_proj": (L, d, C), "out_proj": (L, C, d)}

    return {"embed_tokens": (cfg.vocab_size, d),
            "mamba_layers": mamba(cfg.layers_of(MAMBA) + 1),
            "swa_layers": attention(cfg.layers_of(SWA), (H + 2 * G) * D),
            "full_layers": attention(1, (H + 2 * G) * D),
            "gmu_layers": gmu(cfg.layers_of(GMU)),
            "cross_layers": attention(cfg.layers_of(CROSS), H * D),
            "final_layernorm": (d,), "final_layernorm_bias": (d,)}


def param_count(cfg: Phi4FlashConfig) -> int:
    return count_values(param_shapes(cfg))


def _small(std):
    return lambda k, s: std * jax.random.normal(k, s, jnp.float32)


# the leaves that are neither kernels nor norm gains: Mamba's own
# initialisation (A = 1..N a channel, dt log-uniform in 1e-3..1e-1, D =
# 1: drawn as normals exp(dt A) would be 0 or 1 nearly everywhere and no
# comparison could see a wrong state), small biases, the four lambda
# vectors N(0, 0.1) as the Differential Transformer seeds them
DRAWS = {
    "embed_tokens": _small(EMBED_STD),
    "A_log": lambda k, s: jnp.log(jnp.broadcast_to(
        jnp.arange(A_RANGE[0], s[-1] + 1.0, dtype=jnp.float32), s)),
    "dt_proj_bias": _dt_bias,
    "D": lambda k, s: jnp.ones(s, jnp.float32),
    **{n: _small(LAMBDA_STD) for n in LAMBDAS},
    **{n: _small(0.1) for n in (
        "conv1d_bias", "Wqkv_bias", "out_proj_bias", "input_layernorm_bias",
        "post_attention_layernorm_bias", "final_layernorm_bias")},
}


def seeded_params(cfg: Phi4FlashConfig, seed) -> Dict[str, Any]:
    """`mla_moe.seeded_tree`: on the device, leaf by leaf; kernels (the
    convolution's taps among them) normals scaled by fan-in, norm gains 1
    + 0.1 N, and DRAWS for the leaves that are neither."""
    return seeded_tree(param_shapes(cfg), seed, cfg.dtype,
                       lambda name: 1.0 if name in NORMS else None, DRAWS)


def load_checkpoint(path: str, cfg: Phi4FlashConfig):
    raise NotImplementedError(
        f"{path}: no reader for a phi4flash state dict yet (this family is "
        f"served from seeded weights); remove the file or serve another "
        f"model")


# --- what every layer has ---------------------------------------------------

def _layer_norm(x, gain, bias, eps):
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32) \
        + bias.astype(jnp.float32)


def _normed(cfg: Phi4FlashConfig, lp, x, name: str):
    with jax.named_scope(name):
        return _layer_norm(x, lp[name], lp[f"{name}_bias"],
                           cfg.layer_norm_eps)


def _biased(x, lp, name: str, cfg):
    return _dense(x, lp[name], cfg) + lp[f"{name}_bias"].astype(jnp.float32)


def _mlp(cfg: Phi4FlashConfig, lp, u):
    with jax.named_scope("mlp"):
        with jax.named_scope("fc1"):
            a, b = jnp.split(_dense(u, lp["fc1"], cfg), 2, axis=-1)
        with jax.named_scope("fc2"):
            return _dense(b * jax.nn.silu(a), lp["fc2"], cfg)


def _layer(cfg: Phi4FlashConfig, lp, x, mixer):
    """``h = x + Mixer(LN_1(x))``, ``x' = h + MLP(LN_2(h))``; ``mixer(u)``
    -> its output and whatever else it hands on."""
    m, *rest = mixer(_normed(cfg, lp, x, "input_layernorm"))
    h = x + m
    return (h + _mlp(cfg, lp, _normed(cfg, lp, h,
                                      "post_attention_layernorm")), *rest)


def _resident(lp):
    """A layer's weights as matrices: what a prefill's chunks multiply
    (a `Stacked` leaf is sliced once a layer, not once a chunk)."""
    return jax.tree_util.tree_map(
        matrix, lp, is_leaf=lambda w: isinstance(w, Stacked))


# --- the Mamba-1 mixer and the gated memory unit ----------------------------

# Positions a trip of `selective_scan`'s loop: a position is a handful of
# small operations whose cost is their launches, and XLA fuses the
# positions of one trip.  At 4 rows x 5,120 channels x 16 states on a v5e
# a position takes 5.83 us at 1, 2.26 at 2, 2.14 at 8, 2.04 at 16 (my
# chip run, PR 46: PERF.md section 6); the numbers are the same bit for bit.
SCAN_UNROLL = 8


def selective_scan(u, dt, A, Bm, Cm, start):
    """The recurrence ``s_t = exp(dt_t (x) A) * s_{t-1} + (dt_t * u_t)
    (x) B_t``, ``y_t = s_t C_t`` position by position from ``s = start
    [B, N, C]``, all in float32: ``u``, ``dt [B, Q, C]`` (``dt`` 0 where a
    position is padding: the state then neither decays nor takes input),
    ``A [N, C]`` negative, ``Bm``, ``Cm [B, Q, N]``.  Returns ``y [B, Q,
    C]`` and the state behind the last position."""
    def step(s, now):
        u_t, dt_t, B_t, C_t = now
        s = jnp.exp(dt_t[:, None, :] * A) * s \
            + (dt_t * u_t)[:, None, :] * B_t[:, :, None]
        return s, jnp.sum(s * C_t[:, :, None], axis=1)

    if u.shape[1] == 1:
        s, y = step(start, (u[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0]))
        return y[:, None], s
    s, y = jax.lax.scan(step, start, tuple(
        a.swapaxes(0, 1) for a in (u, dt, Bm, Cm)), unroll=SCAN_UNROLL)
    return y.swapaxes(0, 1), s


def _mamba(cfg: Phi4FlashConfig, lp, v, real, s, tail):
    """The mixer over ``v [B, Q, d]`` (normed) FROM the state ``s [B, N,
    C]`` and the convolution's tail (the ``taps - 1`` inputs in front of
    these positions): its output, the MEMORY ``y`` (the scan's output
    with the ``D`` skip, before the gate), the new state and tail.
    ``real [B, Q]`` says which positions of a prefill are a row's own
    (None: a decode step, every row's): what lies in front of them is
    padding, and the tail stands directly in front of a row's FIRST own
    position (a chunk in which a row has not begun hands its tail on)."""
    C, N, R = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    f32 = jnp.float32
    with jax.named_scope("attn"):
        if real is not None:
            v = _real_only(real, v)
        with jax.named_scope("in_proj"):
            uz = _dense(v, lp["in_proj"], cfg).astype(cfg.dtype)
        u, z = uz[..., :C], uz[..., C:]
        with jax.named_scope("conv1d"):
            u, tail = causal_conv(
                u, matrix(lp["conv1d_weight"]), lp["conv1d_bias"], tail,
                None if real is None else jnp.sum(~real, axis=1))
            u = jax.nn.silu(u)
        with jax.named_scope("x_proj"):
            rbc = _dense(u, lp["x_proj"], cfg)
        r, Bm, Cm = rbc[..., :R], rbc[..., R:R + N], rbc[..., R + N:]
        with jax.named_scope("dt_proj"):
            dt = jax.nn.softplus(_biased(r, lp, "dt_proj", cfg))
        with jax.named_scope("selective_scan"):
            if real is not None:
                dt = _real_only(real, dt)
            A = -jnp.exp(matrix(lp["A_log"]).astype(f32)).T
            y, s = selective_scan(u, dt, A, Bm, Cm, s.astype(f32))
            y = y + lp["D"].astype(f32) * u
            gated = (y * jax.nn.silu(z.astype(f32))).astype(cfg.dtype)
        with jax.named_scope("out_proj"):
            return _dense(gated, lp["out_proj"], cfg), y, s, tail


def _gmu(cfg: Phi4FlashConfig, lp, v, memory):
    """``(m * silu(v W_1)) W_2`` with the memory ``m [B, Q, C]`` of the
    SAME positions."""
    with jax.named_scope("attn"):
        with jax.named_scope("in_proj"):
            gate = _dense(v, lp["in_proj"], cfg)
        with jax.named_scope("gate"):
            # (rounded HERE to the product's operand type: the pass over
            # the memory is then this scope's, not ``out_proj``'s)
            gated = (memory * jax.nn.silu(gate)).astype(cfg.dtype)
        with jax.named_scope("out_proj"):
            return _dense(gated, lp["out_proj"], cfg)


# --- differential attention -------------------------------------------------

def _heads(cfg: Phi4FlashConfig, t, width: int):
    """``[B, Q, heads * width]`` as ``[B, Q, heads, width]`` in the
    model's dtype."""
    return t.reshape(*t.shape[:2], -1, width).astype(cfg.dtype)


def _qkv(cfg: Phi4FlashConfig, lp, v):
    """This call's queries ``[B, Q, H, D]`` and its keys and values as the
    caches hold them, PAIRS of heads ``[B, Q, G / 2, 2 D]``."""
    D, H = cfg.head_dim, cfg.num_attention_heads
    with jax.named_scope("Wqkv"):
        qkv = _biased(v, lp, "Wqkv", cfg)
    q, k, v = jnp.split(qkv, [H * D, (H + cfg.num_key_value_heads) * D],
                        axis=-1)
    return _heads(cfg, q, D), _heads(cfg, k, 2 * D), _heads(cfg, v, 2 * D)


def _some_columns(cfg: Phi4FlashConfig, lp, u, columns: slice):
    """``u`` times ``columns`` of ``Wqkv`` alone, with their bias: the
    full layer's keys and values over a whole prefill, its queries for
    the last position."""
    with jax.named_scope("Wqkv"):
        return jnp.dot(u.astype(cfg.dtype), matrix(lp["Wqkv"])[:, columns],
                       preferred_element_type=jnp.float32) \
            + lp["Wqkv_bias"][columns].astype(jnp.float32)


def _differential(cfg: Phi4FlashConfig, lp, q, k, v, l, q_positions,
                  scope: str = "inner_attn", **mask):
    """Every differential head of ``q [B, Q, H, D]`` (at ``q_positions
    [Q]``) over the pairs ``k``, ``v [B, M, G / 2, 2 D]`` (any storage
    type) under `visible_keys`' ``mask``, then ``out_proj``.  ``l`` is
    the layer's index in the model (``lam_0`` reads it)."""
    B, Q, H, D = q.shape
    f32 = jnp.float32
    with jax.named_scope(scope):
        # head h = 2 j + s: its D values in half s of the pair's 2 D
        wide = (q[:, :, :, None, :] * jnp.tile(
            jnp.eye(2, dtype=q.dtype), (H // 2, 1))[:, :, None]
        ).reshape(B, Q, H, 2 * D)
        ATTENTION_PATHS.bump(attention_path(
            jax.default_backend(), B, Q, k.shape[1], H, masked=True,
            banded=mask.get("window") is not None))
        maps = _attend(wide, k.astype(cfg.dtype), v.astype(cfg.dtype),
                       q_positions, scale=1.0 / math.sqrt(D), **mask
                       ).reshape(B, Q, H // 2, 2, 2 * D).astype(f32)
        lq1, lk1, lq2, lk2 = (lp[n].astype(f32) for n in LAMBDAS)
        lam_0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(l, f32))
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) \
            + lam_0
        o = maps[..., 0, :] - lam * maps[..., 1, :]
    with jax.named_scope("subln"):
        o = _rms_norm(o, lp["subln"], cfg.layer_norm_eps) * (1.0 - lam_0)
    with jax.named_scope("out_proj"):
        return _biased(o.reshape(B, Q, -1), lp, "out_proj", cfg)


def _behind_prefix(held, last, slot):
    """``held [B, M, ...]`` with, wherever ``slot [B, M]`` names one of
    the ``W`` slots of ``last [W, ...]`` (a prefix's last ``W`` entries in
    position order), that entry: a row's prefix stands in front of its own
    ids, and those do not begin at the same index in every row."""
    W = last.shape[0]
    inside = (slot >= 0) & (slot < W)
    return jnp.where(inside[..., None, None],
                     last[jnp.clip(slot, 0, W - 1)].astype(held.dtype), held)


def _window(cfg: Phi4FlashConfig, lp, u, l, at, first, held, prefix=None):
    """A window layer's mixer over one CHUNK of a prefill: ``u [B, Q, d]``
    at the positions ``at [Q]`` against ``held`` (the ``W`` keys and
    values in front of the chunk, ``(k, v)`` each ``[B, W, G / 2, 2 D]``)
    and its own.  Behind a shared prefix, ``prefix`` is the layer's keys
    and values of the prefix's last ``W`` positions ``[W, G / 2, 2 D]``
    and ``own [B]``, the position at which row ``b``'s own ids begin: they
    stand at ``own[b] - W .. own[b] - 1``, over the zeros in front of the
    first chunk and over what the chunk made of a row's padding (a row's
    own query at ``p`` sees no key in front of ``p - W + 1``, so ``W`` of
    them are all it can ask for).  Returns the output and the last ``W``
    of both."""
    W = cfg.sliding_window
    with jax.named_scope("attn"):
        q, k, v = _qkv(cfg, lp, u)
        with jax.named_scope("kv_cache"):
            k, v = (jnp.concatenate([h, t], axis=1)
                    for h, t in zip(held, (k, v)))
            if prefix is not None:
                *last, own = prefix
                # entry n stands at position at[0] - W + n
                slot = at[0] + jnp.arange(k.shape[1]) - own[:, None]
                k, v = (_behind_prefix(t, p, slot)
                        for t, p in zip((k, v), last))
        out = _differential(
            cfg, lp, q, k, v, l, at, kv_start=first, window=W,
            kv_positions=at[0] - W + jnp.arange(k.shape[1]))
        return out, (k[:, -W:], v[:, -W:])


def ring_mask(cfg: Phi4FlashConfig, index, first) -> Dict[str, Any]:
    """What a query at the buffer index ``index [1]`` sees of a ring."""
    W = cfg.sliding_window
    return {"kv_start": first, "window": W,
            "kv_positions": ring_positions(index[0], W)}


def _ring(cfg: Phi4FlashConfig, lp, u, l, index, first, rk, rv, i):
    """A window layer's mixer for ONE new position at the buffer index
    ``index [1]``: its key and value written into slot ``index mod W`` of
    ring ``i``, the query against the ring.  Returns the output and the
    rings."""
    with jax.named_scope("attn"):
        q, k, v = _qkv(cfg, lp, u)
        with jax.named_scope("kv_cache"):
            rk, rv = (jax.lax.dynamic_update_slice(
                c, t[None].astype(c.dtype),
                (i, 0, index[0] % cfg.sliding_window, 0, 0))
                for c, t in ((rk, k), (rv, v)))
            k, v = (jax.lax.dynamic_index_in_dim(c, i, keepdims=False)
                    for c in (rk, rv))
        return _differential(cfg, lp, q, k, v, l, index,
                             **ring_mask(cfg, index, first)), rk, rv


def _cross(cfg: Phi4FlashConfig, lp, u, l, index, first, kc, vc):
    """A ``cross`` layer's mixer: a query projection of its own over the
    cache layer ``n/2 + 1`` wrote, as far as ``index [1]``."""
    with jax.named_scope("attn"):
        with jax.named_scope("Wqkv"):
            q = _heads(cfg, _biased(u, lp, "Wqkv", cfg), cfg.head_dim)
        return _differential(cfg, lp, q, kc, vc, l, index,
                             "inner_cross_attn", kv_start=first)


# --- the two halves -----------------------------------------------------------

def _chunks(a, chunk: int):
    """``[B, S, ...]`` as ``[S / chunk, B, chunk, ...]``: what a layer's
    scan over a prefill walks."""
    B, S = a.shape[:2]
    return a.reshape(B, S // chunk, chunk, *a.shape[2:]).swapaxes(0, 1)


def _unchunked(a):
    c, B, Q = a.shape[:3]
    return a.swapaxes(0, 1).reshape(B, c * Q, *a.shape[3:])


def _mamba_prefill(cfg: Phi4FlashConfig, lp, x, real, Q: int, s, tail):
    """A Mamba layer over a whole prefill ``x [B, S, d]``, ``Q``
    positions a chunk, FROM the state ``s`` and the tail ``tail`` (zeros
    in front of a whole prompt, a snapshot's behind a shared prefix): the
    stream behind it, the memory of each row's LAST position ``[B, 1,
    C]``, the state and the tail behind it."""
    lp = _resident(lp)

    def chunk(carry, xs):
        x, real = xs
        x, y, *carry = _layer(
            cfg, lp, x, lambda v: _mamba(cfg, lp, v, real, *carry))
        return tuple(carry), (x, y[:, -1:])

    (s, tail), (x, memory) = jax.lax.scan(
        chunk, (s.astype(jnp.float32), tail), (_chunks(x, Q),
                                               _chunks(real, Q)))
    return _unchunked(x), memory[-1], s, tail


def _window_prefill(cfg: Phi4FlashConfig, lp, x, l, first, Q: int,
                    start=0, prefix=None):
    """A window layer over a whole prefill whose first position is
    ``start``, ``Q`` positions a chunk (`_window`, its ``prefix``): the
    stream behind it and the last ``W`` keys and values."""
    B, S, _ = x.shape
    W = cfg.sliding_window
    lp = _resident(lp)

    def chunk(held, xs):
        x, at = xs
        x, held = _layer(cfg, lp, x, lambda u: _window(
            cfg, lp, u, l, at, first, held, prefix))
        return held, x

    empty = jnp.zeros((B, W, cfg.kv_pairs, 2 * cfg.head_dim), cfg.dtype)
    held, x = jax.lax.scan(
        chunk, (empty, empty),
        (_chunks(x, Q), start + jnp.arange(S).reshape(-1, Q)))
    return _unchunked(x), held


def _set_layer(stack, l, value):
    return jax.lax.dynamic_update_slice(
        stack, value[None].astype(stack.dtype), (l,) + (0,) * value.ndim)


def _mamba_state(state, i):
    """Mamba layer ``i``'s recurrent state and tail of ``state``."""
    with jax.named_scope("ssm_state"):
        s = jax.lax.dynamic_index_in_dim(state["ssm"], i, keepdims=False)
    with jax.named_scope("conv_state"):
        tail = jax.lax.dynamic_index_in_dim(state["conv"], i,
                                            keepdims=False)
    return s, tail


def _with_mamba_state(state, i, s, tail):
    """``state`` with Mamba layer ``i``'s recurrent state and tail
    overwritten."""
    with jax.named_scope("ssm_state"):
        ssm = _set_layer(state["ssm"], i, s)
    with jax.named_scope("conv_state"):
        conv = _set_layer(state["conv"], i, tail)
    return {**state, "ssm": ssm, "conv": conv}


def _front(cfg: Phi4FlashConfig, params, x, state, step):
    """Layers ``0 .. n/2``: the pairs of a Mamba and a window layer under
    one ``lax.scan`` (`looplm.scan_layers` over the index, the stacked
    leaves closed over), then the memory layer.  ``step(kind, lp, x, i,
    state)`` runs layer ``i`` of its kind FROM ``state`` and returns the
    stream and the state with that layer's part overwritten (and, of the
    memory layer, the memory as a third).  Returns the stream, the memory
    and the state."""
    pairs = cfg.layers_of(SWA)
    leaves = {kind: params[STACKS[kind]] for kind in (MAMBA, SWA)}

    def pair(carry, xs):
        x, state = carry
        lp, i = xs
        with jax.named_scope(MAMBA):
            x, state, _ = step(MAMBA, lp[MAMBA], x, i, state)
        with jax.named_scope(SWA):
            x, state = step(SWA, lp[SWA], x, i, state)
        return (x, state), None

    with jax.named_scope("layers"):
        (x, state), _ = scan_layers(pair, (x, state), leaves, pairs, True)
        with jax.named_scope(MEMORY):
            return step(MAMBA, layer_of(params[STACKS[MEMORY]],
                                        jnp.int32(pairs)), x, pairs, state)


def _back(cfg: Phi4FlashConfig, params, x, memory, index, first, state,
          held: bool):
    """Layer ``n/2 + 1`` and everything behind it for ONE position a row,
    ``x [B, 1, d]`` at the buffer index ``index [1]`` with the memory ``m
    [B, 1, C]`` of that position: the full layer's query over the cache
    (its own key and value written first, unless the cache ``held`` them
    already: a prefill's), then the pairs of a ``gmu`` and a ``cross``
    layer under one ``lax.scan``, the final norm.  Returns the normed
    stream and the state."""
    half = cfg.num_hidden_layers // 2
    kc, vc = state["keys"], state["values"]
    columns = cfg.num_attention_heads * cfg.head_dim

    def full(u):
        nonlocal kc, vc
        with jax.named_scope("attn"):
            if held:
                q = _heads(cfg, _some_columns(cfg, lp, u, slice(columns)),
                           cfg.head_dim)
            else:
                q, k, v = _qkv(cfg, lp, u)
                with jax.named_scope("kv_cache"):
                    kc, vc = (jax.lax.dynamic_update_slice(
                        c, t.astype(c.dtype), (0, index[0], 0, 0))
                        for c, t in ((kc, k), (vc, v)))
            return (_differential(cfg, lp, q, kc, vc, half + 1, index,
                                  kv_start=first),)

    def pair(x, xs):
        lp, i = xs
        with jax.named_scope(GMU):
            x, = _layer(cfg, lp[GMU], x,
                        lambda u: (_gmu(cfg, lp[GMU], u, memory),))
        with jax.named_scope(CROSS):
            x, = _layer(cfg, lp[CROSS], x, lambda u: (_cross(
                cfg, lp[CROSS], u, half + 3 + 2 * i, index, first, kc, vc),))
        return x, None

    with jax.named_scope("layers"):
        with jax.named_scope(FULL):
            lp = layer_of(params[STACKS[FULL]], jnp.int32(0))
            x, = _layer(cfg, lp, x, full)
        x, _ = scan_layers(
            pair, x, {kind: params[STACKS[kind]] for kind in (GMU, CROSS)},
            cfg.layers_of(CROSS), True)
    with jax.named_scope("final_layernorm"):
        x = _layer_norm(x, params["final_layernorm"],
                        params["final_layernorm_bias"], cfg.layer_norm_eps)
    return x, {**state, "keys": kc, "values": vc}


def _embed(params, ids):
    with jax.named_scope("embed_tokens"):
        return params["embed_tokens"][ids].astype(jnp.float32)


def _head(cfg: Phi4FlashConfig, params, x):
    with jax.named_scope("lm_head"):
        return dense_tied(x, params["embed_tokens"], cfg)


def empty_state(cfg: Phi4FlashConfig, batch: int, length: int):
    """The state of the three geometries: ``ssm`` and ``conv`` of the
    Mamba layers (no axis of positions), the window layers' rings of
    ``sliding_window`` slots, and THE cache of ``length`` positions."""
    Lm, Ls = cfg.layers_of(MAMBA) + 1, cfg.layers_of(SWA)
    pair = (cfg.kv_pairs, 2 * cfg.head_dim)
    ring = (Ls, batch, cfg.sliding_window, *pair)
    return {"ssm": jnp.zeros((Lm, batch, cfg.mamba_d_state, cfg.d_inner),
                             cfg.state_dtype),
            "conv": jnp.zeros((Lm, batch, cfg.mamba_d_conv - 1,
                               cfg.d_inner), cfg.dtype),
            "ring_keys": jnp.zeros(ring, cfg.dtype),
            "ring_values": jnp.zeros(ring, cfg.dtype),
            "keys": jnp.zeros((batch, length, *pair), cfg.dtype),
            "values": jnp.zeros((batch, length, *pair), cfg.dtype)}


def state_bytes(cfg: Phi4FlashConfig, batch: int) -> int:
    """Bytes of the RECURRENT state (``ssm`` and the tails): no function
    of the positions."""
    per_layer = cfg.d_inner * (
        cfg.mamba_d_state * jnp.dtype(cfg.state_dtype).itemsize
        + (cfg.mamba_d_conv - 1) * jnp.dtype(cfg.dtype).itemsize)
    return (cfg.layers_of(MAMBA) + 1) * batch * per_layer


def kv_cache_bytes_by_kind(cfg: Phi4FlashConfig, batch: int, length: int
                           ) -> Dict[str, int]:
    position = 2 * batch * cfg.num_key_value_heads * cfg.head_dim \
        * jnp.dtype(cfg.dtype).itemsize
    return {"recurrent": state_bytes(cfg, batch),
            "ring": cfg.layers_of(SWA) * cfg.sliding_window * position,
            "full": length * position}


def kv_cache_bytes(cfg: Phi4FlashConfig, batch: int, length: int) -> int:
    """Bytes of the POSITIONAL state: the rings and the one cache."""
    by_kind = kv_cache_bytes_by_kind(cfg, batch, length)
    return by_kind["ring"] + by_kind["full"]


# --- a prefix shared between requests ----------------------------------------

RINGS = ("ring_keys", "ring_values")


def prefix_bytes(cfg: Phi4FlashConfig, positions: int) -> int:
    """Bytes of the snapshot behind ``positions`` ids: the states and
    tails, the rings, and the one cache's part."""
    return state_bytes(cfg, 1) + kv_cache_bytes(cfg, 1, positions)


def make_prefix_program(cfg: Phi4FlashConfig):
    """The jitted maker of a snapshot, ``lm_prefix_state`` (NOT
    ``lm_generate``: what is counted and timed an execution is the served
    program's): ``prefix_ids [K]``, one row and no padding, through the
    FRONT as a prefill and through layer ``n/2 + 1``'s key-value
    projection (the back half holds nothing to keep) -> ``ssm``, ``conv``
    behind id ``K - 1``; ``ring_keys``, ``ring_values`` ``[L_s, W, ..]``,
    the last ``W`` positions IN POSITION ORDER (slot ``i`` is position ``K
    - W + i``; zeros in front where ``K < W``: which slot of a row's ring a
    position falls in is the row's offset's to say); ``keys``, ``values``
    ``[1, K, ..]``, THE cache's."""
    W = cfg.sliding_window

    def lm_prefix_state(params, prefix_ids):
        K, = prefix_ids.shape
        with jax.named_scope("Phi4Flash"):
            _, _, state = _front_prefill(
                cfg, params, prefix_ids[None], jnp.zeros((1,), jnp.int32), K)
        held = jnp.arange(W)[:, None, None] >= W - K
        rings = {n: jnp.where(held, jnp.roll(state[n][:, 0], -((K - W) % W),
                                             axis=1), 0) for n in RINGS}
        return {"ssm": state["ssm"][:, 0], "conv": state["conv"][:, 0],
                **rings, "keys": state["keys"], "values": state["values"]}

    return jax.jit(lm_prefix_state)


def from_prefix(state, prefix, first):
    """`empty_state`'s ``state`` with every row started from the snapshot
    ``prefix``, as `ssm_hybrid.from_prefix` starts its rows (THE cache is
    a stack of one): the states and tails copied a row (no position in
    them), the K keys and values written at row ``b``'s own offset
    ``first[b]``, directly in front of where that row's suffix will be
    written (the padding lies in front of both, so the mask stays
    ``kv_start = first`` with no hole).  The rings are `_window`'s to
    fill: a slot is named by the buffer index, which the suffix moves."""
    cache = {name: state[name][None] for name in ("keys", "values")}
    new = ssm_hybrid.from_prefix({**state, **cache}, prefix, first)
    return {**state, **new, **{name: new[name][0] for name in cache}}


# --- the served program ---------------------------------------------------

def chunk_of(cfg: Phi4FlashConfig, S: int, prefix=None) -> int:
    """Positions a chunk of the front's walk over a buffer of ``S``:
    ``prefill_chunk``, and behind a snapshot the buffer's own length
    where that is shorter (what is left of a prompt is short)."""
    return cfg.prefill_chunk if prefix is None \
        else min(cfg.prefill_chunk, S)


def _front_prefill(cfg: Phi4FlashConfig, params, prompt_ids, first,
                   length: int, prefix=None):
    """The prompt buffer ``[B, S]`` (row ``b``'s real ids in front,
    ``first[b]`` positions of padding behind) through the FRONT at every
    position and through layer ``n/2 + 1``'s key-value projection: the
    stream ``[B, 1, d]`` and the memory ``[B, 1, C]`` at each row's last
    id, and the state with room for ``length`` positions.  The front
    walks a multiple of `chunk_of` positions: what is missing is more
    padding in front, which no row's state sees.

    With a snapshot ``prefix`` of K ids the buffer holds what FOLLOWS
    them in every row: each row starts from the snapshot (`from_prefix`,
    `_window`'s ``prefix``) and the front walks the ``S`` positions
    behind it; the buffer is laid out ``padding | prefix | row's own
    ids``, its last id at ``K + S - 1`` whatever the row."""
    B, S = prompt_ids.shape
    K = lm_decode.prefix_length(prefix)
    W = cfg.sliding_window
    Q = chunk_of(cfg, S, prefix)
    extra = -S % Q
    with jax.named_scope("prefill"):
        # every row's last real id at the buffer's end
        ids = jnp.pad(jax.vmap(jnp.roll)(prompt_ids, first),
                      ((0, 0), (extra, 0)))
        # positions count from ``extra`` in front of the buffer: row b's
        # real ids begin at ``begins[b]``, its OWN at ``own[b]``, and the
        # front walks ``K .. K + S + extra - 1``
        begins = first + extra
        own = begins + K
        real = jnp.arange(S + extra)[None, :] >= begins[:, None]
        state = empty_state(cfg, B, length)
        if prefix is not None:
            state = from_prefix(state, prefix, first)

        def step(kind, lp, x, i, state):
            if kind == MAMBA:
                x, memory, s, tail = _mamba_prefill(
                    cfg, lp, x, real, Q, *_mamba_state(state, i))
                return x, _with_mamba_state(state, i, s, tail), memory
            last = None if prefix is None else (*(
                jax.lax.dynamic_index_in_dim(prefix[n], i, keepdims=False)
                for n in RINGS), own)
            x, held = _window_prefill(cfg, lp, x, 2 * i + 1, begins, Q, K,
                                      last)
            with jax.named_scope("kv_cache"):
                # slot i of ``held`` is buffer index K + S - W + i: each
                # to the slot its index names
                rk, rv = (_set_layer(state[n], i,
                                     jnp.roll(t, (K + S - W) % W, axis=1))
                          for n, t in zip(RINGS, held))
            return x, {**state, "ring_keys": rk, "ring_values": rv}

        x, state, memory = _front(cfg, params, _embed(params, ids), state,
                                  step)
        # THE cache: layer n/2 + 1's keys and values of every position
        with jax.named_scope("layers"), jax.named_scope(FULL):
            lp = layer_of(params[STACKS[FULL]], jnp.int32(0))
            u = _normed(cfg, lp, x, "input_layernorm")
            with jax.named_scope("attn"):
                kv = _some_columns(cfg, lp, u, slice(
                    cfg.num_attention_heads * cfg.head_dim, None))
                with jax.named_scope("kv_cache"):
                    for n, t in zip(("keys", "values"),
                                    jnp.split(kv, 2, axis=-1)):
                        t = _heads(cfg, t, 2 * cfg.head_dim)[:, extra:]
                        if prefix is not None:
                            t = lm_decode.own_entries(
                                real[:, extra:], t, state[n][None], 0, K)
                        state[n] = jax.lax.dynamic_update_slice(
                            state[n], t, (0, K, 0, 0))
        return x[:, -1:], memory, state


def prefill(cfg: Phi4FlashConfig, params, prompt_ids, first, length: int,
            prefix=None):
    """`_front_prefill`, then the back at each row's last position: the
    logits behind each row's last real id ``[B, V]`` and the state."""
    last = lm_decode.prefix_length(prefix) + prompt_ids.shape[1] - 1
    x, memory, state = _front_prefill(cfg, params, prompt_ids, first,
                                      length, prefix)
    with jax.named_scope("prefill"):
        x, state = _back(cfg, params, x, memory, jnp.full((1,), last),
                         first, state, held=True)
        return _head(cfg, params, x)[:, 0], state


def decode_step(cfg: Phi4FlashConfig, params, token, index, first, state):
    """One new position a row (``token [B]`` at the buffer index ``index
    [1]``) through all the layers on the resident state: the logits, the
    state, and the keys each row's queries saw in the rings and in the
    cache, each summed over the layers that read it ``[B]``."""
    def step(kind, lp, x, i, state):
        if kind == MAMBA:
            s, tail = _mamba_state(state, i)
            x, memory, s, tail = _layer(
                cfg, lp, x, lambda v: _mamba(cfg, lp, v, None, s, tail))
            return x, _with_mamba_state(state, i, s, tail), memory
        x, rk, rv = _layer(cfg, lp, x, lambda u: _ring(
            cfg, lp, u, 2 * i + 1, index, first, state["ring_keys"],
            state["ring_values"], i))
        return x, {**state, "ring_keys": rk, "ring_values": rv}

    x, state, memory = _front(cfg, params, _embed(params, token[:, None]),
                              state, step)
    x, state = _back(cfg, params, x, memory, index, first, state,
                     held=False)

    def seen(slots, **mask):
        return visible_keys(slots, index, **mask)[:, 0].sum(
            axis=-1, dtype=jnp.int32)

    return _head(cfg, params, x)[:, 0], state, (
        cfg.layers_of(SWA) * seen(cfg.sliding_window,
                                  **ring_mask(cfg, index, first)),
        (1 + cfg.layers_of(CROSS)) * seen(state["keys"].shape[1],
                                          kv_start=first))


def generate(cfg: Phi4FlashConfig, max_new_tokens: int, params, prompt_ids,
             prompt_len, seed, temperature, prefix=None
             ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Prefill, then ``max_new_tokens`` decode steps, for every row:
    `looplm.generate`'s contract (rows, lengths, seeds, temperatures; a
    row's numbers do not depend on what the other rows hold, nor on its
    padding), with `_front_prefill`'s ``prefix``.  Returns the new ids
    ``[B, N]``, the float32 logits each was drawn from ``[B, N, V]`` and
    ``stats``, int32: what the program COMPUTED (``prefill_positions``
    through the front, ``cross_positions`` through layer ``n/2 + 1``'s
    attention and the layers behind it in the prefill, ``scan_chunks``,
    ``state_steps``: every row's, padded ones too; of a prefix served
    from a snapshot nothing) and ``keys_attended_ring``,
    ``keys_attended_full [B]`` (what the decode steps' masks let a row's
    queries see, a prefix's keys among them, summed over the layers that
    read the rings, and over the ``1 + cross`` layers that read the one
    cache)."""
    B, S = prompt_ids.shape
    P = S + lm_decode.prefix_length(prefix)
    first = S - jnp.broadcast_to(prompt_len, (B,))

    def start():
        logits, state = prefill(cfg, params, prompt_ids, first,
                                P + max_new_tokens, prefix)
        with jax.named_scope("prefill"):
            rows = jnp.zeros((B,), jnp.int32)
            return logits, (), state, (rows, rows), ()

    def step(token, i, state):
        logits, state, seen = decode_step(cfg, params, token, P + i[None],
                                          first, state)
        return logits, (), state, seen

    tokens, logits, _, (ring_keys, full_keys), _ = lm_decode.generate(
        "Phi4Flash", B, start, step, max_new_tokens, seed, temperature)
    Lm = cfg.layers_of(MAMBA) + 1
    chunk = chunk_of(cfg, S, prefix)
    chunks = -(-S // chunk)
    return tokens, logits, {
        "prefill_positions": jnp.int32(B * chunks * chunk),
        "cross_positions": jnp.int32(B),
        "scan_chunks": jnp.int32(B * Lm * chunks),
        "state_steps": jnp.int32(B * Lm * max_new_tokens),
        "keys_attended_ring": ring_keys, "keys_attended_full": full_keys}


def make_program(cfg: Phi4FlashConfig, max_new_tokens: int):
    """The jitted program, named ``lm_generate`` (``jit_lm_generate`` in a
    device trace) like every language model's: ``(ids, logits, aux,
    stats)``, ``aux`` empty.  With a sixth argument,
    `make_prefix_program`'s snapshot, ``prompt_ids`` holds what follows
    the prefix."""

    def served(*args):
        tokens, logits, stats = generate(cfg, max_new_tokens, *args)
        return tokens, logits, {}, stats

    return lm_decode.make_program(served)


def window_counters(cfg: Phi4FlashConfig, stats, real: int, steps: int
                    ) -> Dict[str, int]:
    """The ``lm.*`` window counters of one execution from its fetched
    ``stats``: what the program computed for EVERY row, and the keys the
    ``real`` rows' decode steps attended to by where they lie (a padded
    row repeats the first and is nobody's)."""
    return {
        **{f"lm.{name}": int(stats[name]) for name in (
            "prefill_positions", "cross_positions", "scan_chunks",
            "state_steps")},
        **{f"lm.keys_attended_{kind}": int(
            stats[f"keys_attended_{kind}"][:real].sum())
           for kind in ("ring", "full")}}
