"""A decoder whose layers are mostly STATE-SPACE layers (Mamba-2), with a
grouped-query attention layer every ten, one gated MLP shape behind both
(ibm-granite/granite-4.0-h-micro, ``model_type`` ``granitemoehybrid``
with no routed expert; the equations are the family's published modeling
file's), served WHOLE on one chip.

    x = embedding_multiplier * E[ids]
    for l in 0..L-1:                                     # every block
      h = x + residual_multiplier * Mixer_l(RMSNorm(x))
      x = h + residual_multiplier * MLP_l(RMSNorm(h))
    logits = E RMSNorm(x) / logits_scaling               # the head is E: tied

    MLP(u) = W_out (silu(a) * b),  [a | b] = W_in u      # shared_mlp

    Mixer of an ``attention`` layer:
      q -> H heads of D;  k, v -> G heads of D;  no bias, NO rotation
      (``position_embedding_type`` ``nope``);  query head h reads
      key-value head h // (H / G)
      W_o concat_h(softmax(q . k * attention_multiplier + causal) v)

    Mixer of a ``mamba`` layer (Mamba-2, one group):
      [z | xBC | dt] = W_in u                            # 2 d | 2 d + 2 n | heads
      xBC = silu(conv1d(xBC) + b)        # causal, depthwise, ``d_conv`` taps
      [x | B | C] = xBC                  # heads x d_head | n | n
      dt = softplus(dt + dt_bias);  A = -exp(A_log)      # a head each
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t       # [heads, d_head, n]
      y_t = S_t C_t + D x_t
      W_out (RMSNorm(y * silu(z)) * g)                   # over all 2 d values

**State of two kinds in one program.**  An attention layer keeps every
position's keys and values (``[L_a, B, P + N, G, D]``, indexed by
POSITION, growing with the sequence); a Mamba layer keeps ``S`` (float32,
``[L_m, B, heads, d_head, n]``) and the last ``d_conv - 1`` inputs of its
convolution (the TAIL), both OVERWRITTEN in place every step and of one
size whatever the sequence's length.

**Two paths for one layer.**  The prefill computes every ``y_t`` and the
final ``S`` by the CHUNKED form (`chunked_scan`: inside a chunk of
``mamba_chunk_size`` positions the recurrence unrolled into products,
between chunks the state carried in float32); a decode step is one step
of the recurrence on the resident state.  Both give the recurrence's
numbers; tests hold each to ``benchmarks/chip/reference/ssm_hybrid.py``,
which writes it as the recurrence.

**Padding.**  Rows are right-aligned (`looplm.generate`'s contract): a
row's padding lies in FRONT of it.  An attention mask hides padded keys;
a recurrence and a convolution would carry them forward.  So a Mamba
mixer's input is zeroed at a row's padded positions (no projection has a
bias: its ``xBC`` is then zero there, what the convolution's taps see in
front of an unpadded sequence) and ``dt`` is forced to 0 there (the state
neither decays nor takes input): a row's numbers are those of its
single-row run.

**Layout, decided here** (neither changes a value).  The published
``in_proj`` has 8512 columns, 66.5 x 128: its leaf is stored as TWO,
``in_proj_zx`` (z | xBC: 8448 = 66 x 128 columns, which the few-row
kernel's blocks divide) and ``in_proj_dt`` (the 64 columns of ``dt``; too
small to be worth a launch).  The tied embedding stays ``[V, d]`` as
published, rows contiguous for the lookup; the head reads it transposed
(`looplm.dense_tied`: the few-row kernel's transposed form at 2 to 8
rows, a ``dot_general`` over the second axis of both otherwise).

Precision: weights, the key-value cache, the tail and matmul operands in
``cfg.dtype``; the residual stream, every RMSNorm, the softmax, the
logits, ``dt``, every ``exp`` of a decay and the recurrent state in
float32 (``cfg.state_dtype``); every product accumulates in float32.

Scopes carry the published modules' names (``GraniteMoeHybrid/decode/
mamba_layers/mamba/in_proj`` ...), read by ``utils/trace.KERNEL_CLASSES``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from comfyui_distributed_tpu.models import lm_decode
from comfyui_distributed_tpu.models.layers import ATTENTION_PATHS, \
    attention_path, visible_keys
from comfyui_distributed_tpu.models.looplm import _dense, _rms_norm, \
    dense_each, dense_tied, few_rows_here, matrix, scan_layers  # noqa: F401
from comfyui_distributed_tpu.models.mla_moe import count_values, seeded_tree
from comfyui_distributed_tpu.models.swa_moe import _attend
from comfyui_distributed_tpu.parallel import sharding as shd

MAMBA, ATTENTION = "mamba", "attention"
STACKS = {MAMBA: "mamba_layers", ATTENTION: "attention_layers"}


@dataclasses.dataclass(frozen=True)
class Run:
    """Blocks of one kind that follow each other."""
    kind: str           # MAMBA | ATTENTION
    start: int          # the first block's index in its stack and its state
    count: int


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The shape keys of the model's ``config.json``, under its names."""
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    shared_intermediate_size: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    attention_multiplier: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16       # weights, cache, tail, matmul operands
    state_dtype: Any = jnp.float32  # the recurrent state

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(
                f"layer_types {self.layer_types} do not name "
                f"{self.num_hidden_layers} blocks as {MAMBA} or {ATTENTION}")
        if self.mamba_n_heads * self.mamba_d_head \
                != self.mamba_expand * self.hidden_size:
            raise ValueError(
                f"{self.mamba_n_heads} heads of {self.mamba_d_head} are not "
                f"{self.mamba_expand} x {self.hidden_size}")
        if self.mamba_n_groups != 1:
            raise ValueError(
                f"{self.mamba_n_groups} groups of B and C are not "
                f"implemented: one, shared by every head")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not divide "
                f"{self.hidden_size} or over {self.num_key_value_heads} "
                f"key-value heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """Columns of ``xBC``: what the convolution runs over."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def layer_applications(self) -> int:
        """Blocks one token passes through."""
        return self.num_hidden_layers

    def layers_of(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def runs(self) -> List[Run]:
        runs: List[Run] = []
        seen = {MAMBA: 0, ATTENTION: 0}
        for kind in self.layer_types:
            if runs and runs[-1].kind == kind:
                runs[-1] = dataclasses.replace(runs[-1],
                                               count=runs[-1].count + 1)
            else:
                runs.append(Run(kind, seen[kind], 1))
            seen[kind] += 1
        return runs


_PERIOD = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4

# ibm-granite/granite-4.0-h-micro config.json, nothing reduced: 40 blocks,
# 4 periods of 5 x mamba, attention, 4 x mamba
GRANITE_4_0_H_MICRO = GraniteHybridConfig(
    vocab_size=100352, hidden_size=2048, num_hidden_layers=40,
    layer_types=_PERIOD * 4, num_attention_heads=32, num_key_value_heads=8,
    shared_intermediate_size=8192, mamba_n_heads=64, mamba_d_head=64,
    mamba_d_state=128, attention_multiplier=0.015625,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0,
    mamba_d_conv=4, mamba_n_groups=1, mamba_expand=2, mamba_chunk_size=256,
    rms_norm_eps=1e-5)

# the CPU tests' and the rehearsal's size (fp32: deterministic
# comparisons): both kinds twice, Mamba blocks in runs of two; chunks of 8
TINY_SSM_HYBRID = GraniteHybridConfig(
    vocab_size=512, hidden_size=64, num_hidden_layers=6,
    layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA, MAMBA, ATTENTION),
    num_attention_heads=4, num_key_value_heads=2,
    shared_intermediate_size=96, mamba_n_heads=4, mamba_d_head=32,
    mamba_d_state=16, attention_multiplier=0.0625, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0, mamba_chunk_size=8,
    dtype=jnp.float32)

CONFIGS = {"full": GRANITE_4_0_H_MICRO, "tiny": TINY_SSM_HYBRID}

NORMS = ("input_layernorm", "post_attention_layernorm", "norm")
# A trained model's tied embedding is small (hence the multiplier).  With
# unit normals the head would read x_0 = 12 E[id] back out of the stream:
# the last prompt id's own logit 20 standard deviations over the rest at
# every step, every greedy token that id.  At 0.01 the blocks' updates
# (0.22 each, about 1.5 together) are most of the last state.
EMBED_STD = 0.01
# Mamba-2's initialisation, not a matrix's: A in 1..16 and dt log-uniform
# in 1e-3..1e-1.  Drawn as normals, exp(dt A) would be 0 or 1 nearly
# everywhere, the recurrence trivial, and no comparison could see a wrong
# state.
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)


def param_shapes(cfg: GraniteHybridConfig) -> Dict[str, Any]:
    """The parameter tree's shapes, kernels ``[in, out]``: the blocks of
    each kind stacked on a leading axis (``mamba_layers``,
    ``attention_layers``), each with its two norms and its MLP.  No
    ``lm_head``: the head is ``embed_tokens``.  ``conv1d_weight`` is
    ``[L, taps, channels]``, tap ``k`` meeting position ``t - (taps - 1)
    + k`` (the published ``[channels, 1, taps]`` transposed)."""
    d, f, D = cfg.hidden_size, cfg.shared_intermediate_size, cfg.head_dim
    H, G = cfg.num_attention_heads, cfg.num_key_value_heads
    heads, taps = cfg.mamba_n_heads, cfg.mamba_d_conv

    def block(L):
        return {"input_layernorm": (L, d), "post_attention_layernorm": (L, d),
                "input_linear": (L, d, 2 * f), "output_linear": (L, f, d)}

    Lm, La = cfg.layers_of(MAMBA), cfg.layers_of(ATTENTION)
    mamba = {**block(Lm),
             "in_proj_zx": (Lm, d, cfg.d_inner + cfg.conv_dim),
             "in_proj_dt": (Lm, d, heads),
             "conv1d_weight": (Lm, taps, cfg.conv_dim),
             "conv1d_bias": (Lm, cfg.conv_dim),
             "dt_bias": (Lm, heads), "A_log": (Lm, heads), "D": (Lm, heads),
             "norm": (Lm, cfg.d_inner), "out_proj": (Lm, cfg.d_inner, d)}
    attention = {**block(La), "q_proj": (La, d, H * D),
                 "k_proj": (La, d, G * D), "v_proj": (La, d, G * D),
                 "o_proj": (La, H * D, d)}
    return {"embed_tokens": (cfg.vocab_size, d), "mamba_layers": mamba,
            "attention_layers": attention, "norm": (d,)}


def param_count(cfg: GraniteHybridConfig) -> int:
    return count_values(param_shapes(cfg))


def _uniform(key, shape, low, high):
    return jax.random.uniform(key, shape, jnp.float32, low, high)


def _dt_bias(key, shape):
    """The inverse softplus of a ``dt`` drawn log-uniform in DT_RANGE."""
    dt = jnp.exp(_uniform(key, shape, *map(math.log, DT_RANGE)))
    return dt + jnp.log(-jnp.expm1(-dt))


DRAWS = {
    "embed_tokens": lambda k, s: EMBED_STD * jax.random.normal(
        k, s, jnp.float32),
    "A_log": lambda k, s: jnp.log(_uniform(k, s, *A_RANGE)),
    "dt_bias": _dt_bias,
    "D": lambda k, s: jnp.ones(s, jnp.float32),
    "conv1d_bias": lambda k, s: 0.1 * jax.random.normal(k, s, jnp.float32),
}


def seeded_params(cfg: GraniteHybridConfig, seed) -> Dict[str, Any]:
    """`mla_moe.seeded_tree`: on the device, leaf by leaf; kernels (the
    convolution's taps among them) normals scaled by fan-in, norm gains 1
    + 0.1 N, and DRAWS for the leaves that are neither."""
    return seeded_tree(param_shapes(cfg), seed, cfg.dtype,
                       lambda name: 1.0 if name in NORMS else None, DRAWS)


def load_checkpoint(path: str, cfg: GraniteHybridConfig):
    from comfyui_distributed_tpu.models.checkpoints import \
        load_granite_hybrid_checkpoint
    return load_granite_hybrid_checkpoint(path, cfg)


# --- the Mamba-2 mixer ------------------------------------------------------

def causal_conv(xbc, weight, bias, tail=None, at=None):
    """The depthwise causal convolution over positions, float32: ``xbc
    [B, T, C]`` behind ``tail [B, taps - 1, C]`` (zeros where None: the
    front of a sequence), taps ``weight [taps, C]``.  With ``at [B]`` row
    ``b``'s sequence starts at position ``at[b]`` (what lies in front is a
    right-aligned row's padding, zeros) and the tail lies directly in
    front of THAT position, not of position 0.  Returns the convolution
    at the ``T`` positions and the new tail (the last ``taps - 1``
    inputs: of a row shorter than that, the end of the old tail and the
    row)."""
    B, T, C = xbc.shape
    taps = weight.shape[0]
    front = jnp.zeros((B, taps - 1, C), xbc.dtype)
    tail = front if tail is None else tail.astype(xbc.dtype)
    if at is None:
        seen = jnp.concatenate([tail, xbc], axis=1)
    else:
        # position j of ``xbc`` is entry j + taps - 1 of ``seen``
        seen = jnp.concatenate([front, xbc], axis=1)
        for b in range(B):
            seen = jax.lax.dynamic_update_slice(seen, tail[b:b + 1],
                                                (b, at[b], 0))
    wide = seen.astype(jnp.float32)
    out = bias.astype(jnp.float32) + sum(
        weight[k].astype(jnp.float32) * wide[:, k:k + T]
        for k in range(taps))
    return out, seen[:, T:]


def chunked_scan(x, dt, A, Bm, Cm, chunk: int, dtype, start=None):
    """The recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``,
    ``y_t = S_t C_t`` from ``S = start [B, h, p, n]`` (float32; 0 where
    None), over ``T`` positions in chunks of ``chunk`` (Mamba-2's
    state-space-dual form): ``x [B, T, h, p]``, ``dt [B, T, h]`` float32
    (0 where a position is padding), ``A [h]`` float32, negative, ``Bm``,
    ``Cm [B, T, n]``.  Returns ``y [B, T, h, p]`` and the state behind
    the last position ``[B, h, p, n]``, float32.

    Inside a chunk position ``q`` reads position ``s <= q`` through
    ``(C_q . B_s) exp(sum_{s < r <= q} dt_r A) dt_s``: one masked ``[Q,
    Q]`` matrix a head, times the chunk's ``x``.  What came before the
    chunk (``start``, for the first) reaches it through the state at the
    chunk's start.  Products take operands in ``dtype`` and accumulate in
    float32; every decay,
    ``dt`` and the state between chunks are float32 (the state is
    rounded to ``dtype`` only as the operand of the product that reads
    it).  ``T`` need not be a multiple of ``chunk``: the end is padded
    with ``dt = 0``, which leaves the state as it is; a sequence shorter
    than a chunk is one chunk."""
    B, T, h, p = x.shape
    Q = min(chunk, T)
    c = -(-T // Q)
    pad = c * Q - T

    def chunks(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return a.reshape(B, c, Q, *a.shape[2:])

    x, dt, Bm, Cm = chunks(x), chunks(dt), chunks(Bm), chunks(Cm)
    f32 = jnp.float32
    # cum[q] = sum_{r <= q} dt_r A: the log of the decay from the chunk's
    # start to behind position q
    cum = jnp.cumsum(dt * A, axis=2)                         # [B, c, Q, h]
    xd, Bd, Cd = x.astype(dtype), Bm.astype(dtype), Cm.astype(dtype)

    # inside the chunks
    G = jnp.einsum("bcqn,bcsn->bcqs", Cd, Bd, preferred_element_type=f32)
    at = jnp.arange(Q)
    span = cum.transpose(0, 1, 3, 2)                         # [B, c, h, Q]
    decay = jnp.exp(jnp.where(at[:, None] >= at[None, :],
                              span[..., :, None] - span[..., None, :],
                              -jnp.inf))                     # [B, c, h, q, s]
    M = G[:, :, None] * decay * dt.transpose(0, 1, 3, 2)[..., None, :]
    y = jnp.einsum("bchqs,bcshp->bcqhp", M.astype(dtype), xd,
                   preferred_element_type=f32)

    # what each chunk alone leaves behind its last position
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dt              # [B, c, Q, h]
    local = jnp.einsum("bcshp,bcsn->bchpn",
                       (x.astype(f32) * to_end[..., None]).astype(dtype), Bd,
                       preferred_element_type=f32)
    whole = jnp.exp(cum[:, :, -1])                           # [B, c, h]

    # between the chunks: the state at each chunk's start, in float32
    def carry_over(S, xs):
        decay, own = xs
        return decay[..., None, None] * S + own, S

    if start is None:
        start = jnp.zeros((B, h, p, Bm.shape[-1]), f32)
    last, starts = jax.lax.scan(
        carry_over, start.astype(f32),
        (whole.swapaxes(0, 1), local.swapaxes(0, 1)))
    starts = starts.swapaxes(0, 1)                           # [B, c, h, p, n]
    y = y + jnp.einsum("bcqn,bchpn->bcqhp", Cd, starts.astype(dtype),
                       preferred_element_type=f32) \
        * jnp.exp(cum)[..., None]
    return y.reshape(B, c * Q, h, p)[:, :T], last


def state_step(S, x, dt, A, Bm, Cm):
    """One step of the recurrence, elementwise in float32: ``S [B, h, p,
    n]``, ``x [B, h, p]``, ``dt [B, h]``, ``Bm``, ``Cm [B, n]``.  Returns
    ``y [B, h, p]`` and the new state."""
    S = jnp.exp(dt * A)[..., None, None] * S \
        + (dt[..., None] * x)[..., None] * Bm[:, None, None, :]
    return jnp.sum(S * Cm[:, None, None, :], axis=-1), S


def _real_only(real, a):
    """``a [B, N, ...]`` with every row's padded positions zeroed."""
    return jnp.where(real[..., None], a, 0.0)


def _mamba(cfg: GraniteHybridConfig, lp, u, real, ssm, conv, l,
           decode: bool):
    """The mixer over ``u [B, N, d]`` (normed) FROM layer ``l`` of the
    recurrent state ``ssm`` and of the tails ``conv`` (zeros in front of
    a whole sequence, a prefix's where the rows start behind one), each
    then overwritten.  ``real [B, N]`` says which positions of the
    prefill are a row's own (its padding lies in front: the tail stands
    directly in front of its first real position); a decode step (``N`` =
    1) has none."""
    B, N, _ = u.shape
    heads, p, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    f32 = jnp.float32
    with jax.named_scope("mamba"):
        if not decode:
            u = _real_only(real, u)
        with jax.named_scope("in_proj"):
            # z | xBC as activations in the model's dtype; dt stays float32
            zx = _dense(u, lp["in_proj_zx"], cfg).astype(cfg.dtype)
            dt = _dense(u, lp["in_proj_dt"], cfg)
        z, xbc = zx[..., :cfg.d_inner], zx[..., cfg.d_inner:]
        with jax.named_scope("conv_state"):
            tail = jax.lax.dynamic_index_in_dim(conv, l, keepdims=False)
        with jax.named_scope("conv1d"):
            xbc, tail = causal_conv(
                xbc, matrix(lp["conv1d_weight"]), lp["conv1d_bias"], tail,
                None if decode else jnp.sum(~real, axis=1))
            xbc = jax.nn.silu(xbc)
        with jax.named_scope("conv_state"):
            conv = jax.lax.dynamic_update_slice(
                conv, tail[None].astype(conv.dtype), (l, 0, 0, 0))
        with jax.named_scope("ssm"):
            x = xbc[..., :cfg.d_inner].reshape(B, N, heads, p)
            Bm = xbc[..., cfg.d_inner:cfg.d_inner + n]
            Cm = xbc[..., cfg.d_inner + n:]
            dt = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))
            A = -jnp.exp(lp["A_log"].astype(f32))
        with jax.named_scope("ssm_state"):
            S = jax.lax.dynamic_index_in_dim(
                ssm, l, keepdims=False).astype(f32)
        with jax.named_scope("ssm"):
            if decode:
                y, S = state_step(S, x[:, 0], dt[:, 0], A, Bm[:, 0],
                                  Cm[:, 0])
                y = y[:, None]
            else:
                y, S = chunked_scan(x, _real_only(real, dt), A, Bm, Cm,
                                    cfg.mamba_chunk_size, cfg.dtype, S)
            y = y + lp["D"].astype(f32)[:, None] * x
        with jax.named_scope("ssm_state"):
            ssm = jax.lax.dynamic_update_slice(
                ssm, S[None].astype(ssm.dtype), (l, 0, 0, 0, 0))
        with jax.named_scope("norm"):
            y = _rms_norm(y.reshape(B, N, -1)
                          * jax.nn.silu(z.astype(f32)), lp["norm"],
                          cfg.rms_norm_eps)
        with jax.named_scope("out_proj"):
            return _dense(y, lp["out_proj"], cfg), ssm, conv


# --- the attention mixer and the MLP ------------------------------------------

def _attention(cfg: GraniteHybridConfig, lp, u, index, first, kc, vc, l,
               decode: bool, prefix: int = 0):
    """The mixer over ``u [B, N, d]`` (normed), the cache with this
    call's keys and values written into layer ``l`` at the buffer indices
    ``index [N]``, and the keys each row's LAST query saw ``[B]``.  A
    prefill from the front of the buffer attends to this call's own keys
    (they ARE the cache's); one behind ``prefix`` positions to the cache
    as far as its own last index, a decode step to all of it.  No
    position reaches a query or a key, so a key is the same wherever in
    the buffer it lies."""
    B, N, _ = u.shape
    D = cfg.head_dim
    mask = {"kv_start": first, "window": None}
    with jax.named_scope("self_attn"):
        with jax.named_scope("q_proj"):
            q = _dense(u, lp["q_proj"], cfg).reshape(B, N, -1, D)
        k, v = (t.reshape(B, N, -1, D)
                for t in dense_each(u, lp, ("k_proj", "v_proj"), cfg))
        q, k, v = (shd.constrain(t.astype(cfg.dtype), "batch", None,
                                 "heads", None) for t in (q, k, v))
        with jax.named_scope("kv_cache"):
            if prefix and not decode:
                own = index[None, :] >= first[:, None] + prefix
                k, v = (lm_decode.own_entries(own, t, c, l, index[0])
                        for c, t in ((kc, k), (vc, v)))
            kc, vc = (jax.lax.dynamic_update_slice(
                c, t[None].astype(c.dtype), (l, 0, index[0], 0, 0))
                for c, t in ((kc, k), (vc, v)))
            if decode or prefix:
                k, v = (jax.lax.dynamic_index_in_dim(
                    c, l, keepdims=False).astype(cfg.dtype)
                    for c in (kc, vc))
            if not decode and prefix:
                k, v = k[:, :prefix + N], v[:, :prefix + N]
        ATTENTION_PATHS.bump(attention_path(
            jax.default_backend(), B, N, k.shape[1],
            cfg.num_attention_heads, masked=True))
        seen = visible_keys(k.shape[1], index[-1:], **mask)[:, 0].sum(
            axis=-1, dtype=jnp.int32)
        a = _attend(q, k, v, index, scale=cfg.attention_multiplier, **mask)
        with jax.named_scope("o_proj"):
            return _dense(a, lp["o_proj"], cfg), kc, vc, seen


def _mlp(cfg: GraniteHybridConfig, lp, u):
    with jax.named_scope("shared_mlp"):
        with jax.named_scope("input_linear"):
            a, b = jnp.split(_dense(u, lp["input_linear"], cfg), 2, axis=-1)
        h = shd.constrain(jax.nn.silu(a) * b, "batch", None, "mlp")
        with jax.named_scope("output_linear"):
            return _dense(h, lp["output_linear"], cfg)


def _stack(cfg: GraniteHybridConfig, params, x, index, first, state,
           decode: bool, prefix: int = 0):
    """Every block, run by run (`GraniteHybridConfig.runs`): one
    ``lax.scan`` a run over the index of its layers with the stacked
    leaves closed over (`looplm.scan_layers`), the state of its kind in
    the carry and the other not touched.  ``state`` is `empty_state`'s
    or, behind a shared prefix, `from_prefix`'s; every block writes this
    call's entries at the buffer indices ``index [N]`` (consecutive, the
    same for every row); row ``b``'s real entries start at ``first[b]``,
    the first ``prefix`` of them in ``state`` already.  Returns the
    normed last state of the stream,
    ``state``, and the keys each row's last query saw, summed over the
    attention layers ``[B]``."""
    B = x.shape[0]
    eps, res = cfg.rms_norm_eps, cfg.residual_multiplier
    real = None if decode else index[None, :] >= first[:, None] + prefix
    state = dict(state)
    seen = jnp.zeros((B,), jnp.int32)

    for run in cfg.runs:
        names = ("ssm", "conv") if run.kind == MAMBA else ("keys", "values")

        def block(carry, xs, kind=run.kind):
            x, s0, s1 = carry
            lp, l = xs
            with jax.named_scope("input_layernorm"):
                u = _rms_norm(x, lp["input_layernorm"], eps)
            if kind == MAMBA:
                m, s0, s1 = _mamba(cfg, lp, u, real, s0, s1, l, decode)
                keys = jnp.zeros((B,), jnp.int32)
            else:
                m, s0, s1, keys = _attention(cfg, lp, u, index, first, s0,
                                             s1, l, decode, prefix)
            h = x + res * m
            with jax.named_scope("post_attention_layernorm"):
                u = _rms_norm(h, lp["post_attention_layernorm"], eps)
            return (h + res * _mlp(cfg, lp, u), s0, s1), keys

        with jax.named_scope(STACKS[run.kind]):
            (x, *new), keys = scan_layers(
                block, (x, *(state[n] for n in names)),
                params[STACKS[run.kind]], run.count, True, first=run.start,
                start=run.start)
        state.update(zip(names, new))
        seen = seen + keys.sum(axis=0)
    with jax.named_scope("final_norm"):
        return _rms_norm(x, params["norm"], eps), state, seen


def _embed(cfg: GraniteHybridConfig, params, ids):
    with jax.named_scope("embed_tokens"):
        return params["embed_tokens"][ids].astype(jnp.float32) \
            * cfg.embedding_multiplier


def _head(cfg: GraniteHybridConfig, params, x):
    with jax.named_scope("lm_head"):
        return dense_tied(x, params["embed_tokens"], cfg) \
            / cfg.logits_scaling


def empty_state(cfg: GraniteHybridConfig, batch: int, length: int):
    """The state of both kinds: ``ssm`` and ``conv`` of the Mamba layers
    (no axis of positions), ``keys`` and ``values`` of the attention
    layers for ``length`` positions."""
    Lm, La = cfg.layers_of(MAMBA), cfg.layers_of(ATTENTION)
    kv = (La, batch, length, cfg.num_key_value_heads, cfg.head_dim)
    return {"ssm": jnp.zeros((Lm, batch, cfg.mamba_n_heads, cfg.mamba_d_head,
                              cfg.mamba_d_state), cfg.state_dtype),
            "conv": jnp.zeros((Lm, batch, cfg.mamba_d_conv - 1,
                               cfg.conv_dim), cfg.dtype),
            "keys": jnp.zeros(kv, cfg.dtype),
            "values": jnp.zeros(kv, cfg.dtype)}


def state_bytes(cfg: GraniteHybridConfig, batch: int) -> int:
    """Bytes of the RECURRENT state (``ssm`` and the tails): no function
    of the positions."""
    per_layer = cfg.d_inner * cfg.mamba_d_state \
        * jnp.dtype(cfg.state_dtype).itemsize \
        + (cfg.mamba_d_conv - 1) * cfg.conv_dim \
        * jnp.dtype(cfg.dtype).itemsize
    return cfg.layers_of(MAMBA) * batch * per_layer


def kv_cache_bytes(cfg: GraniteHybridConfig, batch: int, length: int) -> int:
    """Bytes of the POSITIONAL state: the attention layers' keys and
    values for ``length`` positions."""
    return 2 * cfg.layers_of(ATTENTION) * batch * length \
        * cfg.num_key_value_heads * cfg.head_dim \
        * jnp.dtype(cfg.dtype).itemsize


def kv_cache_bytes_by_kind(cfg: GraniteHybridConfig, batch: int, length: int
                           ) -> Dict[str, int]:
    return {"recurrent": state_bytes(cfg, batch),
            "positional": kv_cache_bytes(cfg, batch, length)}


# --- a prefix shared between requests ----------------------------------------
#
# What a prompt's first K ids leave behind is ONE recurrent state and tail
# a Mamba layer, whatever K, and K keys and values an attention layer: the
# SNAPSHOT (`make_prefix_program`), the state of one row with no axis of
# rows, at the stored widths.  Rows whose prompts start with those ids
# start from copies of it and prefill their own suffix only.  Legal
# because nothing here depends on a position's index: the recurrence has
# none and the attention layers rotate nothing, so the prefix's keys can
# stand wherever a row's own offset puts them.

def prefix_bytes(cfg: GraniteHybridConfig, positions: int) -> int:
    """Bytes of the snapshot behind ``positions`` ids."""
    return state_bytes(cfg, 1) + kv_cache_bytes(cfg, 1, positions)


def make_prefix_program(cfg: GraniteHybridConfig):
    """The jitted maker of a snapshot, ``lm_prefix_state`` (NOT
    ``lm_generate``: what is counted and timed an execution is the served
    program's): ``prefix_ids [K]``, one row and no padding, through every
    block as a prefill -> ``ssm``, ``conv``, ``keys``, ``values`` behind
    id ``K - 1``."""

    def lm_prefix_state(params, prefix_ids):
        K, = prefix_ids.shape
        with jax.named_scope("GraniteMoeHybrid"), \
                jax.named_scope("prefill"):
            _, state, _ = _stack(
                cfg, params, _embed(cfg, params, prefix_ids[None]),
                jnp.arange(K), jnp.zeros((1,), jnp.int32),
                empty_state(cfg, 1, K), decode=False)
        return {name: a[:, 0] for name, a in state.items()}

    return jax.jit(lm_prefix_state)


def from_prefix(state, prefix, first):
    """`empty_state`'s ``state`` with every row started from the snapshot
    ``prefix``: its recurrent state and tail copied a row, its K keys and
    values written at row ``b``'s own offset ``first[b]``, directly in
    front of where that row's suffix will be written (the padding lies in
    front of both, so the mask stays ``kv_start = first`` with no hole)."""
    new = {}
    for name, scope in (("ssm", "ssm_state"), ("conv", "conv_state")):
        with jax.named_scope(scope):
            new[name] = jnp.broadcast_to(
                prefix[name][:, None], state[name].shape
            ).astype(state[name].dtype)
    with jax.named_scope("kv_cache"):
        for name in ("keys", "values"):
            new[name] = lm_decode.write_at_offsets(state[name], prefix[name],
                                                   first)
    return new


# --- the served program ---------------------------------------------------

def prefill(cfg: GraniteHybridConfig, params, prompt_ids, prompt_len,
            length: int, prefix=None):
    """The prompt buffer ``[B, S]`` (row ``b``'s ``prompt_len[b]`` real
    ids in front, padding behind) through every block: the logits behind
    each row's last real id ``[B, V]``, the state of both kinds with room
    for ``length`` positions, and ``first [B]``.  With a snapshot
    ``prefix`` of K ids the buffer holds what FOLLOWS them in every row:
    each row starts from the snapshot (`from_prefix`) and the blocks run
    over the ``S`` positions behind it; the cache is laid out ``padding |
    prefix | row's own ids``, its last id at ``K + S - 1`` whatever the
    row."""
    B, S = prompt_ids.shape
    K = lm_decode.prefix_length(prefix)
    with jax.named_scope("prefill"):
        # every row's last real id at the buffer's end
        first = S - prompt_len
        prompt_ids = jax.vmap(jnp.roll)(prompt_ids, first)
        state = empty_state(cfg, B, length)
        if prefix is not None:
            state = from_prefix(state, prefix, first)
        x, state, _ = _stack(cfg, params, _embed(cfg, params, prompt_ids),
                             K + jnp.arange(S), first, state, decode=False,
                             prefix=K)
        return _head(cfg, params, x[:, S - 1:])[:, 0], state, first


def generate(cfg: GraniteHybridConfig, max_new_tokens: int, params,
             prompt_ids, prompt_len, seed, temperature, prefix=None
             ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Prefill, then ``max_new_tokens`` decode steps, for every row:
    `looplm.generate`'s contract (rows, lengths, seeds, temperatures; a
    row's numbers do not depend on what the other rows hold, nor on its
    padding), with `prefill`'s ``prefix``.  Returns the new ids ``[B,
    N]``, the float32 logits each was drawn from ``[B, N, V]`` and
    ``stats``, int32: what the program COMPUTED (``prefill_positions``,
    ``scan_chunks``, ``state_steps``: every row's, padded ones too; of a
    prefix served from a snapshot nothing) and ``keys_attended_full [B]``
    (what the decode steps' masks let a row's query see, a prefix's keys
    among them, summed over the attention layers)."""
    B, S = prompt_ids.shape
    P = S + lm_decode.prefix_length(prefix)
    prompt_len = jnp.broadcast_to(prompt_len, (B,))
    first = None                    # `prefill`'s, which the steps read

    def start():
        nonlocal first
        logits, state, first = prefill(cfg, params, prompt_ids, prompt_len,
                                       P + max_new_tokens, prefix)
        with jax.named_scope("prefill"):
            return logits, (), state, (jnp.zeros((B,), jnp.int32),), ()

    def step(token, i, state):
        x, state, seen = _stack(
            cfg, params, _embed(cfg, params, token[:, None]), P + i[None],
            first, state, decode=True)
        return _head(cfg, params, x)[:, 0], (), state, (seen,)

    tokens, logits, _, (seen,), _ = lm_decode.generate(
        "GraniteMoeHybrid", B, start, step, max_new_tokens, seed,
        temperature)
    Lm = cfg.layers_of(MAMBA)
    chunks = -(-S // min(cfg.mamba_chunk_size, S))
    return tokens, logits, {
        "prefill_positions": jnp.int32(B * S),
        "scan_chunks": jnp.int32(B * Lm * chunks),
        "state_steps": jnp.int32(B * Lm * max_new_tokens),
        "keys_attended_full": seen}


def make_program(cfg: GraniteHybridConfig, max_new_tokens: int):
    """The jitted program, named ``lm_generate`` (``jit_lm_generate`` in a
    device trace) like every language model's: ``(ids, logits, aux,
    stats)``, ``aux`` empty (this family has no per-position array
    beside the logits).  With a sixth argument, `make_prefix_program`'s
    snapshot, ``prompt_ids`` holds what follows the prefix."""

    def served(*args):
        tokens, logits, stats = generate(cfg, max_new_tokens, *args)
        return tokens, logits, {}, stats

    return lm_decode.make_program(served)


def window_counters(cfg: GraniteHybridConfig, stats, real: int, steps: int
                    ) -> Dict[str, int]:
    """The ``lm.*`` window counters of one execution from its fetched
    ``stats``: what the program computed for EVERY row (the positions of
    its prefill, the chunks its scans walked, the Mamba layers' state
    updates of its decode), and the keys the ``real`` rows' decode steps
    attended to (a padded row repeats the first and is nobody's)."""
    return {
        "lm.prefill_positions": int(stats["prefill_positions"]),
        "lm.scan_chunks": int(stats["scan_chunks"]),
        "lm.state_steps": int(stats["state_steps"]),
        "lm.keys_attended_full": int(
            stats["keys_attended_full"][:real].sum())}
