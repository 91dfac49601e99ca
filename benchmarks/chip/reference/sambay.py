"""The plain reference of the decoder-hybrid-decoder
(microsoft/Phi-4-mini-flash-reasoning, ``model_type`` ``phi4flash``;
SambaY, arXiv:2507.06607; YOCO, arXiv:2405.05254; Mamba,
arXiv:2312.00752; Differential Transformer, arXiv:2410.05258): the
equations below in straightforward ``jax.numpy``, float32, every matrix
product at ``jax.default_matmul_precision("highest")``.  A full forward
pass over one whole sequence: **ALL the layers at EVERY position** (the
program runs the layers behind the cache for a prompt's last position
only: this file holds that to account), the recurrence written as the
recurrence (a ``lax.scan`` over positions), the convolution as a sum over
its taps, attention as the full masked ``[T, T]`` square with the two
softmax maps of every differential head written out, **no cache, no
ring, no chunk, no batching, no padding**.  Independent of the program:
it imports nothing of ``comfyui_distributed_tpu``.

With ``n`` layers, layer ``l`` is ``mamba`` (``l`` even, below ``n/2``),
``swa`` (odd, below ``n/2``), ``memory`` (``n/2``), ``full`` (``n/2 +
1``), ``gmu`` (even, behind) or ``cross`` (odd, behind):

    x = E[ids]                                 # no multiplier, no positions
    every layer:  h = x + Mixer(LN_1(x));  x = h + MLP(LN_2(h))
    LN(x) = (x - mean) / sqrt(var + eps) * g + b
    MLP(u) = (b * silu(a)) W_2,  [a | b] = u W_1
    logits = LN_f(x) E^T                       # tied: the head is E

    mamba, memory (Mamba-1):
      [u | z] = v W_in                         # d_inner | d_inner
      u_t = silu(b_c + sum_k w[k] u_{t - (taps - 1) + k})   # 0 before t = 0
      [r | B | C] = u W_x                      # dt_rank | N | N
      dt = softplus(r W_dt + b_dt);  A = -exp(A_log)        # [d_inner, N]
      s_t = exp(dt_t (x) A) * s_{t-1} + (dt_t * u_t) (x) B_t,  s_{-1} = 0
      y_t = s_t C_t + D * u_t
      out = (y * silu(z)) W_out
      the MEMORY layer also hands on m_t = y_t
    gmu:  out = (m_t * silu(v W_1)) W_2        # m of the SAME position
    swa, full, cross (differential attention; H query heads, G key-value
    heads of D; differential head j of H / 2, g = j // 2):
      [q | k | v] = v W_qkv + b_qkv            # a cross layer: q alone,
                                               # k and v are layer n/2+1's
      P_1 = softmax(q_{2j} k_{2g}^T / sqrt(D) + mask)
      P_2 = softmax(q_{2j+1} k_{2g+1}^T / sqrt(D) + mask)
      V_g = [v_{2g} | v_{2g+1}]                # 2 D wide
      lam_0 = 0.8 - 0.6 exp(-0.3 l)
      lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_0
      o_j = RMSNorm((P_1 - lam P_2) V_g; gamma) * (1 - lam_0)
      out = concat_j(o_j) W_o + b_o
      mask: causal; in a swa layer a query at p sees keys p - W + 1 .. p

``config`` is the model's ``config.json`` as a mapping with the four
Mamba sizes beside it (``mamba_d_state``, ``mamba_d_conv``,
``mamba_dt_rank``; ``d_inner`` is ``in_proj``'s width); ``params`` the
tree the program serves, whatever its storage type: ``embed_tokens [V,
d]``; ``final_layernorm``, ``final_layernorm_bias [d]``; and the layers
of each kind stacked on a leading axis in the order the model has them --
``mamba_layers`` (the memory layer last), ``swa_layers``,
``full_layers`` (one), ``gmu_layers``, ``cross_layers`` -- each with
``input_layernorm``, ``post_attention_layernorm`` (and ``_bias``),
``fc1 [L, d, 2 f]``, ``fc2 [L, f, d]`` and its mixer's leaves.

What the catalog's ``config`` does not carry, and this file therefore
ASSUMES (each an ``assumed`` entry of the configuration's file): the four
Mamba sizes; a bias on ``Wqkv``, ``out_proj`` of an attention, the
convolution and ``dt_proj``, none elsewhere; query heads ``2j``, ``2j +
1`` make differential head ``j``; ``[a | b]``'s order; no norm on ``dt``,
``B``, ``C``; eps ``layer_norm_eps`` in the differential head's RMSNorm;
``m`` includes the ``D`` skip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PRECISION = "highest"
KINDS = ("mamba", "swa", "memory", "full", "gmu", "cross")
MAMBA, SWA, MEMORY, FULL, GMU, CROSS = KINDS
STACKS = {MAMBA: "mamba_layers", SWA: "swa_layers", MEMORY: "mamba_layers",
          FULL: "full_layers", GMU: "gmu_layers", CROSS: "cross_layers"}


def f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def highest(fn):
    """Every matrix product of ``fn`` at the highest precision."""
    def at_highest(*args, **kwargs):
        with jax.default_matmul_precision(PRECISION):
            return fn(*args, **kwargs)
    return at_highest


def layer_kinds(config) -> tuple:
    half = config["num_hidden_layers"] // 2
    return (MAMBA, SWA) * (half // 2) + (MEMORY, FULL) \
        + (GMU, CROSS) * (half // 2 - 1)


def layer_norm(x, gain, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain + bias


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain


def convolution(w, b, x):
    """``x [T, C]`` -> the causal depthwise convolution at every
    position: tap ``k`` of ``w [taps, C]`` meets position ``t - (taps -
    1) + k``, nothing in front of position 0."""
    taps, T = w.shape[0], x.shape[0]
    out = jnp.zeros_like(x) + b
    for k in range(taps):
        back = taps - 1 - k
        shifted = jnp.concatenate(
            [jnp.zeros((back, x.shape[1]), x.dtype), x[:T - back]]) \
            if back else x
        out = out + w[k] * shifted
    return out


def recurrence(u, dt, A, B, C):
    """``s_t = exp(dt_t (x) A) * s_{t-1} + (dt_t * u_t) (x) B_t``, ``y_t
    = s_t C_t``, position by position from ``s = 0``: ``u``, ``dt [T,
    C]``, ``A [C, N]``, ``B``, ``C [T, N]``.  Returns ``y [T, C]`` and the
    state behind the last position ``[C, N]``."""
    def step(s, now):
        u_t, dt_t, B_t, C_t = now
        s = jnp.exp(dt_t[:, None] * A) * s \
            + (dt_t * u_t)[:, None] * B_t[None, :]
        return s, s @ C_t

    last, y = jax.lax.scan(step, jnp.zeros(A.shape, jnp.float32),
                           (u, dt, B, C))
    return y, last


@highest
def mamba_mixer(config, lp, v):
    """The Mamba-1 mixer over the whole sequence ``v [T, d]`` (normed):
    its output, and ``y`` (the scan's output with the ``D`` skip, BEFORE
    the gate): what the memory layer hands on."""
    N, R = config["mamba_d_state"], config["mamba_dt_rank"]
    u, z = jnp.split(v @ lp["in_proj"], 2, axis=-1)
    u = jax.nn.silu(convolution(lp["conv1d_weight"], lp["conv1d_bias"], u))
    rbc = u @ lp["x_proj"]
    r, B, C = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:]
    dt = jax.nn.softplus(r @ lp["dt_proj"] + lp["dt_proj_bias"])
    y, _ = recurrence(u, dt, -jnp.exp(lp["A_log"]), B, C)
    y = y + lp["D"] * u
    return (y * jax.nn.silu(z)) @ lp["out_proj"], y


@highest
def gmu_mixer(lp, v, memory):
    return (memory * jax.nn.silu(v @ lp["in_proj"])) @ lp["out_proj"]


def lambdas(lp, l):
    """``(lam, lam_0)`` of layer ``l``."""
    lam_0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(l, jnp.float32))
    return jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"])) \
        - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + lam_0, lam_0


@highest
def differential_maps(q, k, v, visible, lam):
    """``(P_1 - lam P_2) V_g`` for every differential head: ``q [Tq, H,
    D]`` against ``k``, ``v [Tk, G, D]`` under ``visible [Tq, Tk]``.
    Returns ``[Tq, H / 2, 2 D]``."""
    D = q.shape[-1]
    pair = jnp.arange(q.shape[1] // 2) // 2         # g = j // 2

    def softmax_map(qs, ks):
        scores = jnp.einsum("tjd,ujd->jtu", qs, ks) / math.sqrt(D)
        return jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf),
                              axis=-1)

    P_1 = softmax_map(q[:, 0::2], k[:, 2 * pair])
    P_2 = softmax_map(q[:, 1::2], k[:, 2 * pair + 1])
    V = jnp.concatenate([v[:, 2 * pair], v[:, 2 * pair + 1]], axis=-1)
    return jnp.einsum("jtu,uje->tje", P_1 - lam * P_2, V)


def visible_keys(T: int, window=None):
    at = jnp.arange(T)
    seen = at[None, :] <= at[:, None]
    if window is not None:
        seen = seen & (at[None, :] > at[:, None] - window)
    return seen


@highest
def attention_mixer(config, lp, v, l, window=None, cache=None,
                    rows_at_once=None):
    """Differential attention over the whole sequence ``v [T, d]``
    (normed): with its own keys and values, or (a cross layer: ``Wqkv``
    yields the queries alone) with ``cache = (k, v)`` of another layer.
    Returns the output and the keys and values it attended to.
    ``rows_at_once`` walks the masked square that many query rows at a
    time (a softmax is a row's own: the same numbers), so that 8,255
    positions fit a chip."""
    T = v.shape[0]
    H, G = config["num_attention_heads"], config["num_key_value_heads"]
    D = config["hidden_size"] // H
    qkv = v @ lp["Wqkv"] + lp["Wqkv_bias"]
    q = qkv[:, :H * D].reshape(T, H, D)
    if cache is None:
        cache = (qkv[:, H * D:(H + G) * D].reshape(T, G, D),
                 qkv[:, (H + G) * D:].reshape(T, G, D))
    lam, lam_0 = lambdas(lp, l)
    visible = visible_keys(T, window)
    if rows_at_once is None or rows_at_once >= T:
        o = differential_maps(q, *cache, visible, lam)
    else:
        pad = -T % rows_at_once
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        visible = jnp.pad(visible, ((0, pad), (0, 0)), constant_values=True)
        o = jax.lax.map(
            lambda block: differential_maps(block[0], *cache, block[1], lam),
            (q.reshape(-1, rows_at_once, *q.shape[1:]),
             visible.reshape(-1, rows_at_once, T)))
        o = o.reshape(-1, *o.shape[2:])[:T]
    o = rms_norm(o, lp["subln"], config["layer_norm_eps"]) * (1.0 - lam_0)
    return o.reshape(T, -1) @ lp["out_proj"] + lp["out_proj_bias"], cache


@highest
def mlp(lp, u):
    a, b = jnp.split(u @ lp["fc1"], 2, axis=-1)
    return (b * jax.nn.silu(a)) @ lp["fc2"]


def block(config, kind: str, lp, x, l, memory=None, cache=None,
          rows_at_once=None):
    """Layer ``l`` (of ``kind``) over the whole sequence ``x [T, d]``;
    ``lp`` is that layer's leaves, float32.  Returns the stream behind it
    and what the layer hands on: the memory layer its ``y``, the full
    layer its keys and values, every other layer None."""
    eps = config["layer_norm_eps"]
    v = layer_norm(x, lp["input_layernorm"], lp["input_layernorm_bias"], eps)
    hands_on = None
    if kind in (MAMBA, MEMORY):
        m, y = mamba_mixer(config, lp, v)
        hands_on = y if kind == MEMORY else None
    elif kind == GMU:
        m = gmu_mixer(lp, v, memory)
    else:
        m, own = attention_mixer(
            config, lp, v, l,
            config["sliding_window"] if kind == SWA else None,
            cache if kind == CROSS else None, rows_at_once)
        hands_on = own if kind == FULL else None
    h = x + m
    return h + mlp(lp, layer_norm(h, lp["post_attention_layernorm"],
                                  lp["post_attention_layernorm_bias"],
                                  eps)), hands_on


@highest
def head(config, gain, bias, table, x):
    """The tied head: the final LayerNorm, then every row of the
    embedding."""
    return layer_norm(x, gain, bias, config["layer_norm_eps"]) @ table.T


def layer_params(stack, i):
    return jax.tree_util.tree_map(lambda leaf: f32(leaf[i]), stack)


def forward(config, params, ids):
    """``ids [T]`` -> logits ``[T, V]``, float32: every layer at every
    position."""
    table = f32(params["embed_tokens"])
    x = table[jnp.asarray(ids)]
    at = dict.fromkeys(STACKS.values(), 0)
    memory = cache = None
    for l, kind in enumerate(layer_kinds(config)):
        stack = STACKS[kind]
        x, hands_on = block(config, kind, layer_params(params[stack],
                                                       at[stack]),
                            x, l, memory, cache)
        at[stack] += 1
        if kind == MEMORY:
            memory = hands_on
        elif kind == FULL:
            cache = hands_on
    return head(config, f32(params["final_layernorm"]),
                f32(params["final_layernorm_bias"]), table, x)
