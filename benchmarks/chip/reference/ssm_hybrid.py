"""The plain reference of the decoder of state-space (Mamba-2) and
attention layers (ibm-granite/granite-4.0-h-micro, ``model_type``
``granitemoehybrid`` with no routed expert; the equations are the
family's published modeling file's): the equations below in
straightforward ``jax.numpy``, float32, every matrix product at
``jax.default_matmul_precision("highest")``.  A full forward pass over
one whole sequence: **the recurrence written as the recurrence** (a
``lax.scan`` over positions, one state update a position), the
convolution as a sum over its taps at every position, **no chunks, no
cache, no tail, no batching, no padding**; every layer a step of a Python
loop, the mask a full ``[T, T]`` matrix, every key-value head repeated
for the query heads that read it.  Independent of the program: it imports
nothing of ``comfyui_distributed_tpu``.

    x = embedding_multiplier * E[ids]
    for every block l:
      h = x + residual_multiplier * Mixer_l(RMSNorm(x; g_1))
      x = h + residual_multiplier * MLP_l(RMSNorm(h; g_2))
    logits = RMSNorm(x; g) E^T / logits_scaling           # tied: the head is E

    MLP(u):  [a | b] = u W_in;  (silu(a) * b) W_out

    attention block:
      q = u W_q -> H heads of D;  k, v = u W_k, u W_v -> G heads of D
      no bias, no rotation, no norm on a head
      s[h, t, r] = q[t, h] . k[r, h // (H / G)] * attention_multiplier,
                   r <= t;  softmax over r
      concat_h(s v[:, h // (H / G)]) W_o

    mamba block (Mamba-2; one group: B and C shared by every head):
      [z | xBC | dt] = u W_in           # d_inner | d_inner + 2 n | heads
      xBC_t = silu(b + sum_k w[k] xBC_{t - (taps - 1) + k})   # 0 before t = 0
      [x | B | C] = xBC                 # heads x d_head | n | n
      dt = softplus(dt + dt_bias);  A = -exp(A_log)           # a head each
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,  S_{-1} = 0
      y_t = S_t C_t + D x_t
      RMSNorm(y * silu(z); g_n) W_out   # the norm over all d_inner values

``config`` is the model's ``config.json`` as a mapping; ``params`` the
tree the program serves, whatever its storage type:

    embed_tokens [V, d]; norm (g) [d];
    mamba_layers / attention_layers: the blocks of each kind in the order
      ``layer_types`` gives them, each leaf stacked on a leading axis --
      input_layernorm (g_1), post_attention_layernorm (g_2) [L, d];
      input_linear (W_in) [L, d, 2 f]; output_linear (W_out) [L, f, d];
    attention_layers: q_proj [L, d, H D]; k_proj, v_proj [L, d, G D];
      o_proj [L, H D, d];
    mamba_layers: in_proj_zx [L, d, d_inner + d_inner + 2 n] (the z | xBC
      columns of the published in_proj) and in_proj_dt [L, d, heads] (its
      dt columns); conv1d_weight (w) [L, taps, d_inner + 2 n];
      conv1d_bias (b); dt_bias, A_log, D [L, heads]; norm (g_n)
      [L, d_inner]; out_proj [L, d_inner, d].

What the catalog's ``config`` does not carry, and this file therefore
ASSUMES (each is an ``assumed`` entry of the configuration's file):

* the order of ``in_proj``'s columns (z, x, B, C, dt) and of the
  convolution's channels (x, B, C): Mamba-2's;
* the convolution is a cross-correlation whose last tap meets the
  position itself, with a bias, and ``silu`` behind it;
* ``dt`` is ``softplus(dt + dt_bias)`` with no clamp (``time_step_limit``
  (0, inf)); ``D`` multiplies ``x`` a head;
* the gated norm multiplies by ``silu(z)`` BEFORE it norms, over all
  ``d_inner`` values as one group, eps ``rms_norm_eps``;
* pre-norm blocks with the residual multiplier on both updates; the
  shared MLP's first product yields ``[a | b]`` in that order; a final
  norm; the logits DIVIDED by ``logits_scaling``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISION = "highest"
MAMBA = "mamba"


def f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain


def highest(fn):
    """Every matrix product of ``fn`` at the highest precision."""
    def at_highest(*args, **kwargs):
        with jax.default_matmul_precision(PRECISION):
            return fn(*args, **kwargs)
    return at_highest


def sizes(config):
    """``(heads, d_head, n, d_inner)`` of the Mamba mixer."""
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    return heads, p, config["mamba_d_state"], heads * p


def convolution(w, b, xbc):
    """``xbc [T, C]`` -> the causal depthwise convolution at every
    position: tap ``k`` of ``w [taps, C]`` meets position ``t - (taps -
    1) + k``, nothing in front of position 0."""
    taps, T = w.shape[0], xbc.shape[0]
    out = jnp.zeros_like(xbc) + b
    for k in range(taps):
        back = taps - 1 - k
        shifted = jnp.concatenate(
            [jnp.zeros((back, xbc.shape[1]), xbc.dtype), xbc[:T - back]]) \
            if back else xbc
        out = out + w[k] * shifted
    return out


def recurrence(x, dt, A, B, C):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t
    C_t``, position by position from ``S = 0``: ``x [T, heads, p]``,
    ``dt [T, heads]``, ``A [heads]``, ``B``, ``C [T, n]``.  Returns ``y
    [T, heads, p]`` and the state behind the last position ``[heads, p,
    n]``."""
    def step(S, now):
        x_t, dt_t, B_t, C_t = now
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :]
        return S, jnp.sum(S * C_t[None, None, :], axis=-1)

    start = jnp.zeros((*x.shape[1:], B.shape[-1]), jnp.float32)
    last, y = jax.lax.scan(step, start, (x, dt, B, C))
    return y, last


@highest
def mamba_mixer(config, lp, u):
    """The Mamba-2 mixer over the whole sequence ``u [T, d]`` (normed);
    also returns the state behind the last position."""
    heads, p, n, d_inner = sizes(config)
    T = u.shape[0]
    zx = u @ lp["in_proj_zx"]
    z, xbc = zx[:, :d_inner], zx[:, d_inner:]
    dt = jax.nn.softplus(u @ lp["in_proj_dt"] + lp["dt_bias"])
    xbc = jax.nn.silu(convolution(lp["conv1d_weight"], lp["conv1d_bias"],
                                  xbc))
    x = xbc[:, :d_inner].reshape(T, heads, p)
    B, C = xbc[:, d_inner:d_inner + n], xbc[:, d_inner + n:]
    y, last = recurrence(x, dt, -jnp.exp(lp["A_log"]), B, C)
    y = (y + lp["D"][:, None] * x).reshape(T, d_inner)
    y = rms_norm(y * jax.nn.silu(z), lp["norm"], config["rms_norm_eps"])
    return y @ lp["out_proj"], last


@highest
def attention_mixer(config, lp, u):
    """Grouped-query attention over the whole sequence ``u [T, d]``
    (normed): no position reaches a query or a key."""
    T = u.shape[0]
    H, G = config["num_attention_heads"], config["num_key_value_heads"]
    D = config["hidden_size"] // H
    q = (u @ lp["q_proj"]).reshape(T, H, D)
    k = (u @ lp["k_proj"]).reshape(T, G, D)
    v = (u @ lp["v_proj"]).reshape(T, G, D)
    # query head h reads key-value head h // (H / G)
    k, v = (jnp.repeat(t, H // G, axis=1) for t in (k, v))
    at = jnp.arange(T)
    scores = jnp.einsum("thd,uhd->htu", q, k) \
        * float(config["attention_multiplier"])
    scores = jnp.where((at[None, :] <= at[:, None])[None], scores, -jnp.inf)
    a = jnp.einsum("htu,uhd->thd", jax.nn.softmax(scores, axis=-1), v)
    return a.reshape(T, -1) @ lp["o_proj"]


@highest
def mlp(lp, u):
    a, b = jnp.split(u @ lp["input_linear"], 2, axis=-1)
    return (jax.nn.silu(a) * b) @ lp["output_linear"]


def block(config, kind: str, lp, x):
    """One block over the whole sequence ``x [T, d]``; ``lp`` is that
    block's leaves, float32.  A Mamba block also returns the state behind
    the last position (None for an attention block)."""
    eps, res = config["rms_norm_eps"], float(config["residual_multiplier"])
    u = rms_norm(x, lp["input_layernorm"], eps)
    if kind == MAMBA:
        m, last = mamba_mixer(config, lp, u)
    else:
        m, last = attention_mixer(config, lp, u), None
    h = x + res * m
    return h + res * mlp(lp, rms_norm(h, lp["post_attention_layernorm"],
                                      eps)), last


def embed(config, table, ids):
    return float(config["embedding_multiplier"]) * table[jnp.asarray(ids)]


@highest
def head(config, norm, table, x):
    """The tied head: the final norm, then every row of the embedding."""
    return rms_norm(x, norm, config["rms_norm_eps"]) @ table.T \
        / float(config["logits_scaling"])


def layer_params(stack, l):
    return jax.tree_util.tree_map(lambda leaf: f32(leaf[l]), stack)


def forward(config, params, ids):
    """``ids [T]`` -> logits ``[T, V]`` and the Mamba layers' states
    behind the last position ``[L_m, heads, d_head, n]``, float32."""
    table = f32(params["embed_tokens"])
    x = embed(config, table, ids)
    at = {MAMBA: 0, "attention": 0}
    states = []
    for kind in config["layer_types"]:
        stack = params["mamba_layers" if kind == MAMBA
                       else "attention_layers"]
        x, last = block(config, kind, layer_params(stack, at[kind]), x)
        at[kind] += 1
        if last is not None:
            states.append(last)
    return head(config, f32(params["norm"]), table, x), jnp.stack(states)
