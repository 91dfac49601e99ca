"""The plain reference of the latent-attention decoder with routed
experts (openPangu-Ultra-MoE, ``model_type`` ``pangu_ultra_moe``; the
family's convention is DeepSeek-V3's ``modeling_deepseek.py`` with
sandwich norms): the equations below in straightforward ``jax.numpy``,
float32, every matrix product at
``jax.default_matmul_precision("highest")``.  A full causal forward pass
over one whole sequence: **no cache, no absorption, no batching of
experts** (a loop over experts, every expert over every token), per-head
keys and values expanded at every position.  Independent of the program:
it imports nothing of ``comfyui_distributed_tpu``.

    x = E[ids]
    for every block l:
      n = RMSNorm(x; g1)
      c_q = RMSNorm(n W_qa; g_q);     q = c_q W_qb -> H x (d_nope + d_rope)
      [c_kv, k_r] = n W_kva;          c_kv = RMSNorm(c_kv; g_kv)
      [k_nope, v] = c_kv W_kvb -> H x (d_nope + d_v)
      q_r = RoPE(q_rope); k_r = RoPE(k_r)          # k_r shared by all heads
      s[h, t, u] = (q_nope[t, h] . k_nope[u, h] + q_r[t, h] . k_r[u])
                   / sqrt(d_nope + d_rope),   causal, softmax
      a = concat_h(s v) W_o
      h = x + RMSNorm(a; g2)                       # sandwich norm
      n = RMSNorm(h; g3)
      m = MLP(n)                                   # a leading dense block
        | Shared(n) + sum_{e in top-k(n), e in experts_held} w_e Expert_e(n)
      x = h + RMSNorm(m; g4)                       # sandwich norm
    logits = RMSNorm(x; g) W_head

    router:  s = sigmoid(n W_g) over ALL E experts; top-k;
             w = s_topk / sum(s_topk) * routed_scaling_factor
    expert, shared expert, dense MLP:  (silu(n W_gate) * n W_up) W_down

``experts_held`` (a sequence of expert numbers, or None for all) says
which routed experts THIS share holds; ``params["moe_layers"]["experts"]``
holds exactly those, in that order.  A pair routed to an expert that is
not held adds nothing: its own chip would add it.  The shared expert is
every chip's.

``config`` is the model's ``config.json`` as a mapping, with the counts
AS HELD (``num_hidden_layers`` blocks of which ``dense_layers_held``
leading dense ones, ``router_outputs`` = the router's width E);
``params`` the tree the program serves, whatever its storage type (the
stated bf16 weights are upcast, value for value):

    embed_tokens [V, d]; norm [d]; lm_head [d, V];
    dense_layers / moe_layers: each leaf stacked on a leading layer axis --
      input_layernorm (g1), post_attention_layernorm (g2),
      pre_mlp_layernorm (g3), post_mlp_layernorm (g4) [L, d];
      q_a_proj [L, d, r_q]; q_a_layernorm [L, r_q];
      q_b_proj [L, r_q, H (d_nope + d_rope)];
      kv_a_proj_with_mqa [L, d, r_kv + d_rope]; kv_a_layernorm [L, r_kv];
      kv_b_proj [L, r_kv, H (d_nope + d_v)]; o_proj [L, H d_v, d];
    dense_layers: gate_proj, up_proj [L, d, F]; down_proj [L, F, d];
    moe_layers: gate [L, d, E]; shared_experts / experts: gate_proj,
      up_proj, down_proj, the experts' with ``[L, E_here, ...]``.

What the catalog's ``config`` does not carry, and this file therefore
ASSUMES (each is an ``assumed`` entry of the configuration's file):

* sandwich norms g2 and g4 on each sub-layer's OUTPUT before the
  residual add (``sandwich_norm: true`` says they exist, not where);
* RoPE over INTERLEAVED pairs ``(2i, 2i + 1)`` of the 64 rotary values,
  ``theta ** (-2i / 64)``, no scaling (no ``rope_scaling`` key);
* no bias anywhere (``attention_bias: false`` covers the projections);
* sigmoid scoring with no score-correction bias and no expert groups
  (the config has no ``scoring_func``, ``topk_method`` or ``n_group``);
* the RMSNorms on ``c_q`` and ``c_kv`` (DeepSeek-V3's ``q_a_layernorm``
  / ``kv_a_layernorm``), the final norm before the head.

For a comparison that routing's discontinuity cannot break, `forward`
takes ``choices [T, Le, k]``: the experts to use at every position in
place of its own top-k (their weights still from its own scores).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISION = "highest"


def f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain


def rope(x, theta):
    """``x [T, ..., D]`` rotated to positions ``0..T-1``, pair ``i`` =
    values ``(2i, 2i + 1)``, as a complex product."""
    T, D = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape(T, *([1] * (x.ndim - 2)), D // 2)
    pairs = x.reshape(*x.shape[:-1], D // 2, 2)
    z = jax.lax.complex(pairs[..., 0], pairs[..., 1]) \
        * jax.lax.complex(jnp.cos(ang), jnp.sin(ang))
    return jnp.stack([jnp.real(z), jnp.imag(z)], axis=-1).reshape(x.shape)


def attention(config, lp, x):
    """MLA as written: every head's keys and values at every position."""
    T = x.shape[0]
    H = config["num_attention_heads"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    r_kv = config["kv_lora_rank"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    c_q = rms_norm(x @ lp["q_a_proj"], lp["q_a_layernorm"], eps)
    q = (c_q @ lp["q_b_proj"]).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], theta)
    kva = x @ lp["kv_a_proj_with_mqa"]
    c_kv = rms_norm(kva[:, :r_kv], lp["kv_a_layernorm"], eps)
    k_rope = rope(kva[:, r_kv:], theta)                     # [T, dr]: ONE key
    kv = (c_kv @ lp["kv_b_proj"]).reshape(T, H, -1)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("thd,uhd->htu", q_nope, k_nope)
              + jnp.einsum("thd,ud->htu", q_rope, k_rope)) \
        / jnp.sqrt(float(dn + dr))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    a = jnp.einsum("htu,uhd->thd", jax.nn.softmax(scores, axis=-1), v)
    return a.reshape(T, -1) @ lp["o_proj"]


def gated_mlp(w, n):
    return (jax.nn.silu(n @ w["gate_proj"]) * (n @ w["up_proj"])) \
        @ w["down_proj"]


def router(config, gate, n):
    """Scores over all experts ``[T, E]``, the top-k ``[T, k]``."""
    scores = jax.nn.sigmoid(n @ gate)
    _, chosen = jax.lax.top_k(scores, config["num_experts_per_tok"])
    return scores, chosen


def routed(config, experts, experts_held, n, scores, chosen):
    """The routed experts' part from the experts held: a loop over them,
    each over every token, times the token's weight for it (0 where the
    token did not choose it)."""
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    if config.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * float(config["routed_scaling_factor"])
    out = jnp.zeros_like(n)
    for at, e in enumerate(experts_held):
        weight = jnp.sum(jnp.where(chosen == e, top, 0.0), axis=-1)
        own = {name: w[at] for name, w in experts.items()}
        out = out + weight[:, None] * gated_mlp(own, n)
    return out


def highest(fn):
    """Every matrix product of ``fn`` at the highest precision."""
    def at_highest(*args, **kwargs):
        with jax.default_matmul_precision(PRECISION):
            return fn(*args, **kwargs)
    return at_highest


@highest
def attend(config, lp, x):
    """``h = x + N2(Attn(N1(x)))``."""
    eps = config["rms_norm_eps"]
    a = attention(config, lp, rms_norm(x, lp["input_layernorm"], eps))
    return x + rms_norm(a, lp["post_attention_layernorm"], eps)


@highest
def mlp_input(config, lp, h):
    """``N3(h)`` and, in an expert block, the router's scores and its own
    top-k over it."""
    n = rms_norm(h, lp["pre_mlp_layernorm"], config["rms_norm_eps"])
    return (n, *router(config, lp["gate"], n)) if "gate" in lp else (n,)


@highest
def finish(config, lp, h, m):
    """``h + N4(m)``."""
    return h + rms_norm(m, lp["post_mlp_layernorm"], config["rms_norm_eps"])


gated_mlp = highest(gated_mlp)
routed = highest(routed)


def block(config, lp, x, experts_held=None, chosen=None):
    """One block over the whole sequence ``x [T, d]``; ``lp`` is that
    block's leaves, float32.  An expert block (``"gate"`` in ``lp``) also
    returns its router's scores and the choices it used."""
    h = attend(config, lp, x)
    n, *routing = mlp_input(config, lp, h)
    if not routing:
        return finish(config, lp, h, gated_mlp(lp, n)), None
    scores, own = routing
    chosen = own if chosen is None else chosen
    if experts_held is None:
        experts_held = range(config["router_outputs"])
    m = gated_mlp(lp["shared_experts"], n) + routed(
        config, lp["experts"], experts_held, n, scores, chosen)
    return finish(config, lp, h, m), (scores, chosen)


def head(config, params, x):
    with jax.default_matmul_precision(PRECISION):
        return rms_norm(x, f32(params["norm"]), config["rms_norm_eps"]) \
            @ f32(params["lm_head"])


def layer_params(stack, l):
    return jax.tree_util.tree_map(lambda leaf: f32(leaf[l]), stack)


def forward(config, params, ids, experts_held=None, choices=None):
    """``ids [T]`` -> logits ``[T, V]``, router scores ``[T, Le, E]`` and
    the choices used ``[T, Le, k]``, float32 / int32."""
    x = f32(params["embed_tokens"])[jnp.asarray(ids)]
    dense = config["dense_layers_held"]
    scores, used = [], []
    for l in range(config["num_hidden_layers"]):
        if l < dense:
            x, _ = block(config, layer_params(params["dense_layers"], l), x)
            continue
        at = l - dense
        x, (s, c) = block(
            config, layer_params(params["moe_layers"], at), x, experts_held,
            None if choices is None else jnp.asarray(choices)[:, at])
        scores.append(s)
        used.append(c)
    return head(config, params, x), jnp.stack(scores, axis=1), \
        jnp.stack(used, axis=1)
