"""The plain reference of the latent-attention decoder whose layer is a
block of two attentions and two dense MLPs with a shortcut-connected
expert layer between them and zero-compute experts in its router
(LongCat-Flash-Omni's language model, ``model_type`` ``longcat_flash``;
the LongCat-Flash technical report, arXiv:2509.01322, and the family's
``modeling_longcat_flash.py``): the equations below in straightforward
``jax.numpy``, float32, every matrix product at
``jax.default_matmul_precision("highest")``.  A full causal forward pass
over one whole sequence: **no cache, no absorption, no batching of
experts** (a loop over experts, every expert over every token), per-head
keys and values expanded at every position, every layer at every
position.  Independent of the program: it imports nothing of
``comfyui_distributed_tpu``.

    x = E[ids]
    for every layer l:                               # one published "layer"
      h  = x  + MLA_0(RMSNorm(x; g_in0))
      u  = RMSNorm(h; g_post0)
      m  = MoE(u)                                    # taken HERE ...
      h  = h  + Dense_0(u)
      h2 = h  + MLA_1(RMSNorm(h; g_in1))
      x  = h2 + Dense_1(RMSNorm(h2; g_post1)) + m    # ... added HERE
    logits = RMSNorm(x; g) W_head

    MLA(n):  c_q = RMSNorm(n W_qa; g_q);  q = (c_q W_qb) * sqrt(d / r_q)
             [c_kv, k_r] = n W_kva;  c_kv = RMSNorm(c_kv; g_kv) * sqrt(d / r_kv)
             [k_nope, v] = c_kv W_kvb -> H x (d_nope + d_v)
             q_r = RoPE(q_rope); k_r = RoPE(k_r)     # ONE key, NOT scaled
             s[h, t, u] = (q_nope[t, h] . k_nope[u, h] + q_r[t, h] . k_r[u])
                          / sqrt(d_nope + d_rope),   causal, softmax
             out = concat_h(s v) W_o
    Dense(n), Expert(n):  (silu(n W_gate) * n W_up) W_down
    MoE(u):  p = softmax(u W_r) over ALL E + Z outputs
             chosen = top-k of (p + b);   w = routed_scaling_factor * p[chosen]
             m = sum_{e chosen, e < E, e in experts_held} w_e Expert_e(u)
                 + (sum_{e chosen, e >= E} w_e) * u

``experts_held`` (a sequence of expert numbers below ``E``, or None for
all) says which real experts THIS share holds; ``params["experts"]``
holds exactly those, in that order.  A pair routed to a real expert that
is not held adds nothing: its own chip would add it.  **The zero experts
hold nothing and are every chip's**: a token's own chip adds their part
whole, so a share's result has it and the sum over all shares counts it
ONCE, like a shared expert.

``config`` is the model's ``config.json`` as a mapping, with the counts AS
HELD (``num_layers`` blocks; ``router_outputs`` = ``E + Z``, the router's
width, so ``E = router_outputs - zero_expert_num``); ``params`` the tree
the program serves, whatever its storage type (the stated bf16 weights are
upcast, value for value):

    embed_tokens [V, d]; norm [d]; lm_head [d, V];
    sublayers: each leaf stacked on ONE leading axis of 2 L, sub-layer s
      of layer l at 2 l + s -- input_layernorm (g_in),
      post_attention_layernorm (g_post) [2L, d]; q_a_proj [2L, d, r_q];
      q_a_layernorm [2L, r_q]; q_b_proj [2L, r_q, H (d_nope + d_rope)];
      kv_a_proj_with_mqa [2L, d, r_kv + d_rope]; kv_a_layernorm [2L, r_kv];
      kv_b_proj [2L, r_kv, H (d_nope + d_v)]; o_proj [2L, H d_v, d];
      gate_proj, up_proj [2L, d, F]; down_proj [2L, F, d];
    router: classifier [L, d, E + Z]; e_score_correction_bias [L, E + Z];
    experts: gate_proj, up_proj [L, E_here, d, f]; down_proj [L, E_here, f, d].

What the catalog's ``config`` does not carry, and this file therefore
ASSUMES (each is an ``assumed`` entry of the configuration's file):

* the block's wiring (the config says ``num_layers`` and nothing of the
  two sub-layers): the report's and the modeling file's;
* ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` are booleans: the factors
  ``sqrt(hidden_size / rank)`` and where they apply (the query behind
  ``q_b_proj``, both halves; the normed ``c_kv`` before ``kv_b_proj``;
  not ``k_r``) are the modeling file's;
* ``silu`` in the dense MLPs and the experts; no bias in any projection;
  no renormalisation of the chosen scores and no router bias TERM (the
  score-correction bias moves the selection only);
* RoPE over INTERLEAVED pairs ``(2i, 2i + 1)`` of the 64 rotary values,
  ``theta ** (-2i / 64)``, no scaling (no ``rope_scaling`` key);
* the RMSNorms on ``c_q`` and ``c_kv``, the final norm before the head,
  eps ``rms_norm_eps`` everywhere.

For a comparison that routing's discontinuity cannot break, `forward`
takes ``choices [T, L, k]``: the outputs to use at every position in
place of its own top-k (their weights still from its own scores).
``wrong`` names ONE departure from the equations above (`WRONG`): what a
comparison has to refuse, for the verify script and the tests.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PRECISION = "highest"

# the departures a comparison has to refuse; None is the model
WRONG = ("zero_experts_left_out", "weights_from_biased_scores",
         "experts_fed_from_second_norm",
         "experts_added_before_second_attention", "kv_scale_left_off")


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


def scales(config, wrong=None):
    """The factors on the expanded query and on the normed latent."""
    d = config["hidden_size"]
    q = math.sqrt(d / config["q_lora_rank"]) \
        if config.get("mla_scale_q_lora", True) else 1.0
    kv = math.sqrt(d / config["kv_lora_rank"]) \
        if config.get("mla_scale_kv_lora", True) \
        and wrong != "kv_scale_left_off" else 1.0
    return q, kv


def attention(config, sp, n, wrong=None):
    """MLA as written: every head's keys and values at every position."""
    T = n.shape[0]
    H = config["num_attention_heads"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    r_kv = config["kv_lora_rank"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    q_scale, kv_scale = scales(config, wrong)
    c_q = rms_norm(n @ sp["q_a_proj"], sp["q_a_layernorm"], eps)
    q = (c_q @ sp["q_b_proj"]).reshape(T, H, dn + dr) * q_scale
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], theta)
    kva = n @ sp["kv_a_proj_with_mqa"]
    c_kv = rms_norm(kva[:, :r_kv], sp["kv_a_layernorm"], eps) * kv_scale
    k_rope = rope(kva[:, r_kv:], theta)                     # [T, dr]: ONE key
    kv = (c_kv @ sp["kv_b_proj"]).reshape(T, H, -1)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("thd,uhd->htu", q_nope, k_nope)
              + jnp.einsum("thd,ud->htu", q_rope, k_rope)) \
        / jnp.sqrt(float(dn + dr))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    a = jnp.einsum("htu,uhd->thd", jax.nn.softmax(scores, axis=-1), v)
    return a.reshape(T, -1) @ sp["o_proj"]


def gated_mlp(w, n):
    return (jax.nn.silu(n @ w["gate_proj"]) * (n @ w["up_proj"])) \
        @ w["down_proj"]


def router(config, rp, u):
    """``p [T, E + Z]``, what the selection goes by ``p + b``, and the
    top-k of that ``[T, k]``."""
    p = jax.nn.softmax(u @ rp["classifier"], axis=-1)
    selected_by = p + rp["e_score_correction_bias"]
    _, chosen = jax.lax.top_k(selected_by, config["moe_topk"])
    return p, selected_by, chosen


def pair_weights(config, rp, p, chosen, wrong=None):
    """``routed_scaling_factor * p[chosen]``: from the UNBIASED scores,
    not renormalised."""
    source = p + rp["e_score_correction_bias"] \
        if wrong == "weights_from_biased_scores" else p
    return jnp.take_along_axis(source, chosen, axis=-1) \
        * float(config["routed_scaling_factor"])


def routed(experts, experts_held, u, chosen, weights):
    """The real experts' part from the experts held: a loop over them,
    each over every token, times the token's weight for it (0 where the
    token did not choose it)."""
    out = jnp.zeros_like(u)
    for at, e in enumerate(experts_held):
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        own = {name: w[at] for name, w in experts.items()}
        out = out + weight[:, None] * gated_mlp(own, u)
    return out


def zero_experts(config, u, chosen, weights):
    """The identity experts' part: the token itself, times the sum of its
    weights for the outputs ``>= E`` it chose."""
    E = config["router_outputs"] - config["zero_expert_num"]
    return jnp.sum(jnp.where(chosen >= E, weights, 0.0), axis=-1,
                   keepdims=True) * u


def highest(fn):
    """Every matrix product of ``fn`` at the highest precision."""
    def at_highest(*args, **kwargs):
        with jax.default_matmul_precision(PRECISION):
            return fn(*args, **kwargs)
    return at_highest


@highest
def attend(config, sp, x, wrong=None):
    """``x + MLA(N_in(x))`` of one sub-layer."""
    n = rms_norm(x, sp["input_layernorm"], config["rms_norm_eps"])
    return x + attention(config, sp, n, wrong)


@highest
def post_norm(config, sp, h):
    """``N_post(h)`` of one sub-layer."""
    return rms_norm(h, sp["post_attention_layernorm"],
                    config["rms_norm_eps"])


gated_mlp = highest(gated_mlp)
router = highest(router)
routed = highest(routed)


def moe(config, rp, experts, experts_held, u, chosen=None, wrong=None):
    """The expert layer on ``u``, and what it selected by, chose and
    weighted."""
    p, selected_by, own = router(config, rp, u)
    chosen = own if chosen is None else chosen
    weights = pair_weights(config, rp, p, chosen, wrong)
    if experts_held is None:
        experts_held = range(config["router_outputs"]
                             - config["zero_expert_num"])
    m = routed(experts, experts_held, u, chosen, weights)
    if wrong != "zero_experts_left_out":
        m = m + zero_experts(config, u, chosen, weights)
    return m, (selected_by, chosen, weights)


def block(config, lp, x, experts_held=None, chosen=None, wrong=None):
    """One published layer over the whole sequence ``x [T, d]``; ``lp``
    is ``{"sublayers": (s0, s1), "router": ..., "experts": ...}``, that
    layer's leaves in float32.  Returns the layer's output and its
    routing: what its router selected by, the choices it used and the
    weights it gave them."""
    s0, s1 = lp["sublayers"]

    def experts_on(u):
        return moe(config, lp["router"], lp["experts"], experts_held, u,
                   chosen, wrong)

    h = attend(config, s0, x, wrong)
    u = post_norm(config, s0, h)
    if wrong != "experts_fed_from_second_norm":
        m, routing = experts_on(u)
    h = h + gated_mlp(s0, u)
    if wrong == "experts_added_before_second_attention":
        h, m = h + m, 0.0
    h2 = attend(config, s1, h, wrong)
    n = post_norm(config, s1, h2)
    if wrong == "experts_fed_from_second_norm":
        m, routing = experts_on(n)
    return h2 + gated_mlp(s1, n) + m, routing


def head(config, params, x):
    with jax.default_matmul_precision(PRECISION):
        return rms_norm(x, f32(params["norm"]), config["rms_norm_eps"]) \
            @ f32(params["lm_head"])


def layer_params(params, l):
    """Layer ``l``'s leaves in float32: its two sub-layers, its router,
    its experts."""
    def at(stack, i):
        return jax.tree_util.tree_map(lambda leaf: f32(leaf[i]), stack)
    return {"sublayers": (at(params["sublayers"], 2 * l),
                          at(params["sublayers"], 2 * l + 1)),
            "router": at(params["router"], l),
            "experts": at(params["experts"], l)}


def forward(config, params, ids, experts_held=None, choices=None,
            wrong=None):
    """``ids [T]`` -> logits ``[T, V]``, what the routers selected by
    ``[T, L, E + Z]``, the choices used and their weights ``[T, L, k]``,
    float32 / int32."""
    assert wrong is None or wrong in WRONG, wrong
    x = f32(params["embed_tokens"])[jnp.asarray(ids)]
    routing = []
    for l in range(config["num_layers"]):
        x, routed_by = block(
            config, layer_params(params, l), x, experts_held,
            None if choices is None else jnp.asarray(choices)[:, l], wrong)
        routing.append(routed_by)
    return (head(config, params, x),
            *(jnp.stack(r, axis=1) for r in zip(*routing)))
