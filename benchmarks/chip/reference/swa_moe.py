"""The plain reference of the decoder with window and full attention
layers in one stack, grouped key-value heads and routed experts
(K-EXAONE-236B-A23B, ``model_type`` ``exaone_moe``; the family's
convention is EXAONE 4.0's modeling file): the equations below in
straightforward ``jax.numpy``, float32, every matrix product at
``jax.default_matmul_precision("highest")``.  A full forward pass over
one whole sequence: **no cache, no ring, no scan, no kernel, no batching
of experts**: every layer a step of a Python loop, every mask a full
``[T, T]`` matrix built from positions, every key-value head repeated
for the query heads that read it.  Independent of the program: it
imports nothing of ``comfyui_distributed_tpu``.

    x = E[ids]
    for every block l:
      q = x W_q -> H heads of D;  k, v = x W_k, x W_v -> G heads of D
      q = RMSNorm(q; g_q), k = RMSNorm(k; g_k)     # over each head's D values
      sliding layer:  q, k = RoPE(q), RoPE(k);  seen[t, u] = 0 <= t - u < window
      full layer:     no rotation;              seen[t, u] = u <= t
      s[h, t, u] = q[t, h] . k[u, h // (H / G)] / sqrt(D),  masked, softmax
      a = concat_h(s v[:, h // (H / G)]) W_o
      h = x + RMSNorm(a; g_a)                      # norms behind the
      m = MLP(h)                                   # sub-layers, none before
        | Shared(h) + sum_{e in top-k(h), e in experts_held} w_e Expert_e(h)
      x = h + RMSNorm(m; g_f)
    logits = RMSNorm(x; g) W_head

    router:  s = sigmoid(h W_g) over ALL E experts; top-k;
             w = s_topk / sum(s_topk) * routed_scaling_factor
    expert, shared expert, dense MLP:  (silu(h W_gate) * h W_up) W_down

``experts_held`` (a sequence of expert numbers, or None for all) says
which routed experts THIS share holds; ``params["moe_layers"]["experts"]``
holds exactly those, in that order.  A pair routed to an expert that is
not held adds nothing: its own chip would add it.

``config`` is the model's ``config.json`` as a mapping, with the counts
AS HELD (``num_hidden_layers`` blocks of which ``dense_layers_held``
leading dense ones, ``layer_types`` one entry a block held,
``router_outputs`` = the router's width E); ``params`` the tree the
program serves, whatever its storage type:

    embed_tokens [V, d]; norm [d]; lm_head [d, V];
    dense_layers / moe_layers: each leaf stacked on a leading layer axis --
      q_proj [L, d, H D]; k_proj, v_proj [L, d, G D]; o_proj [L, H D, d];
      q_norm (g_q), k_norm (g_k) [L, D];
      post_attention_layernorm (g_a), post_feedforward_layernorm (g_f) [L, d];
    dense_layers: gate_proj, up_proj [L, d, F]; down_proj [L, F, d];
    moe_layers: gate [L, d, E]; shared_experts / experts: gate_proj,
      up_proj, down_proj, the experts' with ``[L, E_here, ...]``.

What the catalog's ``config`` does not carry, and this file therefore
ASSUMES (each is an ``assumed`` entry of the configuration's file):

* the two RMSNorms of a block stand BEHIND the sub-layers (g_a, g_f), and
  none stands before them;
* RMSNorm over the D values of every query and key head before the
  rotation;
* RoPE (``rotate_half``: value ``i`` pairs with ``i + D/2``;
  ``theta ** (-2i / D)``, no scaling) on the sliding layers only;
* ``sliding_window`` counts the query's own position;
* no bias anywhere; sigmoid scoring with no groups and no correction
  bias; the shared expert one gated MLP added unweighted; a final norm.

`forward` takes ``choices [T, Le, k]`` (the experts to use in place of
its own top-k, their weights still from its own scores) as
``reference/mla_moe.py`` does, and ``window`` to run with another window
than the configuration's (None: every layer sees every earlier key -- the
reading that a comparison which cannot see the mechanism would accept).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISION = "highest"
SLIDING = "sliding_attention"
CONFIGURED = "configured"


def f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain


def rope(x, theta):
    """``x [T, heads, D]`` rotated to positions ``0..T-1``: value ``i``
    pairs with value ``i + D/2``."""
    T, D = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    turned = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + turned * sin


def attention(config, lp, x, sliding: bool, window):
    """One layer's attention over the whole sequence ``x [T, d]``."""
    T = x.shape[0]
    H, G = config["num_attention_heads"], config["num_key_value_heads"]
    D, eps = config["head_dim"], config["rms_norm_eps"]
    q = rms_norm((x @ lp["q_proj"]).reshape(T, H, D), lp["q_norm"], eps)
    k = rms_norm((x @ lp["k_proj"]).reshape(T, G, D), lp["k_norm"], eps)
    v = (x @ lp["v_proj"]).reshape(T, G, D)
    at = jnp.arange(T)
    seen = at[None, :] <= at[:, None]
    if sliding:
        q, k = rope(q, float(config["rope_theta"])), \
            rope(k, float(config["rope_theta"]))
        if window is not None:
            seen = seen & (at[:, None] - at[None, :] < window)
    # query head h reads key-value head h // (H / G)
    k, v = (jnp.repeat(t, H // G, axis=1) for t in (k, v))
    scores = jnp.einsum("thd,uhd->htu", q, k) / jnp.sqrt(float(D))
    scores = jnp.where(seen[None], scores, -jnp.inf)
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
def attend(config, lp, x, sliding: bool, window):
    """``h = x + N_a(Attn(x))``."""
    a = attention(config, lp, x, sliding, window)
    return x + rms_norm(a, lp["post_attention_layernorm"],
                        config["rms_norm_eps"])


@highest
def finish(config, lp, h, m):
    """``h + N_f(m)``."""
    return h + rms_norm(m, lp["post_feedforward_layernorm"],
                        config["rms_norm_eps"])


gated_mlp = highest(gated_mlp)
routed = highest(routed)
router = highest(router)


def window_of(config, window=CONFIGURED):
    return config["sliding_window"] if window == CONFIGURED else window


def block(config, lp, x, sliding: bool, window, experts_held=None,
          chosen=None):
    """One block over the whole sequence ``x [T, d]``; ``lp`` is that
    block's leaves, float32.  An expert block (``"gate"`` in ``lp``) also
    returns its router's scores and the choices it used."""
    h = attend(config, lp, x, sliding, window)
    if "gate" not in lp:
        return finish(config, lp, h, gated_mlp(lp, h)), None
    scores, own = router(config, lp["gate"], h)
    chosen = own if chosen is None else chosen
    if experts_held is None:
        experts_held = range(config["router_outputs"])
    m = gated_mlp(lp["shared_experts"], h) + routed(
        config, lp["experts"], experts_held, h, scores, chosen)
    return finish(config, lp, h, m), (scores, chosen)


def head(config, params, x):
    with jax.default_matmul_precision(PRECISION):
        return rms_norm(x, f32(params["norm"]), config["rms_norm_eps"]) \
            @ f32(params["lm_head"])


def layer_params(stack, l):
    return jax.tree_util.tree_map(lambda leaf: f32(leaf[l]), stack)


def forward(config, params, ids, experts_held=None, choices=None,
            window=CONFIGURED):
    """``ids [T]`` -> logits ``[T, V]``, router scores ``[T, Le, E]`` and
    the choices used ``[T, Le, k]``, float32 / int32."""
    x = f32(params["embed_tokens"])[jnp.asarray(ids)]
    dense = config["dense_layers_held"]
    window = window_of(config, window)
    scores, used = [], []
    for l in range(config["num_hidden_layers"]):
        sliding = config["layer_types"][l] == SLIDING
        if l < dense:
            x, _ = block(config, layer_params(params["dense_layers"], l), x,
                         sliding, window)
            continue
        at = l - dense
        x, (s, c) = block(
            config, layer_params(params["moe_layers"], at), x, sliding,
            window, experts_held,
            None if choices is None else jnp.asarray(choices)[:, at])
        scores.append(s)
        used.append(c)
    return head(config, params, x), jnp.stack(scores, axis=1), \
        jnp.stack(used, axis=1)
