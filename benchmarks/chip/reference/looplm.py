"""The plain reference of the looped language model (Ouro; arXiv
2510.25741 "Scaling Latent Reasoning via Looped Language Models", and the
model's ``modeling_ouro.py``): the equations below in straightforward
``jax.numpy``, float32, every matrix product at
``jax.default_matmul_precision("highest")``.  A full causal forward pass
over one whole sequence: no cache, no scan, no batch.  Independent of the
program: it imports nothing of ``comfyui_distributed_tpu``.

    x = E[ids]
    for r in 0..R-1:                      # the SAME layers every time round
      for l in 0..L-1:
        n = RMSNorm(x; g1_l)
        a = Wo_l Attn(RoPE(Wq_l n), RoPE(Wk_l n), Wv_l n; causal)
        x = x + RMSNorm(a; g2_l)
        n = RMSNorm(x; g3_l)
        m = Wdown_l (silu(Wgate_l n) * (Wup_l n))
        x = x + RMSNorm(m; g4_l)
      x = RMSNorm(x; g_final)
      p_exit[r] = sigmoid(w_gate . x + b_gate)
    logits = W_head x

``config`` is the model's ``config.json`` as a mapping.  ``params`` is
the tree the program serves, whatever its storage type (the stated bf16
weights are upcast, value for value):

    embed_tokens [V, d]; norm [d]; lm_head [d, V];
    early_exit_gate: kernel [d], bias [];
    layers: each leaf stacked on a leading L axis --
      input_layernorm (g1), input_layernorm_2 (g2),
      post_attention_layernorm (g3), post_attention_layernorm_2 (g4) [L, d];
      q_proj, k_proj, v_proj [L, d, H*D]; o_proj [L, H*D, d];
      gate_proj, up_proj [L, d, F]; down_proj [L, F, d]   (kernels [in, out])

What the catalog's ``config`` does not carry, and this file therefore
ASSUMES (each is an ``assumed`` entry of the configuration's file):

* no bias on any projection;
* the sandwich norms g2 and g4 on each sub-layer's OUTPUT, before the
  residual add;
* the final norm after EVERY loop, its output being the next loop's
  input;
* the exit gate as ``Linear(d, 1)`` on the normed state, read here as a
  plain sigmoid per loop (the paper turns these into a distribution over
  exit steps; with ``early_exit_threshold`` 1.0 none is taken and the
  last loop's state is read);
* RoPE in the ``rotate_half`` convention over the whole head,
  ``theta ** (-2i / D)``, no scaling;
* every head a key/value head (16 = 16 in the published config).
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
    """``x [T, H, D]`` rotated to positions ``0..T-1``."""
    T, _, D = x.shape
    half = D // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def layer(config, lp, x):
    """One application of one layer to the whole sequence ``x [T, d]``;
    ``lp`` is that layer's leaves, float32."""
    T = x.shape[0]
    H, D = config["num_attention_heads"], config["head_dim"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    with jax.default_matmul_precision(PRECISION):
        n = rms_norm(x, lp["input_layernorm"], eps)
        q = rope((n @ lp["q_proj"]).reshape(T, H, D), theta)
        k = rope((n @ lp["k_proj"]).reshape(T, H, D), theta)
        v = (n @ lp["v_proj"]).reshape(T, H, D)
        scores = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(float(D))
        causal = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        a = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
        a = a.reshape(T, H * D) @ lp["o_proj"]
        x = x + rms_norm(a, lp["input_layernorm_2"], eps)
        n = rms_norm(x, lp["post_attention_layernorm"], eps)
        m = (jax.nn.silu(n @ lp["gate_proj"]) * (n @ lp["up_proj"])) \
            @ lp["down_proj"]
        return x + rms_norm(m, lp["post_attention_layernorm_2"], eps)


def end_of_loop(config, params, x):
    """The final norm and the exit gate: the next loop's input and this
    loop's exit probability ``[T]``."""
    with jax.default_matmul_precision(PRECISION):
        x = rms_norm(x, f32(params["norm"]), config["rms_norm_eps"])
        gate = params["early_exit_gate"]
        return x, jax.nn.sigmoid(x @ f32(gate["kernel"]) + f32(gate["bias"]))


def head(params, x):
    with jax.default_matmul_precision(PRECISION):
        return x @ f32(params["lm_head"])


def layer_params(params, l):
    return {name: f32(leaf[l]) for name, leaf in params["layers"].items()}


def forward(config, params, ids):
    """``ids [T]`` -> logits ``[T, V]`` and exit probabilities ``[T, R]``,
    float32."""
    x = f32(params["embed_tokens"])[jnp.asarray(ids)]
    exits = []
    for _ in range(config["total_ut_steps"]):
        for l in range(config["num_hidden_layers"]):
            x = layer(config, layer_params(params, l), x)
        x, p_exit = end_of_loop(config, params, x)
        exits.append(p_exit)
    return head(params, x), jnp.stack(exits, axis=-1)
