"""A looped language model (Ouro, arXiv 2510.25741 "Scaling Latent
Reasoning via Looped Language Models"): a decoder-only transformer whose
stack of ``L`` layers runs ``R`` times round with the SAME weights.

    x = E[ids]
    for r in 0..R-1:
      for l in 0..L-1:
        a = Wo_l Attn(RoPE(Wq_l n), RoPE(Wk_l n), Wv_l n; causal; slot (r, l)),  n  = RMSNorm(x; g1_l)
        x = x + RMSNorm(a; g2_l)                                   # sandwich norm
        m = Wdown_l (silu(Wgate_l n') * (Wup_l n')),               n' = RMSNorm(x; g3_l)
        x = x + RMSNorm(m; g4_l)                                   # sandwich norm
      x = RMSNorm(x; g_final)                                      # after every loop
      p_exit[r] = sigmoid(w_gate . x + b_gate)
    logits = W_head x                                              # the last loop's state

Keys and values of loop ``r``, layer ``l`` are not those of loop ``r'``,
layer ``l``: the cache has ``R x L`` slots.  The plain float32 statement
of the same equations, with no cache, is
``benchmarks/chip/reference/looplm.py``; tests hold this file to it.

Served as ONE jitted program, ``lm_generate``: the prefill of the padded
prompt, then exactly ``max_new_tokens`` decode steps in a ``lax.scan``,
each through the whole cache, so no token costs a host dispatch.  The
layers' weights are stacked on a leading ``L`` axis and scanned
(``lax.scan`` over ``l``, a Python loop over ``r``): the 192 layer
applications of the 2.6 B model trace and compile as one body.

The rows of a call are requests of different people: each has its own
real length, seed and temperature, and a decode step reads the weights
once for all of them.  Inside the program every row's prompt is moved to
the END of the prompt buffer, so that all rows write the cache at one
shared index (one in-place ``dynamic_update_slice`` a slot, whatever the
rows' lengths); a row's padding then lies in front of it and a per-row
lower bound in the mask hides it.  RoPE is given each row's own
positions, so a row's numbers are those of its single-row run.

Precision: weights, cache and matmul operands in ``cfg.dtype`` (bf16 for
the published model); the residual stream, every RMSNorm, RoPE, the
softmax and the logits in float32; every matmul accumulates in float32.

Every operation lies under a ``jax.named_scope`` of the published
module's name (``LoopLM/layers/self_attn/q_proj`` ...): that path is
what ``utils/trace.KERNEL_CLASSES`` reads out of a device trace.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

from comfyui_distributed_tpu.models.layers import \
    scaled_dot_product_attention
from comfyui_distributed_tpu.parallel import sharding as shd


@dataclasses.dataclass(frozen=True)
class LoopLMConfig:
    """The shape keys of the model's ``config.json``, under its names."""
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    total_ut_steps: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    dtype: Any = jnp.bfloat16      # weights, cache, matmul operands

    def __post_init__(self):
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "grouped key/value heads are not implemented: "
                f"{self.num_key_value_heads} KV heads for "
                f"{self.num_attention_heads} heads")

    @classmethod
    def from_hf(cls, config: Mapping[str, Any], **over) -> "LoopLMConfig":
        """From a ``config.json`` mapping; keys that say nothing about
        the shape are ignored."""
        names = {f.name for f in dataclasses.fields(cls)} - {"dtype"}
        return cls(**{k: config[k] for k in names if k in config}, **over)

    @property
    def cache_slots(self) -> int:
        return self.total_ut_steps * self.num_hidden_layers

    @property
    def layer_applications(self) -> int:
        """Layer applications one token passes through: every loop's."""
        return self.cache_slots


# ByteDance/Ouro-2.6B config.json, nothing reduced
OURO_2_6B = LoopLMConfig(
    vocab_size=49152, hidden_size=2048, num_hidden_layers=48,
    total_ut_steps=4, num_attention_heads=16, num_key_value_heads=16,
    head_dim=128, intermediate_size=5632, rms_norm_eps=1e-6,
    rope_theta=1e6)

# the CPU tests' size (fp32: deterministic comparisons)
TINY_LOOPLM = LoopLMConfig(
    vocab_size=512, hidden_size=64, num_hidden_layers=3, total_ut_steps=4,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=176, dtype=jnp.float32)

CONFIGS = {"full": OURO_2_6B, "tiny": TINY_LOOPLM}

NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
         "post_attention_layernorm_2")
# the sandwich norms, on a sub-layer's output: their seeded gains are small
SANDWICH_NORMS = ("input_layernorm_2", "post_attention_layernorm_2")
SANDWICH_GAIN = 0.1


def param_shapes(cfg: LoopLMConfig) -> Dict[str, Any]:
    """The parameter tree's shapes: the layers' leaves carry a leading
    ``L`` axis, everything else is as ``modeling_ouro.py`` names it
    (kernels stored ``[in, out]``).  Independent of ``total_ut_steps``:
    a loop adds no weight."""
    d, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    inner = cfg.num_attention_heads * cfg.head_dim
    layers = {n: (L, d) for n in NORMS}
    layers.update(q_proj=(L, d, inner), k_proj=(L, d, inner),
                  v_proj=(L, d, inner), o_proj=(L, inner, d),
                  gate_proj=(L, d, f), up_proj=(L, d, f),
                  down_proj=(L, f, d))
    return {"embed_tokens": (cfg.vocab_size, d), "layers": layers,
            "norm": (d,),
            "early_exit_gate": {"kernel": (d,), "bias": ()},
            "lm_head": (d, cfg.vocab_size)}


def param_count(cfg: LoopLMConfig) -> int:
    return sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def init_params(cfg: LoopLMConfig, seed) -> Dict[str, Any]:
    """Seeded random weights, made where this is traced: under one
    ``jax.jit`` the 2.67 B values of the published model are drawn on the
    device and never cross the host.  Kernels are normals scaled by
    fan-in, embeddings unit normals, norm gains 1 + 0.1 N (a gain of
    exactly 1 would hide a norm applied without its gain), the gate's
    bias 0.  The sandwich norms' gains are a tenth of that: a sub-layer's
    update is then small beside the residual stream, as a trained
    model's is.  With unit gains 192 random layer applications amplify
    bf16's rounding to 9% of a logit's standard deviation (on the chip,
    PERF.md section 6, PR 26), under which no comparison with the
    reference can tell a bf16 cache from an 8-bit one."""
    shapes = param_shapes(cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))

    def leaf(path, shape, key):
        name = path[-1].key
        if name == "bias":
            return jnp.zeros(shape, cfg.dtype)
        x = jax.random.normal(key, shape, jnp.float32)
        if name in NORMS or name == "norm":
            x = 1.0 + 0.1 * x
            if name in SANDWICH_NORMS:
                x = SANDWICH_GAIN * x
        elif name != "embed_tokens":
            # [.., in, out] kernels; the gate's [in] vector
            x = x / math.sqrt(shape[-2] if len(shape) > 1 else shape[-1])
        return x.astype(cfg.dtype)

    return jax.tree_util.tree_unflatten(
        tree, [leaf(p, s, k) for (p, s), k in zip(flat, keys)])


def seeded_params(cfg: LoopLMConfig, seed) -> Dict[str, Any]:
    """`init_params` under one ``jax.jit``: drawn on the device."""
    return jax.jit(functools.partial(init_params, cfg))(seed)


def load_checkpoint(path: str, cfg: LoopLMConfig) -> Dict[str, Any]:
    from comfyui_distributed_tpu.models.checkpoints import \
        load_looplm_checkpoint
    return load_looplm_checkpoint(path, cfg)


# --- the layer ------------------------------------------------------------

def _rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def _sandwich(x, update, gain, eps):
    """The residual add behind a sub-layer: its output is normed first."""
    return x + _rms_norm(update, gain, eps)


def _cache_slot(r: int, l):
    """Loop ``r`` of layer ``l`` has a slot of its own."""
    return r, l


def _dense(x, kernel, cfg):
    """``x @ kernel`` with operands in the model's dtype, accumulated and
    returned in float32."""
    return jnp.dot(x.astype(cfg.dtype), kernel,
                   preferred_element_type=jnp.float32)


def _rope(x, positions, theta):
    """Rotary embedding, the ``rotate_half`` convention, in float32:
    ``x [B, N, H, D]``, ``positions [B, N]`` (each row's own)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _qkv(cfg: LoopLMConfig, lp, x, positions):
    """This call's queries, keys and values, ``[B, N, H, D]`` in the
    model's dtype, keys and queries rotated to ``positions [B, N]``."""
    B, N, _ = x.shape
    heads = (B, N, cfg.num_attention_heads, cfg.head_dim)
    with jax.named_scope("input_layernorm"):
        n = _rms_norm(x, lp["input_layernorm"], cfg.rms_norm_eps)
    with jax.named_scope("self_attn"):
        with jax.named_scope("q_proj"):
            q = _dense(n, lp["q_proj"], cfg).reshape(heads)
        with jax.named_scope("k_proj"):
            k = _dense(n, lp["k_proj"], cfg).reshape(heads)
        with jax.named_scope("v_proj"):
            v = _dense(n, lp["v_proj"], cfg).reshape(heads)
        with jax.named_scope("rotary"):
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
    return tuple(shd.constrain(t.astype(cfg.dtype), "batch", None, "heads",
                               None) for t in (q, k, v))


def _attend_and_mlp(cfg: LoopLMConfig, lp, x, q, k, v, index, first):
    """The rest of the layer: ``q`` (at the buffer indices ``index``)
    against the keys and values ``k``, ``v`` from each row's ``first``
    real index up to the query's own, then the two sandwich-normed
    residual updates."""
    B, N, _ = x.shape
    with jax.named_scope("self_attn"):
        a = scaled_dot_product_attention(q, k, v, q_positions=index,
                                         kv_start=first)
        with jax.named_scope("o_proj"):
            a = _dense(a.reshape(B, N, -1), lp["o_proj"], cfg)
    with jax.named_scope("input_layernorm_2"):
        x = _sandwich(x, a, lp["input_layernorm_2"], cfg.rms_norm_eps)
    with jax.named_scope("post_attention_layernorm"):
        n = _rms_norm(x, lp["post_attention_layernorm"], cfg.rms_norm_eps)
    with jax.named_scope("mlp"):
        with jax.named_scope("gate_proj"):
            g = _dense(n, lp["gate_proj"], cfg)
        with jax.named_scope("up_proj"):
            u = _dense(n, lp["up_proj"], cfg)
        h = shd.constrain(jax.nn.silu(g) * u, "batch", None, "mlp")
        with jax.named_scope("down_proj"):
            m = _dense(h, lp["down_proj"], cfg)
    with jax.named_scope("post_attention_layernorm_2"):
        return _sandwich(x, m, lp["post_attention_layernorm_2"],
                         cfg.rms_norm_eps)


def _stack(cfg: LoopLMConfig, params, x, index, first, cache,
           use_cache: bool):
    """All ``R`` loops over the ``L`` layers.  ``cache`` is the pair of
    ``[R, L, B, T, H, D]`` key and value buffers; every layer application
    writes this call's entries into its own slot ``(r, l)`` at the buffer
    indices ``index [N]`` (consecutive, the same for every row: the
    update stays in place).  Row ``b``'s real entries start at index
    ``first[b]``, which is its position 0: what lies in front is padding
    and never attended to.  With ``use_cache`` the queries attend to the
    slot (a decode step: one query against everything up to its index),
    without to this call's own keys (the prefill: causal among the
    prompt).  Returns the last loop's normed state, the exit
    probabilities ``[B, N, R]`` and the cache."""
    B, N, _ = x.shape
    T = cache[0].shape[3]
    slot = (1, 1, B, T, cfg.num_attention_heads, cfg.head_dim)
    positions = index[None, :] - first[:, None]
    exits = []
    for r in range(cfg.total_ut_steps):
        def layer(carry, xs, r=r):
            x, kc, vc = carry
            lp, l = xs
            q, k, v = _qkv(cfg, lp, x, positions)
            with jax.named_scope("kv_cache"):
                at = _cache_slot(r, l)
                kc = jax.lax.dynamic_update_slice(
                    kc, k[None, None].astype(kc.dtype),
                    (*at, 0, index[0], 0, 0))
                vc = jax.lax.dynamic_update_slice(
                    vc, v[None, None].astype(vc.dtype),
                    (*at, 0, index[0], 0, 0))
                if use_cache:
                    k = jax.lax.dynamic_slice(
                        kc, (*at, 0, 0, 0, 0), slot)[0, 0].astype(cfg.dtype)
                    v = jax.lax.dynamic_slice(
                        vc, (*at, 0, 0, 0, 0), slot)[0, 0].astype(cfg.dtype)
            x = _attend_and_mlp(cfg, lp, x, q, k, v, index, first)
            return (x, kc, vc), None

        with jax.named_scope("layers"):
            (x, *cache), _ = jax.lax.scan(
                layer, (x, *cache),
                (params["layers"], jnp.arange(cfg.num_hidden_layers)))
        with jax.named_scope("final_norm"):
            x = _rms_norm(x, params["norm"], cfg.rms_norm_eps)
        with jax.named_scope("early_exit_gate"):
            gate = params["early_exit_gate"]
            exits.append(jax.nn.sigmoid(
                _dense(x, gate["kernel"], cfg)
                + gate["bias"].astype(jnp.float32)))
    return x, jnp.stack(exits, axis=-1), tuple(cache)


def _embed(params, ids):
    with jax.named_scope("embed_tokens"):
        return params["embed_tokens"][ids].astype(jnp.float32)


def _head(cfg: LoopLMConfig, params, x):
    with jax.named_scope("lm_head"):
        return _dense(x, params["lm_head"], cfg)


def empty_cache(cfg: LoopLMConfig, batch: int, length: int):
    shape = (cfg.total_ut_steps, cfg.num_hidden_layers, batch, length,
             cfg.num_attention_heads, cfg.head_dim)
    return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)


def kv_cache_bytes(cfg: LoopLMConfig, batch: int, length: int) -> int:
    """Bytes of the ``R x L``-slot cache for ``length`` positions."""
    return 2 * cfg.cache_slots * batch * length * cfg.num_attention_heads \
        * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize


# --- the served program ---------------------------------------------------

def generate(cfg: LoopLMConfig, max_new_tokens: int, params, prompt_ids,
             prompt_len, seed, temperature
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill, then ``max_new_tokens`` decode steps, for every row.

    ``prompt_ids [B, P]`` holds in row ``b`` ``prompt_len[b]`` real ids
    and padding behind them; ``prompt_len``, ``seed`` and ``temperature``
    are ``[B]``, or scalars where every row has the same.  Token ``i`` of
    a row is drawn from the logits at its position ``prompt_len + i - 1``:
    greedy where its ``temperature`` is 0, else sampled at that
    temperature from the key its ``seed`` gives.  A row's numbers do not
    depend on what the other rows hold.  Padding is never attended to
    (`_stack`).  Returns the new ids ``[B, N]``, the float32 logits each
    was drawn from ``[B, N, V]`` and the exit probabilities ``[B, N, R]``.
    """
    B, P = prompt_ids.shape
    prompt_len, seed, temperature = (
        jnp.broadcast_to(a, (B,)) for a in (prompt_len, seed, temperature))
    first = P - prompt_len
    keys = jax.vmap(jax.random.PRNGKey)(seed)

    def draw(key, logits, temperature, i):
        drawn = jax.random.categorical(
            jax.random.fold_in(key, i),
            logits / jnp.maximum(temperature, 1e-6))
        return jnp.where(temperature > 0, drawn,
                         jnp.argmax(logits)).astype(jnp.int32)

    with jax.named_scope("LoopLM"):
        # every row's last real id at P - 1
        prompt_ids = jax.vmap(jnp.roll)(prompt_ids, first)
        cache = empty_cache(cfg, B, P + max_new_tokens)
        x, exits, cache = _stack(cfg, params, _embed(params, prompt_ids),
                                 jnp.arange(P), first, cache,
                                 use_cache=False)
        logits = _head(cfg, params, x[:, P - 1:])[:, 0]

        def step(carry, i):
            logits, exits, cache = carry
            with jax.named_scope("sample"):
                token = jax.vmap(draw, (0, 0, 0, None))(
                    keys, logits, temperature, i)
            x, nxt_exits, cache = _stack(
                cfg, params, _embed(params, token[:, None]), P + i[None],
                first, cache, use_cache=True)
            nxt = _head(cfg, params, x)[:, 0]
            return (nxt, nxt_exits[:, 0], cache), (token, logits, exits)

        _, (tokens, logits, exits) = jax.lax.scan(
            step, (logits, exits[:, P - 1], cache),
            jnp.arange(max_new_tokens))
    return (tokens.swapaxes(0, 1), logits.swapaxes(0, 1),
            exits.swapaxes(0, 1))


def make_generate(cfg: LoopLMConfig, max_new_tokens: int):
    """The jitted program, named ``lm_generate`` (``jit_lm_generate`` in a
    device trace) whatever its configuration."""

    def lm_generate(params, prompt_ids, prompt_len, seed, temperature):
        return generate(cfg, max_new_tokens, params, prompt_ids, prompt_len,
                        seed, temperature)

    return jax.jit(lm_generate)


def make_program(cfg: LoopLMConfig, max_new_tokens: int):
    """`make_generate` as ``models/registry.py`` serves every family's
    program: ``(ids, logits, aux, stats)``, ``aux`` the per-position
    arrays a comparison wants beside the logits (here the exit
    probabilities), ``stats`` what the host counts from (here nothing)."""

    def lm_generate(params, prompt_ids, prompt_len, seed, temperature):
        tokens, logits, exits = generate(cfg, max_new_tokens, params,
                                         prompt_ids, prompt_len, seed,
                                         temperature)
        return tokens, logits, {"exit_probs": exits}, {}

    return jax.jit(lm_generate)


def window_counters(cfg: LoopLMConfig, stats, real: int, steps: int
                    ) -> Dict[str, int]:
    """This family counts nothing of its own."""
    return {}
