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
from typing import Any, Dict, Mapping, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from comfyui_distributed_tpu.models import lm_decode
from comfyui_distributed_tpu.models.layers import _live_mesh, \
    scaled_dot_product_attention
from comfyui_distributed_tpu.ops.pallas.fewrow_dense import LANES, \
    block_sizes, fewrow_dense, fewrow_dense_t
from comfyui_distributed_tpu.parallel import sharding as shd
from comfyui_distributed_tpu.utils.trace import DENSE_PATHS


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


# --- a product of the rows with a weight -----------------------------------
#
# One algorithm (stream the weight once, accumulate in float32) wants a
# different lowering at different row counts.  With ONE row XLA writes a
# multiply-and-reduce over the stacked leaf with the layer scan's
# ``dynamic-slice`` fused in, at 680-705 GB/s of a v5e's 819; with a
# prefill's 64-256 rows an ordinary matmul.  With 2-8 rows (an execution
# shared by the requests waiting, PERF.md section 6, PR 28) what it writes
# depends on the shape.  Most products it streams as well as one row's
# (720-755 GB/s); but Ouro's q / k / v / o layer slices and openPangu's
# ``q_b_proj`` slice it first COPIES out of the stacked leaf into another
# layout, every layer of every step, and then reads again: twice to three
# times the leaf's bytes (section 6, PR 33).  There the product goes to
# the weight-streaming kernel (ops/pallas/fewrow_dense.py), which reads
# its layer out of the leaf in place and matches XLA's rate on every
# shape XLA streams well, so ONE path takes every few-row product.

FEWROW_ROWS = (2, 8)            # what the kernel's resident ``x`` holds
# under this a weight is not worth a launch (a router's or a gate's
# vector; the tiny models' everything)
FEWROW_MIN_WEIGHT_BYTES = 1 << 20


def few_rows(platform: str, rows: int,
             mesh_axes: Optional[dict] = None) -> bool:
    """Whether a call of ``rows`` rows (batch x positions) is one whose
    products `dense_path` may send to the few-row kernel: on a TPU, 2 to
    8 rows, no multi-device mesh live (XLA cannot partition the custom
    call; under a mesh every product stays with ``jnp.dot``, which it
    can).  A layer scan reads this once: where it holds, the scan walks
    the layer INDEX with the stacked leaves closed over, so that a
    product can be handed its whole leaf (`Stacked`)."""
    return platform == "tpu" \
        and FEWROW_ROWS[0] <= rows <= FEWROW_ROWS[1] \
        and math.prod((mesh_axes or {}).values()) == 1


def dense_path(platform: str, rows: int, k: int, n: int, itemsize: int = 2,
               mesh_axes: Optional[dict] = None) -> str:
    """Which lowering a product ``[rows, k] x [k, n]`` with a resident
    leaf takes: ``fewrow`` (the weight-streaming Pallas kernel) or
    ``xla`` (``jnp.dot``).  A function of what the code can see at trace
    time and nothing else: the backend's platform, the static shapes, the
    live mesh.  The one-row program, both prefills and every other
    backend keep ``jnp.dot``; so does a weight the kernel's blocks do not
    divide (``k``, ``n`` multiples of 128), one that is too small to be
    worth a launch, and one whose columns the blocks could only walk a
    lane group at a time (a 151,936-row head: 1187 x 128 with 1187 prime,
    so a tile would be 256-byte runs of the leaf, thousands of them)."""
    if few_rows(platform, rows, mesh_axes) and k % LANES == 0 \
            and n % LANES == 0 \
            and k * n * itemsize >= FEWROW_MIN_WEIGHT_BYTES \
            and block_sizes(k, n, 1, itemsize)[1] >= min(n, 2 * LANES):
        return "fewrow"
    return "xla"


class Stacked(NamedTuple):
    """A weight as it lies in the device's memory: the whole leaf
    ``[L, in, out]`` and the layer asked for, or ``[in, out]`` and None.
    What `_dense` needs to hand the kernel a leaf to read in place; a
    plain array handed to `_dense` may be a slice made inside the
    program (a routed expert's), which a custom call would materialise,
    and always meets ``jnp.dot``."""
    leaf: jax.Array
    layer: Optional[jax.Array] = None


def matrix(kernel) -> jax.Array:
    """``[in, out]`` of a weight handed over either way."""
    if not isinstance(kernel, Stacked):
        return kernel
    if kernel.layer is None:
        return kernel.leaf
    return jax.lax.dynamic_index_in_dim(kernel.leaf, kernel.layer,
                                        keepdims=False)


def layer_of(leaves, l):
    """Layer ``l`` of a tree of stacked leaves, as a few-row scan body
    sees it: matrices as `Stacked`, vectors (norm gains) sliced."""
    return jax.tree_util.tree_map(
        lambda w: Stacked(w, l) if w.ndim >= 3
        else jax.lax.dynamic_index_in_dim(w, l, keepdims=False), leaves)


def scan_layers(body, carry, leaves, count: int, stream: bool,
                first: int = 0, start: int = 0):
    """``lax.scan`` of ``body(carry, (layer start + i's weights,
    first + i))`` over ``count`` layers of the stacked ``leaves``: as
    ``xs`` (each step handed its slices; the whole stack only), or with
    ``stream`` over the index alone (`layer_of`).  A run that is a part
    of the leaves (the layers of one KIND in a stack that mixes two)
    walks the index: a slice of the leaves as ``xs`` would be a copy of
    those weights."""
    if stream:
        return jax.lax.scan(
            lambda c, i: body(c, (layer_of(leaves, start + i), first + i)),
            carry, jnp.arange(count))
    assert start == 0, "a run inside a stack walks the index"
    return jax.lax.scan(body, carry, (leaves, first + jnp.arange(count)))


def _where() -> Tuple[str, Optional[dict]]:
    """What the rule reads beside shapes: the backend's platform and the
    live mesh's ``{axis: size}`` (None on one device)."""
    mesh = _live_mesh()
    return jax.default_backend(), \
        dict(mesh.shape) if mesh is not None else None


def few_rows_here(rows: int) -> bool:
    """`few_rows` of a call of ``rows`` rows where this is traced."""
    platform, mesh_axes = _where()
    return few_rows(platform, rows, mesh_axes)


def _streams(kernels: Sequence[Any], rows: int, cfg) -> bool:
    """Whether these weights, all meeting the same rows, go to the
    kernel: each a resident leaf, all of one shape that `dense_path`
    sends there."""
    if not all(isinstance(w, Stacked) for w in kernels):
        return False
    platform, mesh_axes = _where()
    shape = kernels[0].leaf.shape
    return all(w.leaf.shape == shape for w in kernels) and dense_path(
        platform, rows, *shape[-2:], jnp.dtype(cfg.dtype).itemsize,
        mesh_axes) == "fewrow"


def _count_sites(path: str, rows: int, sites: int = 1) -> None:
    """``dense_paths`` on ``GET /distributed/metrics``: the products with
    a weight by the lowering each took and the rows that met it
    (``one``, ``few``: 2 to 8, ``many``), counted per call site while a
    program is TRACED, as ``attention_paths`` are."""
    met = "one" if rows == 1 else "few" if rows <= FEWROW_ROWS[1] else "many"
    DENSE_PATHS.bump(f"{path}_{met}", sites)


def _products(x, kernels: Sequence[Any], cfg, name: str = "fewrow_dense"):
    """``x @ kernel`` for each kernel, operands in the model's dtype,
    accumulated and returned in float32: one call of the few-row kernel
    (named ``name``) where `_streams`, else a ``jnp.dot`` each."""
    rows = math.prod(x.shape[:-1])
    streams = _streams(kernels, rows, cfg)
    _count_sites("fewrow" if streams else "xla", rows, len(kernels))
    if not streams:
        return [jnp.dot(x.astype(cfg.dtype), matrix(w),
                        preferred_element_type=jnp.float32)
                for w in kernels]
    out = fewrow_dense(x.reshape(rows, -1).astype(cfg.dtype),
                       [w.leaf for w in kernels], kernels[0].layer,
                       name=name)
    return [y.reshape(*x.shape[:-1], -1) for y in out]


def _dense(x, kernel, cfg):
    """``x @ kernel`` with operands in the model's dtype, accumulated and
    returned in float32."""
    return _products(x, [kernel], cfg)[0]


def dense_tied(x, leaf, cfg):
    """``x @ leaf.T`` for a TIED embedding ``leaf [V, d]`` read as the
    head, operands in the model's dtype, accumulated and returned in
    float32.  The leaf stays as the lookup wants it (a row an id,
    contiguous); where `dense_path` sends a ``[d, V]`` weight to the
    few-row kernel its transposed form streams the rows as they lie,
    elsewhere a ``dot_general`` contracts the second axis of both: no
    path makes a transposed copy of the leaf."""
    rows = math.prod(x.shape[:-1])
    platform, mesh_axes = _where()
    path = dense_path(platform, rows, leaf.shape[1], leaf.shape[0],
                      jnp.dtype(cfg.dtype).itemsize, mesh_axes)
    _count_sites(f"{path}_tied", rows)
    if path == "fewrow":
        out = fewrow_dense_t(x.reshape(rows, -1).astype(cfg.dtype), leaf)
        return out.reshape(*x.shape[:-1], -1)
    return jax.lax.dot_general(
        x.astype(cfg.dtype), leaf, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def dense_each(x, weights, names: Sequence[str], cfg,
               scope=jax.named_scope):
    """`_dense` of ``x`` with ``weights[name]`` for each name, each under
    its name's scope.  Few rows against resident leaves of one shape
    (q / k / v; gate / up) are ONE call of the kernel, which streams them
    all, under the first name's scope; the kernel's own name says
    which."""
    kernels = [weights[name] for name in names]
    if _streams(kernels, math.prod(x.shape[:-1]), cfg):
        with scope(names[0]):
            return _products(x, kernels, cfg,
                             "fewrow_dense_" + "_".join(names))
    out = []
    for name, kernel in zip(names, kernels):
        with scope(name):
            out.append(_dense(x, kernel, cfg))
    return out


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
        q, k, v = (t.reshape(heads) for t in dense_each(
            n, lp, ("q_proj", "k_proj", "v_proj"), cfg))
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
        g, u = dense_each(n, lp, ("gate_proj", "up_proj"), cfg)
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
    prompt).  A call of few rows on a TPU (`few_rows`: a shared
    execution's decode step) walks the layer index with the leaves closed
    over, so that its products can stream their leaves in place.
    Returns the last loop's normed state, the exit probabilities
    ``[B, N, R]`` and the cache."""
    B, N, _ = x.shape
    T = cache[0].shape[3]
    slot = (1, 1, B, T, cfg.num_attention_heads, cfg.head_dim)
    positions = index[None, :] - first[:, None]
    stream = few_rows_here(B * N)
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
            (x, *cache), _ = scan_layers(
                layer, (x, *cache), params["layers"],
                cfg.num_hidden_layers, stream)
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
        return _dense(x, Stacked(params["lm_head"]), cfg)


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
    """Prefill, then ``max_new_tokens`` decode steps, for every row
    (`lm_decode.generate` around this family's blocks).

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
    first = P - jnp.broadcast_to(prompt_len, (B,))

    def prefill():
        with jax.named_scope("prefill"):
            # every row's last real id at P - 1
            ids = jax.vmap(jnp.roll)(prompt_ids, first)
            cache = empty_cache(cfg, B, P + max_new_tokens)
            x, exits, cache = _stack(
                cfg, params, _embed(params, ids), jnp.arange(P), first,
                cache, use_cache=False)
            logits = _head(cfg, params, x[:, P - 1:])[:, 0]
            return logits, (exits[:, P - 1],), cache, (), ()

    def step(token, i, cache):
        x, exits, cache = _stack(
            cfg, params, _embed(params, token[:, None]), P + i[None], first,
            cache, use_cache=True)
        return _head(cfg, params, x)[:, 0], (exits[:, 0],), cache, ()

    tokens, logits, (exits,), _, _ = lm_decode.generate(
        "LoopLM", B, prefill, step, max_new_tokens, seed, temperature)
    return tokens, logits, exits


def make_generate(cfg: LoopLMConfig, max_new_tokens: int):
    """`generate` jitted, named ``lm_generate`` (``jit_lm_generate`` in a
    device trace) whatever its configuration."""
    return lm_decode.make_program(
        functools.partial(generate, cfg, max_new_tokens))


def make_program(cfg: LoopLMConfig, max_new_tokens: int):
    """`make_generate` as ``models/registry.py`` serves every family's
    program: ``(ids, logits, aux, stats)``, ``aux`` the per-position
    arrays a comparison wants beside the logits (here the exit
    probabilities), ``stats`` what the host counts from (here nothing)."""

    def served(*args):
        tokens, logits, exits = generate(cfg, max_new_tokens, *args)
        return tokens, logits, {"exit_probs": exits}, {}

    return lm_decode.make_program(served)


def window_counters(cfg: LoopLMConfig, stats, real: int, steps: int
                    ) -> Dict[str, int]:
    """This family counts nothing of its own."""
    return {}
