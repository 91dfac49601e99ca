"""A decoder whose layers are of TWO attention kinds in one stack, with
grouped key-value heads and routed experts (K-EXAONE-236B-A23B,
``model_type`` ``exaone_moe``; the family's convention is EXAONE 4.0's
modeling file), served as ONE chip's share of a deployment.

    x = E[ids]
    for l in 0..L-1:                                     # every block
      h = x + N_a(Attn_l(x))                             # norms BEHIND the
      x = h + N_f(MLP_l(h))                              # sub-layers, none before
    logits = W_head N(x)

    Attn, per block:
      q = x W_q -> H heads of D;  k, v = x W_k, x W_v -> G heads of D
      q, k = RMSNorm over each head's D values (q_norm, k_norm)
      layer_types[l] == "sliding_attention":
          q, k rotated (rotate_half, theta 1e6);
          query at position i sees keys j with 0 <= i - j < window
      layer_types[l] == "full_attention":
          NO rotation; query at i sees every key j <= i
      query head h reads key-value head h // (H / G)
      out = W_o concat_h(softmax(q . k / sqrt(D)) v)       # softmax in float32

    MLP of the leading dense blocks:  W_down (silu(W_gate h) * W_up h)
    MLP of the expert blocks:
      s = sigmoid(h W_g) over ALL num_experts, float32;  top-k;
      w = s_topk / sum(s_topk) * routed_scaling_factor
      y = Shared(h) + sum over the chosen experts e HELD HERE of w_e Expert_e(h)

**Two cache geometries in one program.**  A sliding layer needs its last
``window`` keys and no more: its cache is a RING of ``window`` slots
(``[L_sliding, B, window, G, D]``), the key of buffer index ``j`` in slot
``j mod window``, overwritten as the decode goes on.  A full layer keeps
every position (``[L_full, B, P + N, G, D]``).  Rows stay right-aligned to
one shared write index (`looplm.generate`'s contract), so the slot a step
writes is one scalar.  The prefill attends to its own keys under a
banded causal mask and leaves the ring its last ``window`` positions; a
decode step writes its slot, then attends to the ring under a mask over
SLOTS: slot ``s`` holds index ``j - ((j - s) mod window)`` of the step at
``j``, seen where that is at least the row's first real index (a slot
never written holds a negative one).  The mask is `layers.visible_keys`,
which counts the keys as well (``stats``).

**The layer stack.**  Leaves are stacked as ``models/mla_moe.py``'s
(``dense_layers``, ``moe_layers``); the blocks of one kind that follow
each other in a stack are a RUN (`ExaoneMoeConfig.runs`), one
``lax.scan`` body each over the index of its layers, with the cache of
its kind in the carry and the other not touched.

**The share** and the expert layer are ``models/mla_moe.py``'s, imported:
``route``, ``_routed``, ``_moe``, ``_gated_mlp`` read what they need
(``experts_first``, ``experts_held``, ``num_experts_per_tok``,
``norm_topk_prob``, ``routed_scaling_factor``) from either config.  8
chips share each layer here, 16 of the 128 experts to a chip.

Served as one jitted program, ``lm_generate``, under two outer scopes,
``prefill`` and ``decode`` (a trace summary's seconds by PHASE).
Precision as the other families': weights, caches and matmul operands in
``cfg.dtype``; the residual stream, every RMSNorm, RoPE, the softmax and
the logits in float32; the router in float32 at the highest precision.

Scopes carry the published modules' names (``ExaoneMoe/decode/
moe_layers/self_attn/q_proj`` ...), read by ``utils/trace.KERNEL_CLASSES``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from comfyui_distributed_tpu.models import lm_decode
from comfyui_distributed_tpu.models.layers import ATTENTION_PATHS, \
    attention_path, visible_keys, xla_attention
from comfyui_distributed_tpu.models.looplm import _dense, _embed, _head, \
    _rms_norm, _rope, _sandwich, dense_each, few_rows_here, \
    scan_layers  # noqa: F401
from comfyui_distributed_tpu.models.mla_moe import _gated_mlp, _moe, \
    count_values, routing_counters, seeded_tree
from comfyui_distributed_tpu.parallel import sharding as shd

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Run:
    """Blocks of one kind that follow each other in one stack."""
    stack: str          # "dense_layers" | "moe_layers"
    kind: str           # SLIDING | FULL
    start: int          # the first block's index in its stack
    count: int
    cache_start: int    # and in the cache of its kind


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    """The shape keys of the model's ``config.json``, under its names, AS
    HELD: ``num_hidden_layers``, ``first_k_dense_replace`` and
    ``layer_types`` count the blocks of this share, ``vocab_size`` its
    slice.  ``num_experts`` is the router's width (the published count);
    ``experts_first`` / ``experts_held`` name this chip's experts."""
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    layer_types: Tuple[str, ...]
    sliding_window: int
    num_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    experts_first: int = 0
    experts_held: int = -1          # -1: all of them
    dtype: Any = jnp.bfloat16       # weights, caches, matmul operands

    def __post_init__(self):
        if self.experts_held < 0:
            object.__setattr__(self, "experts_held", self.num_experts)
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if not 0 <= self.experts_first <= self.experts_first \
                + self.experts_held <= self.num_experts:
            raise ValueError(
                f"experts {self.experts_first}..{self.experts_first}+"
                f"{self.experts_held} are not among the router's "
                f"{self.num_experts}")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(
                f"{self.first_k_dense_replace} dense blocks of "
                f"{self.num_hidden_layers}")
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types {self.layer_types} do not name "
                f"{self.num_hidden_layers} blocks as {SLIDING} or {FULL}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not divide "
                f"over {self.num_key_value_heads} key-value heads")

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def layer_applications(self) -> int:
        """Blocks one token passes through."""
        return self.num_hidden_layers

    def layers_of(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def runs(self) -> List[Run]:
        runs: List[Run] = []
        seen = {SLIDING: 0, FULL: 0}
        for l, kind in enumerate(self.layer_types):
            dense = l < self.first_k_dense_replace
            stack = "dense_layers" if dense else "moe_layers"
            at = l if dense else l - self.first_k_dense_replace
            last = runs[-1] if runs else None
            if last and (last.stack, last.kind) == (stack, kind):
                runs[-1] = dataclasses.replace(last, count=last.count + 1)
            else:
                runs.append(Run(stack, kind, at, 1, seen[kind]))
            seen[kind] += 1
        return runs


# LGAI-EXAONE/K-EXAONE-236B-A23B config.json, every width as published,
# cut to ONE chip's share of an 8-chip expert-parallel deployment
# (benchmarks/chip/configs/k-exaone-236b-expand-sd15-512.json has the
# arithmetic): published layers 0..4 (the leading dense block and the four
# expert blocks behind it: sliding, sliding, sliding, full, sliding; the
# four that follow the dense one hold the published 3 : 1; further blocks
# lie on further chips, as pipeline stages), experts 32..47 of the 128
# (chip 2 of the 8), an eighth of the 153,600-row vocabulary.  The
# multi-token-prediction layer is not held.
K_EXAONE_SHARE = ExaoneMoeConfig(
    vocab_size=19200, hidden_size=6144, num_hidden_layers=5,
    first_k_dense_replace=1, num_attention_heads=64, num_key_value_heads=8,
    head_dim=128, intermediate_size=18432, moe_intermediate_size=2048,
    num_experts=128, num_experts_per_tok=8,
    layer_types=(SLIDING, SLIDING, SLIDING, FULL, SLIDING),
    sliding_window=128, num_shared_experts=1, routed_scaling_factor=2.5,
    norm_topk_prob=True, rms_norm_eps=1e-5, rope_theta=1e6,
    experts_first=32, experts_held=16)

# the CPU tests' and the rehearsal's size (fp32: deterministic
# comparisons): the published order of layer types at a window of 8, 4
# query heads over 2 key-value heads, experts 4..7 of 16
TINY_SWA_MOE = ExaoneMoeConfig(
    vocab_size=512, hidden_size=64, num_hidden_layers=5,
    first_k_dense_replace=1, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, intermediate_size=160, moe_intermediate_size=48,
    num_experts=16, num_experts_per_tok=4,
    layer_types=(SLIDING, SLIDING, SLIDING, FULL, SLIDING),
    sliding_window=8, routed_scaling_factor=2.5, experts_first=4,
    experts_held=4, dtype=jnp.float32)

CONFIGS = {"full": K_EXAONE_SHARE, "tiny": TINY_SWA_MOE}

# the two norms of a block, each on a sub-layer's OUTPUT; their seeded
# gains are half the others' (five blocks, as openPangu's share: at 0.5 an
# 8-bit cache stands clear of the served path's rounding, PERF.md
# section 6, PR 34)
POST_NORMS = ("post_attention_layernorm", "post_feedforward_layernorm")
HEAD_NORMS = ("q_norm", "k_norm")
POST_NORM_GAIN = 0.5


def param_shapes(cfg: ExaoneMoeConfig) -> Dict[str, Any]:
    """The parameter tree's shapes, kernels ``[in, out]``: two stacks of
    blocks as ``models/mla_moe.py``'s, the routed experts
    ``[L, E_here, in, out]``."""
    d, D = cfg.hidden_size, cfg.head_dim
    H, G = cfg.num_attention_heads, cfg.num_key_value_heads

    def mlp(width, *lead):
        return {"gate_proj": (*lead, d, width), "up_proj": (*lead, d, width),
                "down_proj": (*lead, width, d)}

    def block(L):
        layers = {n: (L, d) for n in POST_NORMS}
        layers.update({n: (L, D) for n in HEAD_NORMS})
        layers.update(q_proj=(L, d, H * D), k_proj=(L, d, G * D),
                      v_proj=(L, d, G * D), o_proj=(L, H * D, d))
        return layers

    Ld, Le = cfg.first_k_dense_replace, cfg.moe_layers
    dense = {**block(Ld), **mlp(cfg.intermediate_size, Ld)}
    moe = {**block(Le), "gate": (Le, d, cfg.num_experts),
           "shared_experts": mlp(
               cfg.moe_intermediate_size * cfg.num_shared_experts, Le),
           "experts": mlp(cfg.moe_intermediate_size, Le, cfg.experts_held)}
    return {"embed_tokens": (cfg.vocab_size, d), "dense_layers": dense,
            "moe_layers": moe, "norm": (d,), "lm_head": (d, cfg.vocab_size)}


def param_count(cfg: ExaoneMoeConfig) -> int:
    return count_values(param_shapes(cfg))


def _norm_gain(name: str):
    if name in POST_NORMS:
        return POST_NORM_GAIN
    return 1.0 if name in HEAD_NORMS or name == "norm" else None


def seeded_params(cfg: ExaoneMoeConfig, seed) -> Dict[str, Any]:
    """`mla_moe.seeded_tree`: on the device, leaf by leaf."""
    return seeded_tree(param_shapes(cfg), seed, cfg.dtype, _norm_gain)


def load_checkpoint(path: str, cfg: ExaoneMoeConfig):
    raise NotImplementedError(
        f"{path}: no reader for an exaone_moe state dict yet (this family "
        f"is served from seeded weights: a share of 236 B parameters is "
        f"not a file anybody has); remove the file or serve another model")


# --- the layer ------------------------------------------------------------

def _qkv(cfg: ExaoneMoeConfig, kind: str, lp, x, positions):
    """This call's queries ``[B, N, H, D]`` and keys and values
    ``[B, N, G, D]`` in the model's dtype: each head normed over its
    ``D`` values, then (a sliding layer only) rotated to ``positions``."""
    B, N, _ = x.shape
    with jax.named_scope("q_proj"):
        q = _dense(x, lp["q_proj"], cfg).reshape(B, N, -1, cfg.head_dim)
    k, v = (t.reshape(B, N, -1, cfg.head_dim)
            for t in dense_each(x, lp, ("k_proj", "v_proj"), cfg))
    with jax.named_scope("q_norm"):
        q = _rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
    with jax.named_scope("k_norm"):
        k = _rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    if kind == SLIDING:
        with jax.named_scope("rotary"):
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
    return tuple(shd.constrain(t.astype(cfg.dtype), "batch", None, "heads",
                               None) for t in (q, k, v))


def _attend(q, k, v, q_positions, scale=None, selected=None, **mask):
    """``q [B, N, H, D]`` at ``q_positions [N]`` against ``k``, ``v
    [B, M, G, D]`` under the rest of `visible_keys`' ``mask``, the scores
    times ``scale`` (default ``1 / sqrt(D)``): the
    ``H / G`` query heads of a group meet the group's one key-value head
    as ``H / G`` queries a position of a ``G``-headed call, head ``h`` in
    group ``h // (H / G)``; a position's ``selected [B, N or 1, M]`` keys
    (and, with no ``q_positions``, that selection alone) are all its
    heads'.  -> ``[B, N, H * D]``."""
    B, N, H, D = q.shape
    G = k.shape[2]
    grouped = q.reshape(B, N, G, H // G, D).swapaxes(2, 3)
    if selected is not None and selected.shape[1] > 1:
        selected = jnp.repeat(selected, H // G, axis=1)
    out = xla_attention(
        grouped.reshape(B, N * (H // G), G, D), k, v,
        1.0 / math.sqrt(D) if scale is None else scale,
        None if q_positions is None else jnp.repeat(q_positions, H // G),
        selected=selected, **mask)
    return out.reshape(B, N, H // G, G, D).swapaxes(2, 3).reshape(B, N, -1)


def ring_positions(index, window: int):
    """The buffer index each slot of a ring holds once ``index`` has been
    written: the latest ``j <= index`` with ``j mod window`` the slot
    (negative where the slot was never written)."""
    return index - (index - jnp.arange(window)) % window


def _ring_of(k, window: int):
    """A prefill's keys ``[B, P, G, D]`` as the ring holds them: the last
    ``window`` positions, index ``j`` in slot ``j mod window`` (a prompt
    buffer shorter than the window fills the first ``P`` slots)."""
    P = k.shape[1]
    if P <= window:
        return k
    return jnp.roll(k[:, P - window:], (P - window) % window, axis=1)


def _attention(cfg: ExaoneMoeConfig, kind: str, lp, x, index, first, kc, vc,
               l, decode: bool):
    """``h = x + N_a(Attn(x))``, the caches of this layer's kind with
    this call's keys and values written into layer ``l`` of them, and the
    keys each row's LAST query saw ``[B]``.  Without ``decode`` the
    queries attend to this call's own keys (the prefill, ``index`` =
    ``arange(P)``), with it to the cache (one query at ``index[0]``)."""
    B, N, _ = x.shape
    window = cfg.sliding_window if kind == SLIDING else None
    mask = {"kv_start": first, "window": window}
    with jax.named_scope("self_attn"):
        q, k, v = _qkv(cfg, kind, lp, x, index[None, :] - first[:, None])
        with jax.named_scope("kv_cache"):
            if not decode and kind == SLIDING:
                at, new = 0, [_ring_of(t, window) for t in (k, v)]
            else:
                at = index[0] % window if kind == SLIDING else index[0]
                new = [k, v]
            kc, vc = (jax.lax.dynamic_update_slice(
                c, t[None].astype(c.dtype), (l, 0, at, 0, 0))
                for c, t in zip((kc, vc), new))
            if decode:
                k, v = (jax.lax.dynamic_index_in_dim(
                    c, l, keepdims=False).astype(cfg.dtype)
                    for c in (kc, vc))
                if kind == SLIDING:
                    mask["kv_positions"] = ring_positions(index[0], window)
        ATTENTION_PATHS.bump(attention_path(
            jax.default_backend(), B, N, k.shape[1],
            cfg.num_attention_heads, masked=True, banded=kind == SLIDING))
        seen = visible_keys(k.shape[1], index[-1:], **mask)[:, 0].sum(
            axis=-1, dtype=jnp.int32)
        a = _attend(q, k, v, index, **mask)
        with jax.named_scope("o_proj"):
            a = _dense(a, lp["o_proj"], cfg)
    with jax.named_scope("post_attention_layernorm"):
        return _sandwich(x, a, lp["post_attention_layernorm"],
                         cfg.rms_norm_eps), kc, vc, seen


def _stack(cfg: ExaoneMoeConfig, params, x, index, first, caches,
           decode: bool):
    """Every block held, run by run (`ExaoneMoeConfig.runs`).  ``caches``
    maps each kind of layer to its ``(keys, values)``; each block writes
    this call's entries at the buffer indices ``index [N]`` (consecutive,
    the same for every row; a ring at ``index mod window``); row ``b``'s
    real entries start at ``first[b]``, its position 0.  Every run walks
    the index of its layers with the stacked leaves closed over
    (`looplm.scan_layers`), so a few-row call's products stream their
    leaves in place.  Returns the normed last state, the caches, the
    routers' ``(scores [B, N, Le, E], choices [B, N, Le, k])``, the
    routing counts summed over the expert blocks (local pairs ``[B]``,
    hits, dropped, rows computed) and the keys each row's last query saw,
    summed over the layers of each kind (``{kind: [B]}``)."""
    B = x.shape[0]
    eps = cfg.rms_norm_eps
    moe = dict(params["moe_layers"])
    experts = moe.pop("experts")
    stacks = {"dense_layers": params["dense_layers"], "moe_layers": moe}
    caches = dict(caches)
    seen = {kind: jnp.zeros((B,), jnp.int32) for kind in caches}
    routed, counts = [], []

    for run in cfg.runs:
        def block(carry, xs, run=run):
            x, kc, vc = carry
            lp, l = xs
            h, kc, vc, keys = _attention(
                cfg, run.kind, lp, x, index, first, kc, vc,
                l - run.start + run.cache_start, decode)
            with jax.named_scope("mlp"):
                if run.stack == "dense_layers":
                    m, routing = _gated_mlp(cfg, lp, h), None
                else:
                    m, routing = _moe(cfg, lp, experts, l, h)
            with jax.named_scope("post_feedforward_layernorm"):
                return (_sandwich(h, m, lp["post_feedforward_layernorm"],
                                  eps), kc, vc), (routing, keys)

        with jax.named_scope(run.stack):
            (x, kc, vc), (routing, keys) = scan_layers(
                block, (x, *caches[run.kind]), stacks[run.stack], run.count,
                True, first=run.start, start=run.start)
        caches[run.kind] = (kc, vc)
        seen[run.kind] = seen[run.kind] + keys.sum(axis=0)
        if routing is not None:
            routed.append(routing[0])
            counts.append(routing[1])
    with jax.named_scope("final_norm"):
        x = _rms_norm(x, params["norm"], eps)
    scores, choices = (jnp.moveaxis(jnp.concatenate(r), 0, 2)
                       for r in zip(*routed))
    return x, caches, (scores, choices), tuple(
        jnp.concatenate(c).sum(axis=0) for c in zip(*counts)), seen


def empty_cache(cfg: ExaoneMoeConfig, batch: int, length: int):
    """``{kind: (keys, values)}``: a ring of ``sliding_window`` slots for
    the sliding layers, ``length`` positions for the full ones."""
    def pair(kind, positions):
        shape = (cfg.layers_of(kind), batch, positions,
                 cfg.num_key_value_heads, cfg.head_dim)
        return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)
    return {SLIDING: pair(SLIDING, cfg.sliding_window),
            FULL: pair(FULL, length)}


def kv_cache_bytes_by_kind(cfg: ExaoneMoeConfig, batch: int, length: int
                           ) -> Dict[str, int]:
    position = 2 * batch * cfg.num_key_value_heads * cfg.head_dim \
        * jnp.dtype(cfg.dtype).itemsize
    return {"ring": cfg.layers_of(SLIDING) * cfg.sliding_window * position,
            "full": cfg.layers_of(FULL) * length * position}


def kv_cache_bytes(cfg: ExaoneMoeConfig, batch: int, length: int) -> int:
    return sum(kv_cache_bytes_by_kind(cfg, batch, length).values())


# --- the served program ---------------------------------------------------

def generate(cfg: ExaoneMoeConfig, max_new_tokens: int, params, prompt_ids,
             prompt_len, seed, temperature
             ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array],
                        Dict[str, jax.Array]]:
    """Prefill, then ``max_new_tokens`` decode steps, for every row:
    `looplm.generate`'s contract (rows, lengths, seeds, temperatures,
    padding never attended to).  Returns the new ids ``[B, N]``, the
    float32 logits each was drawn from ``[B, N, V]``, ``aux`` as
    `mla_moe.generate`'s (``router_scores``, ``expert_choices``,
    ``prompt_choices``) and ``stats``, int32: over the DECODE steps
    ``expert_pairs_local [B]``, ``expert_hits``, ``keys_attended_window
    [B]`` and ``keys_attended_full [B]`` (what the steps' masks let a
    row's query see, summed over the layers of the kind);
    ``expert_pairs_local_prefill [B]`` (over the whole prompt buffer) and
    ``expert_rows_computed_prefill`` (the rows the routed experts
    multiplied for them); ``expert_pairs_dropped`` over both (0)."""
    B, P = prompt_ids.shape
    first = P - jnp.broadcast_to(prompt_len, (B,))

    def prefill():
        with jax.named_scope("prefill"):
            # every row's last real id at P - 1
            ids = jax.vmap(jnp.roll)(prompt_ids, first)
            x, caches, routed, counts, _ = _stack(
                cfg, params, _embed(params, ids), jnp.arange(P), first,
                empty_cache(cfg, B, P + max_new_tokens), decode=False)
            logits = _head(cfg, params, x[:, P - 1:])[:, 0]
            rows = jnp.zeros((B,), jnp.int32)
            return (logits, tuple(r[:, P - 1] for r in routed), caches,
                    (rows, jnp.int32(0), counts[2], rows, rows),
                    (routed[1], counts))

    def step(token, i, caches):
        x, caches, routed, (pairs, hits, dropped, _), seen = _stack(
            cfg, params, _embed(params, token[:, None]), P + i[None], first,
            caches, decode=True)
        return (_head(cfg, params, x)[:, 0], tuple(r[:, 0] for r in routed),
                caches, (pairs, hits, dropped, seen[SLIDING], seen[FULL]))

    tokens, logits, (scores, choices), \
        (pairs, hits, dropped, window_keys, full_keys), \
        (prompt_choices, (prefill_pairs, _, _, prefill_rows)) = \
        lm_decode.generate("ExaoneMoe", B, prefill, step, max_new_tokens,
                           seed, temperature)
    return (tokens, logits,
            {"router_scores": scores, "expert_choices": choices,
             "prompt_choices": prompt_choices},
            {"expert_pairs_local": pairs, "expert_hits": hits,
             "expert_pairs_dropped": dropped,
             "expert_pairs_local_prefill": prefill_pairs,
             "expert_rows_computed_prefill": prefill_rows,
             "keys_attended_window": window_keys,
             "keys_attended_full": full_keys})


def make_program(cfg: ExaoneMoeConfig, max_new_tokens: int):
    """The jitted program, named ``lm_generate`` (``jit_lm_generate`` in a
    device trace) like every language model's."""
    return lm_decode.make_program(
        functools.partial(generate, cfg, max_new_tokens))


def window_counters(cfg: ExaoneMoeConfig, stats, real: int, steps: int
                    ) -> Dict[str, int]:
    """The ``lm.*`` window counters of one execution from its fetched
    ``stats``: `mla_moe.routing_counters`' and the keys the ``real`` rows'
    decode steps attended to by kind of layer (a padded row repeats the
    first and is nobody's)."""
    return {
        **routing_counters(cfg, stats, real, steps),
        **{f"lm.keys_attended_{kind}": int(
            stats[f"keys_attended_{kind}"][:real].sum())
           for kind in ("window", "full")}}
