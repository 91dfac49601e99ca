"""A grouped-query decoder whose queries attend to a LEARNED SELECTION of
the keys, with routed experts in every block (the language model of
Keye-VL-2.0-30B-A3B, ``model_type`` ``KeyeVL2``: Qwen3-MoE-shaped blocks
with the lightning indexer of DeepSeek-V3.2's sparse attention beside
each attention), served as ONE pipeline stage of a deployment: the first
blocks WHOLE (every expert, the whole vocabulary).

    x = E[ids]
    for l in 0..L-1:                                     # pre-norm blocks
      h = x + Attn_l(N1_l(x))
      x = h + MoE_l(N2_l(h))
    logits = W_head N(x)

    Attn, u = N1(x), position triple p = (t, h, w) of each token:
      q = u W_q -> H heads of D;  k, v = u W_k, u W_v -> G heads of D
      q, k = RMSNorm over each head's D values, then rotated
        (rotate_half; frequency pair i turns by the component of p that
        ``mrope_section`` gives it; text has t = h = w)
      the indexer:  q^I = u W^I_q -> H_I heads of D_I;
                    k^I = LayerNorm(u W^I_k)  -- ONE head of D_I;
                    w   = u W^I_w             -- H_I values
                    q^I, k^I rotated as q, k are
        I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])      (s <= t)
        S_t = the ``topk`` keys of largest I[t, .] (the lower position
              first among equals); every key where t sees no more
      out = W_o concat_h softmax_{s in S_t}(q_h . k_g(h) / sqrt(D)) v_g(h)

    MoE, n = N2(h):  p = softmax(n W_g) over ALL experts, float32; top-k;
      w = p_topk / sum(p_topk);  y = sum over the chosen e of w_e Expert_e(n)
      (no shared expert, no dense block)

**Two caches of different width in one carry**: keys and values
``[L, B, T, G, D]`` and the index keys ``[L, B, T, D_I]``, both written by
every call.  A decode step reads the narrow one WHOLE (every cached index
key of a row is scored), takes the ``topk`` best, and GATHERS those keys
and values out of the wide one: what a step reads is chosen by the data.
The prefill walks its queries in chunks of ``q_chunk_size`` (the index
scores of a whole prompt must not stand at once), each against the keys
up to its own end: a chunk that ends within the first ``topk`` positions
sees no more than ``topk`` keys a query and skips the indexer; behind
that, the chunk's scores, the ``topk``-th largest of each query's (a
search over the bits of the score: 32 counting passes, no sort), and
attention under the mask ``I[t, s] >=`` that value (ties at it by the
lower position): the same set a gather would read.

Rows stay right-aligned to one shared write index (`looplm.generate`'s
contract); a padded position is never scored, selected or attended to.

**The share** is a pipeline stage: ``num_hidden_layers`` counts the
blocks held; the expert layer is ``models/mla_moe.py``'s (`_moe`), here
with every expert held and none shared.

Selection and routing are both discontinuous, so the program returns
beside the logits what it chose: the routers' scores and choices and the
keys each query selected (``aux``); and what it read (``stats``).

Precision as the other families': weights, caches and matmul operands in
``cfg.dtype``; the residual stream, every norm, the rotation, the index
scores' sum, the softmax and the logits in float32; the router in float32
at the highest precision.

Scopes carry the published modules' names (``KeyeVL2/decode/layers/
self_attn/indexer/wq`` ...), read by ``utils/trace.KERNEL_CLASSES``.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from comfyui_distributed_tpu.models import lm_decode
from comfyui_distributed_tpu.models.layers import ATTENTION_PATHS, \
    attention_path, visible_keys
from comfyui_distributed_tpu.models.looplm import _dense, _embed, _head, \
    _rms_norm, dense_each, few_rows_here, scan_layers  # noqa: F401
from comfyui_distributed_tpu.models.mla_moe import _moe, count_values, \
    routing_counters, seeded_tree
from comfyui_distributed_tpu.models.swa_moe import _attend
from comfyui_distributed_tpu.parallel import sharding as shd


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    """The shape keys of the language model's ``config.json``, under its
    names (``sa_config``'s and ``rope_scaling``'s flattened), AS HELD:
    ``num_hidden_layers`` counts the blocks of this stage.
    ``num_experts`` is the router's width; ``experts_first`` /
    ``experts_held`` name the experts held (here all)."""
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    indexer_num_heads: int
    indexer_head_dim: int
    topk: int
    q_chunk_size: int
    mrope_section: Tuple[int, int, int]
    norm_topk_prob: bool = True
    scoring_func: str = "softmax"
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    experts_first: int = 0
    experts_held: int = -1          # -1: all of them
    dtype: Any = jnp.bfloat16       # weights, caches, matmul operands

    def __post_init__(self):
        if self.experts_held < 0:
            object.__setattr__(self, "experts_held", self.num_experts)
        object.__setattr__(self, "mrope_section", tuple(self.mrope_section))
        if not 0 <= self.experts_first <= self.experts_first \
                + self.experts_held <= self.num_experts:
            raise ValueError(
                f"experts {self.experts_first}..{self.experts_first}+"
                f"{self.experts_held} are not among the router's "
                f"{self.num_experts}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not divide "
                f"over {self.num_key_value_heads} key-value heads")
        if sum(self.mrope_section) != self.head_dim // 2:
            raise ValueError(
                f"mrope_section {self.mrope_section} does not name the "
                f"{self.head_dim // 2} frequency pairs of a head")

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def layer_applications(self) -> int:
        """Blocks one token passes through."""
        return self.num_hidden_layers


# Kwai-Keye/Keye-VL-2.0-30B-A3B config.json (the language model's keys),
# every width as published, cut in DEPTH alone: the first 6 of the 48
# blocks, one stage of an 8-stage pipeline, with all 128 experts of each
# and the whole 151,936-row vocabulary (benchmarks/chip/configs/
# keye-vl-2.0-30b-a3b-expand-sd15-512.json has the arithmetic: 8.75 GB).
# The vision tower is not held: the expander is fed text.
KEYE_VL2_STAGE = KeyeConfig(
    vocab_size=151936, hidden_size=2048, num_hidden_layers=6,
    num_attention_heads=32, num_key_value_heads=4, head_dim=128,
    moe_intermediate_size=768, num_experts=128, num_experts_per_tok=8,
    indexer_num_heads=16, indexer_head_dim=64, topk=2048, q_chunk_size=512,
    mrope_section=(16, 24, 24), norm_topk_prob=True, rms_norm_eps=1e-6,
    rope_theta=1e7)

# the CPU tests' and the rehearsal's size (fp32: deterministic
# comparisons): 4 query heads over 2 key-value heads, 2 index heads of 8,
# the 8 best keys a query in chunks of 4 queries, 8 experts top-2
TINY_DSA_MOE = KeyeConfig(
    vocab_size=512, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    moe_intermediate_size=48, num_experts=8, num_experts_per_tok=2,
    indexer_num_heads=2, indexer_head_dim=8, topk=8, q_chunk_size=4,
    mrope_section=(2, 3, 3), dtype=jnp.float32)

CONFIGS = {"full": KEYE_VL2_STAGE, "tiny": TINY_DSA_MOE}

BLOCK_NORMS = ("input_layernorm", "post_attention_layernorm")
HEAD_NORMS = ("q_norm", "k_norm")
# The seeded gains of the two head norms.  With unit gains a query's
# scores over its keys are N(0, 1): the softmax is flat, a mean over 2,048
# of 8,192 random values is as small as a mean over all of them, and no
# comparison could tell a program that never selects.  At 2 x 2 the
# scores are N(0, 16), a few keys carry a query's weight, and WHICH keys
# were selected decides the sub-layer's output.
HEAD_NORM_GAIN = 2.0


def param_shapes(cfg: KeyeConfig) -> Dict[str, Any]:
    """The parameter tree's shapes, kernels ``[in, out]``: one stack of
    blocks, each leaf with a leading layer axis; the indexer's leaves
    under ``indexer``, the routed experts ``[L, E_here, in, out]``."""
    d, D, L = cfg.hidden_size, cfg.head_dim, cfg.num_hidden_layers
    H, G = cfg.num_attention_heads, cfg.num_key_value_heads
    HI, DI, F = cfg.indexer_num_heads, cfg.indexer_head_dim, \
        cfg.moe_intermediate_size
    layers = {n: (L, d) for n in BLOCK_NORMS}
    layers.update({n: (L, D) for n in HEAD_NORMS})
    layers.update(
        q_proj=(L, d, H * D), k_proj=(L, d, G * D), v_proj=(L, d, G * D),
        o_proj=(L, H * D, d), gate=(L, d, cfg.num_experts),
        indexer={"wq": (L, d, HI * DI), "wk": (L, d, DI),
                 "k_layernorm": (L, DI), "k_layernorm_bias": (L, DI),
                 "weights_proj": (L, d, HI)},
        experts={"gate_proj": (L, cfg.experts_held, d, F),
                 "up_proj": (L, cfg.experts_held, d, F),
                 "down_proj": (L, cfg.experts_held, F, d)})
    return {"embed_tokens": (cfg.vocab_size, d), "layers": layers,
            "norm": (d,), "lm_head": (d, cfg.vocab_size)}


def param_count(cfg: KeyeConfig) -> int:
    return count_values(param_shapes(cfg))


def _norm_gain(name: str):
    if name in HEAD_NORMS:
        return HEAD_NORM_GAIN
    return 1.0 if name in BLOCK_NORMS + ("norm", "k_layernorm") else None


def _bias(key, shape):
    return 0.1 * jax.random.normal(key, shape, jnp.float32)


def seeded_params(cfg: KeyeConfig, seed) -> Dict[str, Any]:
    """`mla_moe.seeded_tree`: on the device, leaf by leaf; the head norms'
    gains HEAD_NORM_GAIN x (1 + 0.1 N), the index key norm's bias 0.1 N."""
    return seeded_tree(param_shapes(cfg), seed, cfg.dtype, _norm_gain,
                       {"k_layernorm_bias": _bias})


def load_checkpoint(path: str, cfg: KeyeConfig):
    raise NotImplementedError(
        f"{path}: no reader for a KeyeVL2 state dict yet (this family is "
        f"served from seeded weights: a stage of 30 B parameters is not a "
        f"file anybody has); remove the file or serve another model")


# --- the layer ------------------------------------------------------------

def text_positions(index, first):
    """The position triple of TEXT: all three components the token's
    index counted from its row's first real id, ``[3, B, N]``."""
    at = index[None, :] - first[:, None]
    return jnp.broadcast_to(at[None], (3, *at.shape))


def _mrope(x, positions, theta, sections):
    """Rotary embedding, the ``rotate_half`` convention, in float32, over
    position TRIPLES: ``x [B, N, H, D]``, ``positions [3, B, N]``; the
    ``D / 2`` frequency pairs lie in three ``sections`` (scaled to this
    head's width), each turning by its own component.  With equal
    components this is `looplm._rope`."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ends = [c * half // sum(sections)
            for c in itertools.accumulate(sections)]
    component = np.searchsorted(ends, np.arange(half), side="right")
    at = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)[..., component]
    ang = at * inv_freq
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _layer_norm(x, gain, bias, eps):
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32) \
        + bias.astype(jnp.float32)


def _qkv(cfg: KeyeConfig, lp, u, positions):
    """This call's queries ``[B, N, H, D]`` and keys and values
    ``[B, N, G, D]`` in the model's dtype: each head normed over its ``D``
    values, then rotated to ``positions [3, B, N]``."""
    B, N, _ = u.shape
    with jax.named_scope("q_proj"):
        q = _dense(u, lp["q_proj"], cfg).reshape(B, N, -1, cfg.head_dim)
    k, v = (t.reshape(B, N, -1, cfg.head_dim)
            for t in dense_each(u, lp, ("k_proj", "v_proj"), cfg))
    with jax.named_scope("q_norm"):
        q = _rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
    with jax.named_scope("k_norm"):
        k = _rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    with jax.named_scope("rotary"):
        q = _mrope(q, positions, cfg.rope_theta, cfg.mrope_section)
        k = _mrope(k, positions, cfg.rope_theta, cfg.mrope_section)
    return tuple(shd.constrain(t.astype(cfg.dtype), "batch", None, "heads",
                               None) for t in (q, k, v))


def _index(cfg: KeyeConfig, ip, u, positions):
    """The indexer's three projections of this call's positions: the index
    queries ``[B, N, H_I, D_I]`` and the ONE index key ``[B, N, D_I]`` in
    the model's dtype, both rotated, and the heads' weights
    ``[B, N, H_I]``, float32."""
    B, N, _ = u.shape
    with jax.named_scope("wq"):
        qi = _dense(u, ip["wq"], cfg).reshape(B, N, -1,
                                              cfg.indexer_head_dim)
    with jax.named_scope("wk"):
        ki = _dense(u, ip["wk"], cfg)
    with jax.named_scope("k_layernorm"):
        ki = _layer_norm(ki, ip["k_layernorm"], ip["k_layernorm_bias"],
                         cfg.rms_norm_eps)
    with jax.named_scope("index_rotary"):
        qi = _mrope(qi, positions, cfg.rope_theta, cfg.mrope_section)
        ki = _mrope(ki[:, :, None], positions, cfg.rope_theta,
                    cfg.mrope_section)[:, :, 0]
    with jax.named_scope("weights_proj"):
        w = _dense(u, ip["weights_proj"], cfg)
    return qi.astype(cfg.dtype), ki.astype(cfg.dtype), w


def index_scores(qi, w, ki):
    """``I[b, t, s] = sum_j w[b, t, j] relu(q^I[b, t, j] . k^I[b, s])``,
    float32: ``qi [B, N, H_I, D_I]``, ``w [B, N, H_I]``, ``ki
    [B, M, D_I]``.  A row at a time: the heads' scores before their sum
    are ``H_I`` times the result (a chunk of 512 queries against 8192
    keys: 268 MB a row in float32) and must not stand for four rows at
    once."""
    def row(qi, w, ki):
        each = jnp.einsum("njd,md->njm", qi, ki,
                          preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(each) * w[..., None], axis=1)
    return jax.lax.map(lambda args: row(*args), (qi, w, ki))


def _sortable(x):
    """float32 -> uint32 in the same order (-inf lowest, above 0; the
    two zeros, which compare equal, one value: a sum of ReLUs that are
    all 0 is -0 where its weights are negative)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x),
                                        jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _largest_under(count, bits: int, limit, like):
    """The largest ``q`` below ``2 ** bits`` with ``count(q) <= limit``
    (``count`` non-decreasing in ``q``, ``count(0) <= limit``), built bit
    by bit from the top: ``bits`` counting passes."""
    def body(i, q):
        bit = jnp.left_shift(jnp.ones_like(q), (bits - 1 - i).astype(q.dtype))
        return jnp.where(count(q | bit) <= limit, q | bit, q)
    return jax.lax.fori_loop(0, bits, body, jnp.zeros_like(like))


def select_keys(scores, valid, k: int):
    """The mask of each query's ``k`` best keys: ``scores [..., M]``
    float32, ``valid [..., M]`` (the keys the query may see), ``M > k``.
    Every valid key where no more than ``k`` are; among keys that tie at
    the ``k``-th score, the lower positions.  No sort: the ``k``-th
    largest score is searched bit by bit (the largest value that ``k``
    scores reach: 32 passes that count), then the position behind which
    the ties are left out (``log2 M`` passes)."""
    M = scores.shape[-1]
    keys = jnp.where(valid, _sortable(scores), jnp.uint32(0))
    at = jnp.arange(M, dtype=jnp.int32)

    def fewer_reach(v):                 # non-decreasing in v
        return -jnp.sum(keys >= v[..., None], axis=-1, dtype=jnp.int32)

    # the largest v that at least k scores reach (v = 0: every score)
    kth = _largest_under(fewer_reach, 32, -k, keys[..., 0])
    above, ties = keys > kth[..., None], keys == kth[..., None]
    need = k - jnp.sum(above, axis=-1, dtype=jnp.int32)

    def ties_before(q):
        return jnp.sum(ties & (at < q[..., None]), axis=-1, dtype=jnp.int32)

    cut = _largest_under(ties_before, M.bit_length(), need, need)
    return valid & (above | (ties & (at < cut[..., None])))


def _pack(mask, words: int):
    """``mask [..., m]`` as ``words`` uint32 a row: key ``s`` is bit
    ``s % 32`` of word ``s // 32``."""
    pad = [(0, 0)] * (mask.ndim - 1) + [(0, 32 * words - mask.shape[-1])]
    bits = jnp.pad(mask, pad).reshape(*mask.shape[:-1], words, 32)
    return jnp.sum(jnp.left_shift(bits.astype(jnp.uint32),
                                  jnp.arange(32, dtype=jnp.uint32)),
                   axis=-1, dtype=jnp.uint32)


def _indices_of(mask, count: int):
    """The positions set in ``mask [B, m]``, ``[B, count]`` int32, -1
    behind the last."""
    live, at = jax.lax.top_k(mask.astype(jnp.int32),
                             min(count, mask.shape[-1]))
    at = jnp.where(live > 0, at, -1).astype(jnp.int32)
    return jnp.pad(at, ((0, 0), (0, count - at.shape[-1])),
                   constant_values=-1)


def _attend_selected(cfg: KeyeConfig, q, k, v, qi, ki, w, first,
                     gathered: int, own=None):
    """The prefill's attention: the ``P`` queries of this call against the
    ``M`` keys ``k`` / ``v`` / ``ki`` hold, its own the LAST ``P`` of them
    (behind a snapshot the ``M - P`` in front are the cache's), a chunk of
    ``q_chunk_size`` queries at a time, each chunk against the keys up to
    its end.  A chunk that ends within the first ``topk`` positions
    attends to every key it may see; a later one scores its index queries
    against the index keys, selects (`select_keys`) and attends under
    that mask.  A query that is not a row's ``own [B, P]`` (behind a
    snapshot a shorter row's first slots, where the end of its prefix
    stands) sees nothing, as a padded one.  Returns ``[B, P, H * D]``, the
    selection of every query packed (`_pack`, ``[B, P, ceil(M / 32)]``),
    the last query's as indices ``[B, gathered]`` (-1 behind the last)
    and, int32 ``[B]``: the index keys scored, the keys selected (by the
    queries that see more than ``topk``) and the keys attended to (by
    all)."""
    B, P = q.shape[:2]
    M = k.shape[1]
    held = M - P
    C, K, H = cfg.q_chunk_size, cfg.topk, cfg.num_attention_heads
    words = -(-M // 32)
    out, packed = [], []
    scored = selected = attended = jnp.zeros((B,), jnp.int32)
    for start in range(0, P, C):
        stop = min(start + C, P)
        end = held + stop
        seen = visible_keys(end, jnp.arange(held + start, end), first)
        if own is not None:
            seen = seen & own[:, start:stop, None]
        if end > K:
            sees = jnp.sum(seen, axis=-1, dtype=jnp.int32)
            with jax.named_scope("indexer"):
                with jax.named_scope("index_scores"):
                    scores = index_scores(qi[:, start:stop], w[:, start:stop],
                                          ki[:, :end])
                with jax.named_scope("topk"):
                    seen = select_keys(scores, seen, K)
            scored = scored + jnp.sum(sees, axis=-1)
            selected = selected + jnp.sum(jnp.where(sees > K, K, 0), axis=-1)
        ATTENTION_PATHS.bump(attention_path(
            jax.default_backend(), B, stop - start, end, H, masked=True,
            selected=end > K))
        # (a chunk that selects nothing attends under the same call: its
        # selection is every key it may see)
        out.append(_attend(q[:, start:stop], k[:, :end], v[:, :end], None,
                           selected=seen))
        attended = attended + jnp.sum(seen, axis=(1, 2), dtype=jnp.int32)
        with jax.named_scope("selection_record"):
            packed.append(_pack(seen, words))
    with jax.named_scope("selection_record"):
        last = _indices_of(jnp.pad(seen[:, -1], ((0, 0), (0, M - end))),
                           gathered)
    return jnp.concatenate(out, axis=1), jnp.concatenate(packed, axis=1), \
        last, (scored, selected, attended)


def _attend_gathered(cfg: KeyeConfig, q, qi, w, kc, vc, ic, l, at, first,
                     gathered: int):
    """A decode step's attention: the one query a row at buffer index
    ``at`` scores EVERY cached index key of layer ``l`` (``ic``, read
    whole), takes its ``gathered`` best among those it may see, and reads
    those keys and values alone out of ``kc`` / ``vc``, by index.
    Returns ``[B, 1, H * D]``, the indices ``[B, gathered]`` (-1 where the
    row sees fewer) and the counts of `_attend_selected`."""
    B, T = q.shape[0], ic.shape[2]
    seen = visible_keys(T, at[None], first)[:, 0]
    with jax.named_scope("indexer"):
        with jax.named_scope("index_scores"):
            scores = index_scores(qi, w, jax.lax.dynamic_index_in_dim(
                ic, l, keepdims=False).astype(cfg.dtype))[:, 0]
        with jax.named_scope("topk"):
            best, chosen = jax.lax.top_k(
                jnp.where(seen, scores, -jnp.inf), gathered)
            live = best > -jnp.inf
        with jax.named_scope("gather"):
            rows = jnp.arange(B)[:, None]
            k, v = (c[l, rows, chosen].astype(cfg.dtype) for c in (kc, vc))
    ATTENTION_PATHS.bump(attention_path(
        jax.default_backend(), B, 1, gathered, cfg.num_attention_heads,
        masked=True, selected=True))
    a = _attend(q, k, v, None, selected=live[:, None, :])
    sees = jnp.sum(seen, axis=-1, dtype=jnp.int32)
    return a, jnp.where(live, chosen, -1).astype(jnp.int32), \
        (sees, jnp.where(sees > cfg.topk, cfg.topk, 0),
         jnp.sum(live, axis=-1, dtype=jnp.int32))


def _attention(cfg: KeyeConfig, lp, x, positions, index, first, caches, l,
               decode: bool, prefix: int = 0):
    """``h = x + Attn(N1(x))``, the three caches with this call's keys,
    values and index keys written into layer ``l`` at the buffer indices
    ``index``, and what was selected (`_attend_selected`'s or
    `_attend_gathered`'s).  A prefill from the front of the buffer attends
    to this call's own keys (they ARE the cache's); one behind ``prefix``
    positions that the caches hold already (`from_prefix`) writes a row's
    own entries alone and attends to the caches as far as its last
    index."""
    kc, vc, ic = caches
    gathered = min(cfg.topk, ic.shape[2])
    own = index[None, :] >= first[:, None] + prefix if prefix else None
    with jax.named_scope("input_layernorm"):
        u = _rms_norm(x, lp["input_layernorm"], cfg.rms_norm_eps)
    with jax.named_scope("self_attn"):
        q, k, v = _qkv(cfg, lp, u, positions)
        with jax.named_scope("indexer"):
            qi, ki, w = _index(cfg, lp["indexer"], u, positions)
        with jax.named_scope("kv_cache"):
            if prefix:
                k, v, ki = (lm_decode.own_entries(own, t, c, l, index[0])
                            for c, t in zip((kc, vc, ic), (k, v, ki)))
            kc, vc = (jax.lax.dynamic_update_slice(
                c, t[None].astype(c.dtype), (l, 0, index[0], 0, 0))
                for c, t in zip((kc, vc), (k, v)))
            ic = jax.lax.dynamic_update_slice(
                ic, ki[None].astype(ic.dtype), (l, 0, index[0], 0))
            if prefix:
                k, v, ki = (jax.lax.dynamic_index_in_dim(
                    c, l, keepdims=False)[:, :prefix + len(index)].astype(
                        cfg.dtype) for c in (kc, vc, ic))
        if decode:
            a, *chose = _attend_gathered(cfg, q, qi, w, kc, vc, ic, l,
                                         index[0], first, gathered)
        else:
            a, *chose = _attend_selected(cfg, q, k, v, qi, ki, w, first,
                                         gathered, own)
        with jax.named_scope("o_proj"):
            a = _dense(a, lp["o_proj"], cfg)
    return x + a, (kc, vc, ic), chose


def _stack(cfg: KeyeConfig, params, x, positions, index, first, caches,
           decode: bool, prefix: int = 0):
    """Every block held.  ``caches`` is `empty_cache`'s triple or, behind
    a shared prefix, `from_prefix`'s; each block writes this call's
    entries at the buffer indices ``index [N]`` (consecutive, the same
    for every row); row ``b``'s real entries start at ``first[b]``, the
    first ``prefix`` of them in ``caches`` already; ``positions
    [3, B, N]`` are the tokens' position triples.  The layer index is
    walked with the stacked leaves closed over (`looplm.scan_layers`), so
    a few-row call's products stream their leaves in place and no expert
    leaf is sliced.  Returns the
    normed last state, the caches and a dict: the routers' ``scores
    [B, L, E]`` at the LAST position and ``chosen [B, N, L, k]``, the
    routing ``counts`` summed over the blocks (local pairs ``[B]``, hits,
    dropped, rows computed), ``selection [B, L, gathered]`` (the last
    query's keys by index), ``keys`` (``[B]`` each: scored, selected,
    attended, summed over the blocks) and, of a prefill, ``packed
    [B, N, L, words]`` (every query's selection over the buffer as far
    as the call's last index)."""
    layers = dict(params["layers"])
    experts = layers.pop("experts")

    def block(carry, xs):
        x, *caches = carry
        lp, l = xs
        h, caches, chose = _attention(cfg, lp, x, positions, index, first,
                                      caches, l, decode, prefix)
        with jax.named_scope("post_attention_layernorm"):
            n = _rms_norm(h, lp["post_attention_layernorm"],
                          cfg.rms_norm_eps)
        with jax.named_scope("mlp"):
            m, ((scores, chosen), counts) = _moe(cfg, lp, experts, l, n)
        return (h + m, *caches), (scores[:, -1], chosen, counts, chose)

    with jax.named_scope("layers"):
        (x, *caches), (scores, chosen, counts, chose) = scan_layers(
            block, (x, *caches), layers, cfg.num_hidden_layers, True)
    with jax.named_scope("final_norm"):
        x = _rms_norm(x, params["norm"], cfg.rms_norm_eps)
    *packed, selection, keys = chose
    out = {"scores": scores.swapaxes(0, 1),
           "chosen": jnp.moveaxis(chosen, 0, 2),
           "counts": tuple(c.sum(axis=0) for c in counts),
           "selection": selection.swapaxes(0, 1),
           "keys": tuple(c.sum(axis=0) for c in keys)}
    if packed:
        out["packed"] = jnp.moveaxis(packed[0], 0, 2)
    return x, tuple(caches), out


def empty_cache(cfg: KeyeConfig, batch: int, length: int):
    """``(keys, values, index keys)``: ``[L, B, T, G, D]`` twice and the
    narrow ``[L, B, T, D_I]`` that exists only to choose what to read of
    the other two."""
    wide = (cfg.num_hidden_layers, batch, length, cfg.num_key_value_heads,
            cfg.head_dim)
    return jnp.zeros(wide, cfg.dtype), jnp.zeros(wide, cfg.dtype), \
        jnp.zeros((*wide[:3], cfg.indexer_head_dim), cfg.dtype)


def kv_cache_bytes_by_kind(cfg: KeyeConfig, batch: int, length: int
                           ) -> Dict[str, int]:
    position = cfg.num_hidden_layers * batch * length \
        * jnp.dtype(cfg.dtype).itemsize
    return {"keys_values": 2 * cfg.num_key_value_heads * cfg.head_dim
            * position, "index_keys": cfg.indexer_head_dim * position}


def kv_cache_bytes(cfg: KeyeConfig, batch: int, length: int) -> int:
    return sum(kv_cache_bytes_by_kind(cfg, batch, length).values())


# --- a prefix shared between requests ----------------------------------------
#
# What a prompt's first K ids leave behind is K keys, values and index
# keys a block: the SNAPSHOT (`make_prefix_program`), the caches of one row
# with no axis of rows, beside what the blocks CHOSE over those K
# positions (`generate`'s ``aux`` records the whole prompt's).  Rows whose
# prompts start with those ids start from copies of it and prefill their
# own suffix only.  Legal because a token's position counts from its
# row's first real id (`text_positions`): a prefix's key is rotated the
# same in every row, wherever the row's padding pushes it in the buffer,
# so the snapshot can stand at each row's own offset.

PREFIX_CACHES = ("keys", "values", "index_keys")


def prefix_bytes(cfg: KeyeConfig, positions: int) -> int:
    """Bytes of the snapshot behind ``positions`` ids: the three caches
    and the records (the experts chosen, int32; every query's selection,
    a bit a key)."""
    records = cfg.num_hidden_layers * positions * 4 \
        * (cfg.num_experts_per_tok + -(-positions // 32))
    return kv_cache_bytes(cfg, 1, positions) + records


def make_prefix_program(cfg: KeyeConfig):
    """The jitted maker of a snapshot, ``lm_prefix_state`` (NOT
    ``lm_generate``: what is counted and timed an execution is the served
    program's): ``prefix_ids [K]``, one row and no padding, through every
    block as a prefill -> ``keys``, ``values`` ``[L, K, G, D]`` and
    ``index_keys`` ``[L, K, D_I]`` as the caches hold them, ``choices
    [K, L, k]`` (the experts chosen) and ``selected [K, L, ceil(K / 32)]``
    (`_pack`: every query's selection by position in the ROW, which is
    the buffer's index here)."""

    def lm_prefix_state(params, prefix_ids):
        K, = prefix_ids.shape
        index, first = jnp.arange(K), jnp.zeros((1,), jnp.int32)
        with jax.named_scope("KeyeVL2"), jax.named_scope("prefill"):
            _, caches, out = _stack(
                cfg, params, _embed(params, prefix_ids[None]),
                text_positions(index, first), index, first,
                empty_cache(cfg, 1, K), decode=False)
        return {**{name: c[:, 0] for name, c in zip(PREFIX_CACHES, caches)},
                "choices": out["chosen"][0], "selected": out["packed"][0]}

    return jax.jit(lm_prefix_state)


def from_prefix(caches, prefix, first):
    """`empty_cache`'s ``caches`` with every row started from the snapshot
    ``prefix``: its K keys, values and index keys written at row ``b``'s
    own offset ``first[b]``, directly in front of where that row's suffix
    will be written (the padding lies in front of both, so the mask stays
    ``kv_start = first`` with no hole)."""
    with jax.named_scope("kv_cache"):
        return tuple(lm_decode.write_at_offsets(cache, prefix[name], first)
                     for cache, name in zip(caches, PREFIX_CACHES))


def _shift_up(words, by, width: int):
    """Packed masks ``words [..., w]`` (`_pack`) with every key moved up
    ``by`` positions (a traced int32), in ``width >= w`` words: bit ``s``
    of a mask is bit ``s + by`` of the result, what would lie behind the
    last word is dropped.  A funnel shift: word ``j`` takes the low bits
    of word ``j - by // 32`` and the high bits of the one below."""
    pad = [(0, 0)] * (words.ndim - 1)
    words = jnp.pad(words, pad + [(0, width - words.shape[-1])])
    bits = (by % 32).astype(jnp.uint32)
    # (a shift by 32 is no shift of 0 bits: nothing is carried)
    carried = jnp.where(bits > 0, words >> ((32 - bits) % 32), 0)
    moved = (words << bits) | jnp.pad(carried, pad + [(1, 0)])[..., :-1]
    return jax.lax.dynamic_slice_in_dim(
        jnp.pad(moved, pad + [(width, 0)]), width - by // 32, width, axis=-1)


def _prefix_records(prefix, first, chosen, packed):
    """The records of the WHOLE prompt buffer behind a snapshot: ``chosen
    [B, S, L, k]`` and ``packed [B, S, L, words]`` of the suffix's call
    stand behind the snapshot's K positions, and row ``b``'s
    ``first[b] ... first[b] + K - 1`` hold the snapshot's own (over a
    shorter row's first suffix slots too: they are its prefix's end), its
    selections moved up to the buffer's indices (`_shift_up`)."""
    K = prefix["choices"].shape[0]

    def behind(rows, record):
        """``rows [B, S, ...]`` behind K positions, ``record`` (``[K,
        ...]``, or row ``b``'s from ``b``) written at each row's offset."""
        return lm_decode.write_at_offsets(
            jnp.pad(rows, ((0, 0), (K, 0), (0, 0), (0, 0))), record, first,
            rows=0)

    with jax.named_scope("gate"):
        chosen = behind(chosen, prefix["choices"])
    with jax.named_scope("selection_record"):
        packed = behind(packed, lambda b: _shift_up(
            prefix["selected"], first[b], packed.shape[-1]))
    return chosen, packed


# --- the served program ---------------------------------------------------

def generate(cfg: KeyeConfig, max_new_tokens: int, params, prompt_ids,
             prompt_len, seed, temperature, prefix=None
             ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array],
                        Dict[str, jax.Array]]:
    """Prefill, then ``max_new_tokens`` decode steps, for every row:
    `looplm.generate`'s contract (rows, lengths, seeds, temperatures,
    padding never attended to).  With a snapshot ``prefix`` of K ids
    (`make_prefix_program`) ``prompt_ids [B, S]`` holds what FOLLOWS them
    in every row: each row starts from the snapshot (`from_prefix`) and
    the blocks run over the ``S`` positions behind it; the buffer of
    ``P = K + S`` positions is laid out ``padding | prefix | row's own
    ids``, a row's last id at ``P - 1`` as without one, so the decode
    steps and every buffer index below are what they are without.
    Returns the new ids ``[B, N]``, the
    float32 logits each was drawn from ``[B, N, V]``, ``aux`` as
    `mla_moe.generate`'s (``router_scores``, ``expert_choices``,
    ``prompt_choices``) with, where those logits were computed, the keys
    each block's query selected ``key_selections [B, N, L, topk]`` (by
    buffer index; -1 where it saw fewer) and, over the prompt buffer,
    every query's selection ``prompt_selected [B, P, L, ceil(P / 32)]``
    (`_pack`); and ``stats``, int32: over the DECODE steps
    ``expert_pairs_local [B]``, ``expert_hits``, ``keys_scored [B]``
    (index keys read), ``keys_selected [B]`` (by queries that saw more
    than ``topk``) and ``keys_attended [B]`` (what attention read),
    summed over the blocks; over the PREFILL the same four with
    ``_prefill`` behind the name, ``expert_rows_computed_prefill`` and
    ``prefill_positions`` (every position of every row THE BLOCKS RAN
    OVER: of a prefix served from a snapshot nothing is computed and
    nothing counted, while its records stand in ``aux``); over both
    ``expert_pairs_dropped`` (0)."""
    B, S = prompt_ids.shape
    K = lm_decode.prefix_length(prefix)
    P = K + S
    first = S - jnp.broadcast_to(prompt_len, (B,))

    def at_last(out):
        """What the blocks chose at a call's last position."""
        return out["scores"], out["chosen"][:, -1], out["selection"]

    def prefill():
        with jax.named_scope("prefill"):
            # every row's last real id at P - 1
            ids = jax.vmap(jnp.roll)(prompt_ids, first)
            index = jnp.arange(K, P)
            x, positions = _embed(params, ids), text_positions(index, first)
            caches = empty_cache(cfg, B, P + max_new_tokens)
            if prefix is not None:
                caches = from_prefix(caches, prefix, first)
            x, caches, out = _stack(cfg, params, x, positions, index, first,
                                    caches, decode=False, prefix=K)
            logits = _head(cfg, params, x[:, S - 1:])[:, 0]
            if prefix is not None:
                out["chosen"], out["packed"] = _prefix_records(
                    prefix, first, out["chosen"], out["packed"])
            rows = jnp.zeros((B,), jnp.int32)
            return (logits, at_last(out), caches,
                    (rows, jnp.int32(0), out["counts"][2], rows, rows, rows),
                    out)

    def step(token, i, caches):
        index = P + i[None]
        x, caches, out = _stack(
            cfg, params, _embed(params, token[:, None]),
            text_positions(index, first), index, first, caches, decode=True)
        return (_head(cfg, params, x)[:, 0], at_last(out), caches,
                (*out["counts"][:3], *out["keys"]))

    tokens, logits, (scores, choices, selections), \
        (pairs, hits, dropped, scored, selected, attended), prefilled = \
        lm_decode.generate("KeyeVL2", B, prefill, step, max_new_tokens, seed,
                           temperature)
    prefill_pairs, _, _, prefill_rows = prefilled["counts"]
    return (tokens, logits,
            {"router_scores": scores, "expert_choices": choices,
             "prompt_choices": prefilled["chosen"],
             "key_selections": selections,
             "prompt_selected": prefilled["packed"]},
            {"expert_pairs_local": pairs, "expert_hits": hits,
             "expert_pairs_dropped": dropped,
             "keys_scored": scored, "keys_selected": selected,
             "keys_attended": attended,
             "expert_pairs_local_prefill": prefill_pairs,
             "expert_rows_computed_prefill": prefill_rows,
             "prefill_positions": jnp.int32(B * S),
             **{f"keys_{name}_prefill": count for name, count in zip(
                 ("scored", "selected", "attended"), prefilled["keys"])}})


def make_program(cfg: KeyeConfig, max_new_tokens: int):
    """The jitted program, named ``lm_generate`` (``jit_lm_generate`` in a
    device trace) like every language model's.  With a sixth argument,
    `make_prefix_program`'s snapshot, ``prompt_ids`` holds what follows
    the prefix."""
    return lm_decode.make_program(
        functools.partial(generate, cfg, max_new_tokens))


def window_counters(cfg: KeyeConfig, stats, real: int, steps: int
                    ) -> Dict[str, int]:
    """The ``lm.*`` window counters of one execution from its fetched
    ``stats``: `mla_moe.routing_counters`', the index keys the ``real``
    rows' decode steps scored and the keys they selected and attended to
    (a padded row repeats the first and is nobody's), and what the
    prefill computed for EVERY row of the program (its positions, the
    pairs it scored, selected and attended to)."""
    def real_rows(name):
        return int(stats[name][:real].sum())

    return {
        **routing_counters(cfg, stats, real, steps),
        "lm.prefill_positions": int(stats["prefill_positions"]),
        "lm.keys_scored_decode": real_rows("keys_scored"),
        "lm.keys_selected": real_rows("keys_selected"),
        "lm.keys_attended": real_rows("keys_attended"),
        **{f"lm.keys_{name}_prefill": int(
            stats[f"keys_{name}_prefill"].sum())
           for name in ("scored", "selected", "attended")}}
