"""The plain reference of the grouped-query decoder with a learned key
selection and routed experts (the language model of Keye-VL-2.0-30B-A3B,
``model_type`` ``KeyeVL2``; the blocks are Qwen3-MoE's, the indexer is the
lightning indexer of DeepSeek-V3.2's sparse attention): the equations
below in straightforward ``jax.numpy``, float32, every matrix product at
``jax.default_matmul_precision("highest")``.  A full forward pass over
one whole sequence: **no cache, no gather, no threshold search, no scan,
no kernel, no batching of experts**; position by position all index
scores, a FULL SORT, the first ``topk``, a softmax over exactly those.
The only blocks are blocks of QUERIES (``block``), which let the
``[T, T]`` scores of a long sequence fit and change no number.
Independent of the program: it imports nothing of
``comfyui_distributed_tpu``.

    x = E[ids]
    for every block l:
      u = RMSNorm(x; g_1)
      q = u W_q -> H heads of D;  k, v = u W_k, u W_v -> G heads of D
      q = RMSNorm(q; g_q), k = RMSNorm(k; g_k)     # over each head's D values
      q, k = RoPE3(q), RoPE3(k)
      q^I = RoPE3(u W^I_q) -> H_I heads of D_I
      k^I = RoPE3(LayerNorm(u W^I_k; g_I, b_I))    # ONE head of D_I
      w   = u W^I_w                                # H_I values
      I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])          for s <= t
      S_t = the first topk of the keys s <= t sorted by I[t, s] descending,
            the lower position first among equals (all of them where
            t + 1 <= topk)
      s[h, t, u] = q[t, h] . k[u, h // (H / G)] / sqrt(D) for u in S_t, softmax
      h = x + concat_h(s v[:, h // (H / G)]) W_o
      n = RMSNorm(h; g_2)
      p = softmax(n W_g) over ALL E experts; top-k
      w = p_topk / sum(p_topk)                     # norm_topk_prob
      x = h + sum_{e in top-k(n), e in experts_held} w_e Expert_e(n)
    logits = RMSNorm(x; g) W_head

    expert:  (silu(n W_gate) * n W_up) W_down
    RoPE3 (``rotate_half``: value i pairs with value i + D/2; theta ** (-2i/D)):
      frequency pair i turns by component c(i) of the token's position
      triple (t, h, w), c(i) = 0, 1, 2 over ``mrope_section``'s three runs
      of pairs; for text t = h = w = the token's index

``experts_held`` (a sequence of expert numbers, or None for all) says
which routed experts THIS share holds; ``params["layers"]["experts"]``
holds exactly those, in that order.

``config`` is the language model's ``config.json`` as a mapping
(``sa_config`` and ``rope_scaling`` nested as published),
``num_hidden_layers`` the blocks HELD; ``params`` the tree the program
serves, whatever its storage type:

    embed_tokens [V, d]; norm [d]; lm_head [d, V];
    layers: each leaf stacked on a leading layer axis --
      input_layernorm (g_1), post_attention_layernorm (g_2) [L, d];
      q_proj [L, d, H D]; k_proj, v_proj [L, d, G D]; o_proj [L, H D, d];
      q_norm (g_q), k_norm (g_k) [L, D]; gate [L, d, E];
      indexer: wq [L, d, H_I D_I]; wk [L, d, D_I]; k_layernorm (g_I),
        k_layernorm_bias (b_I) [L, D_I]; weights_proj [L, d, H_I];
      experts: gate_proj, up_proj [L, E_here, d, F]; down_proj [L, E_here, F, d].

What the catalog's ``config`` does not carry, and this file therefore
ASSUMES (each is an ``assumed`` entry of the configuration's file):

* pre-norm blocks (Qwen3-MoE's), a final norm, an untied head, no bias;
* RMSNorm over the D values of every query and key head before the
  rotation;
* the indexer reads the block's normed input (no query latent exists
  here); its key passes a LayerNorm with a bias (eps ``rms_norm_eps``);
  its heads are rotated over their 64 values, the three sections scaled
  to its 32 pairs (8, 12, 12); its positive uniform scales (1/sqrt(D_I),
  1/sqrt(H_I)) are left out: they order nothing;
* ``q_chunk_size`` / ``kv_chunk_size`` tile the index computation and
  select nothing by blocks;
* softmax scoring over all experts with no correction bias, no scaling
  factor, no shared expert.

`forward` takes ``choices [T, L, k]`` (the experts to use in place of its
own top-k, their weights still from its own scores), ``selections
[T, L, T]`` (the keys each query attends to in place of its own sort:
both are discontinuous), ``layers`` (stop behind that many blocks) and
``breakage``, one of BREAKAGES: readings that a comparison which cannot
see the mechanism would accept.
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"
# no selection (every earlier key); the last topk keys in place of the
# best; the index scores without the ReLU; without the heads' weights
BREAKAGES = ("no_selection", "last_topk", "no_relu", "no_head_weights")


def f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain


def layer_norm(x, gain, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain + bias


def rope3(x, positions, theta, sections):
    """``x [T, heads, D]`` rotated to the position triples ``positions
    [3, T]``: value ``i`` pairs with value ``i + D/2``; pair ``i`` turns
    by the component its section names (the sections scaled to ``D / 2``
    pairs)."""
    D = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ends = [c * (D // 2) // sum(sections)
            for c in itertools.accumulate(sections)]
    component = np.searchsorted(ends, np.arange(D // 2), side="right")
    ang = f32(positions)[component].T[:, None, :] * inv_freq
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    turned = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + turned * sin


def text_positions(T: int):
    return jnp.broadcast_to(jnp.arange(T)[None], (3, T))


def sections_of(config):
    return tuple(config["rope_scaling"]["mrope_section"])


def index_keys(config, ip, u, positions):
    """The ONE index key of every position, ``[T, D_I]``."""
    ki = layer_norm(u @ ip["wk"], ip["k_layernorm"], ip["k_layernorm_bias"],
                    config["rms_norm_eps"])
    return rope3(ki[:, None], positions, float(config["rope_theta"]),
                 sections_of(config))[:, 0]


def index_scores(config, ip, u, positions, ki, breakage=None):
    """``I[t, s]`` of the queries ``u [Tq, d]`` at ``positions [3, Tq]``
    against the index keys ``ki [T, D_I]``."""
    sa = config["sa_config"]
    qi = rope3((u @ ip["wq"]).reshape(len(u), sa["indexer_num_heads"],
                                      sa["indexer_head_dim"]),
               positions, float(config["rope_theta"]), sections_of(config))
    each = jnp.einsum("tjd,sd->tjs", qi, ki)
    if breakage != "no_relu":
        each = jax.nn.relu(each)
    if breakage == "no_head_weights":
        return jnp.sum(each, axis=1)
    return jnp.einsum("tjs,tj->ts", each, u @ ip["weights_proj"])


def select(config, scores, at, breakage=None):
    """``S_t`` as a mask ``[Tq, T]`` for the queries at the indices
    ``at [Tq]``: the keys ``s <= t`` sorted by score, descending, the
    lower position first among equals; the first ``topk``."""
    topk = config["sa_config"]["topk"]
    keys = jnp.arange(scores.shape[-1])
    seen = keys[None, :] <= at[:, None]
    if breakage == "no_selection":
        return seen
    if breakage == "last_topk":
        return seen & (keys[None, :] > at[:, None] - topk)
    order = jnp.argsort(-jnp.where(seen, scores, -jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1)
    return seen & (rank < topk)


def attention(config, lp, u, positions, at, k, v, ki, selection=None,
              breakage=None):
    """The queries ``u [Tq, d]`` (normed inputs at the indices ``at``,
    position triples ``positions [3, Tq]``) against the whole sequence's
    keys and values ``k``, ``v [T, G, D]`` and index keys ``ki``; the
    concatenated heads ``[Tq, H D]`` and the selection used ``[Tq, T]``."""
    H, G = config["num_attention_heads"], config["num_key_value_heads"]
    D, eps = config["head_dim"], config["rms_norm_eps"]
    q = rms_norm((u @ lp["q_proj"]).reshape(len(u), H, D), lp["q_norm"], eps)
    q = rope3(q, positions, float(config["rope_theta"]), sections_of(config))
    if selection is None:
        selection = select(config, index_scores(
            config, lp["indexer"], u, positions, ki, breakage), at, breakage)
    # query head h reads key-value head h // (H / G)
    k, v = (jnp.repeat(t, H // G, axis=1) for t in (k, v))
    scores = jnp.einsum("thd,uhd->htu", q, k) / jnp.sqrt(float(D))
    scores = jnp.where(selection[None], scores, -jnp.inf)
    a = jnp.einsum("htu,uhd->thd", jax.nn.softmax(scores, axis=-1), v)
    return a.reshape(len(u), -1), selection


def keys_values(config, lp, u, positions):
    """Every position's keys (normed, rotated) and values ``[T, G, D]``."""
    G, D = config["num_key_value_heads"], config["head_dim"]
    k = rms_norm((u @ lp["k_proj"]).reshape(len(u), G, D), lp["k_norm"],
                 config["rms_norm_eps"])
    k = rope3(k, positions, float(config["rope_theta"]), sections_of(config))
    return k, (u @ lp["v_proj"]).reshape(len(u), G, D)


def gated_mlp(w, n):
    return (jax.nn.silu(n @ w["gate_proj"]) * (n @ w["up_proj"])) \
        @ w["down_proj"]


def router(config, gate, n):
    """Scores over all experts ``[T, E]`` (a softmax), the top-k
    ``[T, k]``."""
    scores = jax.nn.softmax(n @ gate, axis=-1)
    _, chosen = jax.lax.top_k(scores, config["num_experts_per_tok"])
    return scores, chosen


def routed(config, experts, experts_held, n, scores, chosen):
    """The routed experts' part from the experts held: a loop over them,
    each over every token, times the token's weight for it (0 where the
    token did not choose it)."""
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    if config.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
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
def prepare(config, lp, x, positions):
    """What every query of a block needs of the whole sequence: the
    normed input ``u`` and the keys, values and index keys."""
    u = rms_norm(x, lp["input_layernorm"], config["rms_norm_eps"])
    return (u, *keys_values(config, lp, u, positions),
            index_keys(config, lp["indexer"], u, positions))


@highest
def attend_rows(config, lp, x, positions, at, u, k, v, ki, selection=None,
                breakage=None):
    """``h = x + Attn(N1(x))`` of the rows ``at`` (indices ``[Tq]``) and
    the selection they used."""
    a, selection = attention(config, lp, u[at], positions[:, at], at, k, v,
                             ki, selection, breakage)
    return x[at] + a @ lp["o_proj"], selection


def attend(config, lp, x, positions, selection=None, breakage=None,
           block=None):
    """``h = x + Attn(N1(x))`` over the whole sequence ``x [T, d]``, a
    ``block`` of queries at a time (None: all at once), and the selection
    used ``[T, T]``."""
    T = len(x)
    whole = prepare(config, lp, x, positions)
    out, used = [], []
    for start in range(0, T, block or T):
        at = jnp.arange(start, min(start + (block or T), T))
        h, sel = attend_rows(config, lp, x, positions, at, *whole,
                             None if selection is None
                             else jnp.asarray(selection)[at], breakage)
        out.append(h)
        used.append(sel)
    return jnp.concatenate(out), jnp.concatenate(used)


@highest
def mlp_input(config, lp, h):
    """``N2(h)`` and the router's scores and own choices."""
    n = rms_norm(h, lp["post_attention_layernorm"], config["rms_norm_eps"])
    return (n, *router(config, lp["gate"], n))


gated_mlp = highest(gated_mlp)
routed = highest(routed)


def block_of(config, lp, x, positions, experts_held=None, chosen=None,
             selection=None, breakage=None, block=None):
    """One block over the whole sequence ``x [T, d]``; ``lp`` is that
    block's leaves, float32.  Returns the new state, the router's scores,
    the choices used and the selection used."""
    h, selection = attend(config, lp, x, positions, selection, breakage,
                          block)
    n, scores, own = mlp_input(config, lp, h)
    chosen = own if chosen is None else chosen
    if experts_held is None:
        experts_held = range(config["num_experts"])
    return h + routed(config, lp["experts"], experts_held, n, scores,
                      chosen), scores, chosen, selection


def head(config, params, x):
    with jax.default_matmul_precision(PRECISION):
        return rms_norm(x, f32(params["norm"]), config["rms_norm_eps"]) \
            @ f32(params["lm_head"])


def layer_params(stack, l):
    return jax.tree_util.tree_map(lambda leaf: f32(leaf[l]), stack)


def forward(config, params, ids, positions=None, experts_held=None,
            choices=None, selections=None, layers=None, breakage=None,
            block=None):
    """``ids [T]`` -> logits ``[T, V]``, router scores ``[T, L, E]``, the
    choices used ``[T, L, k]`` and the selections used ``[T, L, T]``,
    float32 / int32 / bool.  ``positions [3, T]`` are the tokens' position
    triples (None: text)."""
    x = f32(params["embed_tokens"])[jnp.asarray(ids)]
    if positions is None:
        positions = text_positions(len(x))
    positions = jnp.asarray(positions)
    scores, used, selected = [], [], []
    depth = config["num_hidden_layers"] if layers is None else layers
    for l in range(depth):
        x, s, c, sel = block_of(
            config, layer_params(params["layers"], l), x, positions,
            experts_held,
            None if choices is None else jnp.asarray(choices)[:, l],
            None if selections is None else jnp.asarray(selections)[:, l],
            breakage, block)
        scores.append(s)
        used.append(c)
        selected.append(sel)
    return head(config, params, x), jnp.stack(scores, axis=1), \
        jnp.stack(used, axis=1), jnp.stack(selected, axis=1)
