"""Bytes one decode step of the latent-attention decoder with routed
experts must move, computed from shapes and from what the program
counted of its routing: what the algorithm requires, not what an
implementation reads.

A decode step of a few rows is matrix-vector work, bound by memory.  With
``L`` blocks held (``num_hidden_layers``), ``dense_layers_held`` of them
dense and the rest expert blocks of which this chip holds
``n_routed_experts`` experts each, a step reads

* every resident NON-expert weight once, whatever the rows: per block
  the latent attention (q_a, q_a's norm, q_b, kv_a, the latent's norm,
  kv_b -- absorbed, but all of it -- and o) and four norm gains; the
  dense blocks' gated MLP (3 x hidden x intermediate); per expert block
  the router (hidden x ``router_outputs``: all 256, the published width)
  and the shared expert; the final norm and the head's slice
  (hidden x vocabulary held);
* for each row of the program (a padded row is read like a real one) its
  embedding row and the LATENT cache: 576 values of each position it
  attends to in each block, and it writes its own;
* of the routed experts ONLY THOSE HIT: ``hits`` experts (distinct local
  experts with at least one pair, summed over the expert blocks of a
  step: the program's window counter ``lm.expert_hits`` over its decode
  steps) x one expert's 3 x hidden x moe_intermediate.  A program that
  streams all the experts it holds reads more and shows a lower share.

openPangu-Ultra-MoE's share (5 blocks, 16 of 256 experts, 19,200 rows),
bf16: 1.752 B non-expert values = 3.50 GB, plus 94.4 MB an expert hit.
"""

from __future__ import annotations

from .lm_bytes import BYTES_PER_VALUE, served


def attention_params(lm: dict) -> int:
    d, H = lm["hidden_size"], lm["num_attention_heads"]
    rq, rkv = lm["q_lora_rank"], lm["kv_lora_rank"]
    dn, dr, dv = (lm["qk_nope_head_dim"], lm["qk_rope_head_dim"],
                  lm["v_head_dim"])
    return d * rq + rq + rq * H * (dn + dr) + d * (rkv + dr) + rkv \
        + rkv * H * (dn + dv) + H * dv * d


def expert_params(lm: dict) -> int:
    return 3 * lm["hidden_size"] * lm["moe_intermediate_size"]


def latent_values_per_position(lm: dict) -> int:
    """What the cache holds of one position over all blocks held."""
    return lm["num_hidden_layers"] * (lm["kv_lora_rank"]
                                      + lm["qk_rope_head_dim"])


def resident_params(lm: dict) -> int:
    """Every non-expert weight a decode step reads."""
    d = lm["hidden_size"]
    dense = lm["dense_layers_held"]
    moe = lm["num_hidden_layers"] - dense
    block = attention_params(lm) + 4 * d
    return dense * (block + 3 * d * lm["intermediate_size"]) \
        + moe * (block + d * lm["router_outputs"]
                 + lm["n_shared_experts"] * expert_params(lm)) \
        + d + d * lm["vocab_size"]


def decode_bytes_per_step(lm: dict, cached_positions: float = 0.0,
                          rows: float = 1.0, hits: float = 0.0) -> float:
    """Least bytes of one decode step of a program of ``rows`` rows, each
    attending to ``cached_positions`` earlier positions, whose routing
    hit ``hits`` local experts over its expert blocks."""
    per_row = lm["hidden_size"] \
        + latent_values_per_position(lm) * (cached_positions + 1)
    return BYTES_PER_VALUE * (resident_params(lm) + rows * per_row
                              + hits * expert_params(lm))


def routing(ctx) -> dict | None:
    """What the window's decode steps routed, per step: ``hits`` (distinct
    local experts, over the expert blocks), ``pairs`` and ``local``
    (token-expert pairs of the real rows, and those routed to experts
    held here), and ``dropped`` in all.  None where the program counts no
    routing (a model without experts, or a program from before it did)."""
    counters = ctx.metrics_window["pipeline"]["counters"]
    serves = served(ctx)
    if serves is None or "lm.expert_hits" not in counters \
            or not serves["steps"]:
        return None
    steps = counters["lm.executions"] * serves["steps"]
    return {"hits": counters["lm.expert_hits"] / steps,
            "pairs": counters.get("lm.expert_pairs", 0) / steps,
            "local": counters.get("lm.expert_pairs_local", 0) / steps,
            "dropped": counters.get("lm.expert_pairs_dropped", 0)}
