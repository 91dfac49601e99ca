"""Bytes a decode step and FLOPs a prefill of the latent-attention decoder
with a shortcut-connected expert layer and zero-compute experts must move
and make, computed from shapes and from what the program counted: what
the algorithm requires, not what an implementation does.

A published layer is TWO latent attentions (each with its own latent
cache slot), TWO dense MLPs, four norm gains, and one router over
``router_outputs`` = the real experts and the ``zero_expert_num`` zero
experts, of which this chip holds ``n_routed_experts`` real ones.

* a DECODE step of a few rows is matrix-vector work, bound by memory.  It
  reads every resident NON-expert weight once, whatever the rows (per
  layer both attentions -- q_a, q_b, kv_a, kv_b absorbed but all of it, o,
  the two latent norms -- both dense MLPs, the four gains, the router's
  matrix and its score-correction bias; the final norm and the head's
  slice); of the routed experts ONLY THOSE HIT (``lm.expert_hits`` over the
  window's decode steps x one expert's 3 x hidden x expert width);
  **nothing for the zero experts** (they hold nothing: a pair routed to one
  is an add of what the step already holds); for each row of the PROGRAM
  (a padded row is computed like a real one) its embedding row and the
  latents it writes, one in each attention's slot; and 1,152 B (576 bf16
  values: the latent and the rotary key) for every key the program's
  masks let a real row attend to, summed over the attentions
  (``lm.keys_attended``).
* a PREFILL is matrix-matrix work, bound by compute.  Its least FLOPs: the
  products with a non-expert weight over every position THE PROGRAM
  COUNTED (``lm.prefill_positions``: 2 a value a position; the latent's
  expansion to every head's keys and values is one of them), the experts
  over the pairs it routed to experts held here
  (``lm.expert_pairs_local_prefill``), attention over the query-key pairs
  its masks let through, summed over the attentions
  (``lm.keys_attended_prefill``): the scores of ``H`` heads of ``d_nope +
  d_rope`` and their values' ``d_v``, 2 FLOPs each; the head for one
  position a row.  **Nothing for the zero experts** here either.

LongCat-Flash-Omni's share (4 layers, 16 of 512 experts, 16,384 rows),
bf16: 2,656,166,912 non-expert values a step = 5.31 GB (the layers' 5.11
and the head's 0.20), plus 75.5 MB an expert hit and 1,152 B a key.
"""

from __future__ import annotations

from .lm_bytes import BYTES_PER_VALUE

COUNTERS = ("lm.prefill_positions", "lm.keys_attended",
            "lm.keys_attended_prefill", "lm.expert_hits",
            "lm.expert_pairs_local", "lm.expert_pairs_zero",
            "lm.expert_pairs", "lm.expert_pairs_local_prefill",
            "lm.expert_pairs_zero_prefill")


def attention_params(lm: dict) -> int:
    """One latent attention: its five matrices and two latent norms."""
    d, H = lm["hidden_size"], lm["num_attention_heads"]
    rq, rkv = lm["q_lora_rank"], lm["kv_lora_rank"]
    dn, dr, dv = (lm["qk_nope_head_dim"], lm["qk_rope_head_dim"],
                  lm["v_head_dim"])
    return d * rq + rq + rq * H * (dn + dr) + d * (rkv + dr) + rkv \
        + rkv * H * (dn + dv) + H * dv * d


def dense_mlp_params(lm: dict) -> int:
    return 3 * lm["hidden_size"] * lm["ffn_hidden_size"]


def expert_params(lm: dict) -> int:
    return 3 * lm["hidden_size"] * lm["expert_ffn_hidden_size"]


def attentions(lm: dict) -> int:
    """Attentions held (cache slots, dense MLPs): two a layer."""
    return 2 * lm["num_layers"]


def latent_bytes(lm: dict) -> int:
    """What one position holds in one attention's cache slot."""
    return BYTES_PER_VALUE * (lm["kv_lora_rank"] + lm["qk_rope_head_dim"])


def layer_matrices(lm: dict) -> int:
    """The non-expert MATRICES of every layer held (with the latent norms'
    gains, which `attention_params` counts): what a position of a prefill
    meets and a decode step reads."""
    return lm["num_layers"] * (
        2 * (attention_params(lm) + dense_mlp_params(lm))
        + lm["hidden_size"] * lm["router_outputs"])


def resident_params(lm: dict) -> int:
    """Every non-expert weight a decode step reads: the layers' matrices,
    four gains of the width and the router's bias a layer, the final norm,
    the head's slice."""
    d = lm["hidden_size"]
    return layer_matrices(lm) \
        + lm["num_layers"] * (4 * d + lm["router_outputs"]) \
        + d + d * lm["vocab_size"]


def decode_bytes_per_step(lm: dict, rows: float = 1.0, keys: float = 0.0,
                          hits: float = 0.0) -> float:
    """Least bytes of one decode step of a program of ``rows`` rows whose
    real rows attend, together, to ``keys`` keys over all attentions and
    whose routing hit ``hits`` experts held here over all layers."""
    per_row = BYTES_PER_VALUE * lm["hidden_size"] \
        + attentions(lm) * latent_bytes(lm)
    return BYTES_PER_VALUE * (resident_params(lm)
                              + hits * expert_params(lm)) \
        + rows * per_row + keys * latent_bytes(lm)


def pair_flops(lm: dict) -> float:
    """One query against one key in one attention: ``H`` heads' score and
    their weighted value."""
    return 2.0 * lm["num_attention_heads"] * (
        lm["qk_nope_head_dim"] + lm["qk_rope_head_dim"] + lm["v_head_dim"])


def prefill_flops(lm: dict, positions: float, rows: float, keys: float,
                  local_pairs: float) -> float:
    """Least FLOPs of a prefill that computed ``positions`` positions in
    ``rows`` rows, attended to ``keys`` query-key pairs over all
    attentions and routed ``local_pairs`` token-expert pairs to experts
    held here."""
    return 2.0 * layer_matrices(lm) * positions \
        + 2.0 * expert_params(lm) * local_pairs + pair_flops(lm) * keys \
        + 2.0 * lm["hidden_size"] * lm["vocab_size"] * rows


def counted(ctx) -> dict | None:
    """What the program counted, a mean EXECUTION of the window's, under
    the counters' names less ``lm.``.  None where the program counts no
    pairs to zero experts (every family but this one, and the parent)."""
    counters = ctx.metrics_window["pipeline"]["counters"]
    executions = counters.get("lm.executions")
    if not executions or "lm.expert_pairs_zero" not in counters \
            or "lm.prefill_positions" not in counters:
        return None
    return {name[3:]: counters.get(name, 0) / executions
            for name in COUNTERS}
