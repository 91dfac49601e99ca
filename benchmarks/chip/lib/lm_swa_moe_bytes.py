"""Bytes a decode step and FLOPs a prefill of the decoder with window
and full attention layers and routed experts must move and make,
computed from shapes and from what the program counted: what the
algorithm requires, not what an implementation does.  And the program's
seconds by PHASE, which its trace summary gives where its scopes carry
one (``prefill``, ``decode``).

With ``L`` blocks held (``num_hidden_layers``), ``dense_layers_held`` of
them dense and the rest expert blocks of which this chip holds
``num_experts`` experts each:

* a DECODE step of a few rows is matrix-vector work, bound by memory.  It
  reads every resident NON-expert weight once, whatever the rows: per
  block the attention (q ``d x H D``, k and v ``d x G D`` each, o
  ``H D x d``: grouped key-value heads) and four norm gains (two of the
  width, two of a head); the dense blocks' gated MLP; per expert block the
  router (``d x router_outputs``: all 128, the published width) and the
  shared expert; the final norm and the head's slice.  For each row of
  the program (a padded row is read like a real one) its embedding row,
  the keys and values it ATTENDS TO (4 KiB each: 8 heads of 128, bf16,
  key and value), as the program counted them from the masks its steps
  applied (``lm.keys_attended_window``: a ring's live slots, 128 a
  sliding layer once it is full; ``lm.keys_attended_full``: a full
  layer's, the row's real prompt and what it has decoded) and the one it
  writes in each block.  Of the routed experts ONLY THOSE HIT
  (``lm.expert_hits``), one expert's ``3 x d x moe_intermediate`` each.
* a PREFILL is matrix-matrix work, bound by compute.  Its least FLOPs:
  the non-expert products over every position of the program's rows
  (2 a weight a position); the experts over the LOCAL pairs the program
  counted (``lm.expert_pairs_local_prefill``: a pair through one
  expert's three matrices), not over every token an expert that was hit;
  attention over the keys each query MAY SEE (the band of a sliding
  layer, the triangle of a full one, over a row's real positions:
  ``4 H D`` a query and key, scores and weighted values); the head for
  one position a row.

K-EXAONE-236B-A23B's share (5 blocks, 16 of 128 experts, 19,200 rows),
bf16: 1.178 B non-expert values = 2.36 GB, plus 75.5 MB an expert hit and
4 KiB a key.
"""

from __future__ import annotations

import re

from .lm_bytes import BYTES_PER_VALUE
from .profile import summary

SLIDING = "sliding_attention"


def attention_params(lm: dict) -> int:
    d, D = lm["hidden_size"], lm["head_dim"]
    H, G = lm["num_attention_heads"], lm["num_key_value_heads"]
    return d * H * D + 2 * d * G * D + H * D * d


def expert_params(lm: dict) -> int:
    return 3 * lm["hidden_size"] * lm["moe_intermediate_size"]


def key_bytes(lm: dict) -> int:
    """A key and its value in one layer's cache."""
    return 2 * lm["num_key_value_heads"] * lm["head_dim"] * BYTES_PER_VALUE


def block_matrices(lm: dict) -> int:
    """The non-expert MATRICES of every block held: what a position of a
    prefill meets (2 FLOPs a value) and a decode step reads."""
    d = lm["hidden_size"]
    dense = lm["dense_layers_held"]
    moe = lm["num_hidden_layers"] - dense
    return lm["num_hidden_layers"] * attention_params(lm) \
        + dense * 3 * d * lm["intermediate_size"] \
        + moe * (d * lm["router_outputs"]
                 + lm["num_shared_experts"] * expert_params(lm))


def resident_params(lm: dict) -> int:
    """Every non-expert weight a decode step reads: the blocks' matrices
    and norm gains, the final norm, the head's slice."""
    d = lm["hidden_size"]
    gains = lm["num_hidden_layers"] * 2 * (d + lm["head_dim"]) + d
    return block_matrices(lm) + gains + d * lm["vocab_size"]


def decode_bytes_per_step(lm: dict, keys: float = 0.0, rows: float = 1.0,
                          hits: float = 0.0) -> float:
    """Least bytes of one decode step of a program of ``rows`` rows, each
    attending to ``keys`` cached keys over all its layers, whose routing
    hit ``hits`` local experts over its expert blocks."""
    per_row = BYTES_PER_VALUE * lm["hidden_size"] \
        + (keys + lm["num_hidden_layers"]) * key_bytes(lm)
    return BYTES_PER_VALUE * (resident_params(lm)
                              + hits * expert_params(lm)) + rows * per_row


def visible_pairs(lm: dict, positions: float) -> float:
    """Query-key pairs a causal prefill of ``positions`` real positions
    may see, over all layers held: the triangle of a full layer, the band
    of a sliding one."""
    n, w = positions, min(float(lm["sliding_window"]), positions)
    triangle = n * (n + 1) / 2.0
    band = w * (w + 1) / 2.0 + (n - w) * w
    sliding = sum(kind == SLIDING for kind in lm["layer_types"])
    return sliding * band + (len(lm["layer_types"]) - sliding) * triangle


def prefill_flops(lm: dict, rows: float, positions: int, real: float,
                  local_pairs: float) -> float:
    """Least FLOPs of the prefill of a program of ``rows`` rows of
    ``positions`` positions, ``real`` of them a row's own ids, whose
    routing sent ``local_pairs`` token-expert pairs to experts held
    here."""
    heads = lm["num_attention_heads"] * lm["head_dim"]
    return 2.0 * block_matrices(lm) * rows * positions \
        + 2.0 * expert_params(lm) * local_pairs \
        + 4.0 * heads * rows * visible_pairs(lm, real) \
        + 2.0 * lm["hidden_size"] * lm["vocab_size"] * rows


def phase_s(ctx, phase: str) -> float | None:
    """Device seconds of one execution of the generate program under the
    scope ``phase`` (leaf operations; the gaps inside the program are
    nobody's).  None with no trace, no summary, or a program whose scopes
    carry no phase (every program from before PR 34, and two of the three
    families since)."""
    prof = summary(ctx)
    if prof is None or "lm_generate" not in ctx.config["programs"]:
        return None
    pattern = re.compile(ctx.config["programs"]["lm_generate"])
    rows = [p for name, p in prof["programs"].items() if pattern.search(name)]
    if len(rows) != 1:
        return None
    return rows[0].get("phases", {}).get(phase)


def attended(ctx, serves: dict) -> float | None:
    """Keys a row attends to in one decode step, over all its layers:
    the program's two window counters over its real rows' steps.  None
    where the program counts none."""
    counters = ctx.metrics_window["pipeline"]["counters"]
    if "lm.keys_attended_window" not in counters or not serves["steps"]:
        return None
    row_steps = counters["lm.rows"] * serves["steps"]
    return (counters["lm.keys_attended_window"]
            + counters.get("lm.keys_attended_full", 0)) / row_steps
