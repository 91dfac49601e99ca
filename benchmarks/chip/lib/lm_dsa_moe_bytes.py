"""Bytes a decode step and FLOPs a prefill of the decoder with a learned
key selection and routed experts must move and make, computed from
shapes and from what the program counted: what the algorithm requires,
not what an implementation does.

With ``L`` blocks held (``num_hidden_layers``), every one an expert block
with all ``num_experts`` experts held, an indexer beside each attention
(``sa_config``: ``H_I`` heads of ``D_I`` over ONE index key a position):

* a DECODE step of a few rows is matrix-vector work, bound by memory.  It
  reads every resident NON-expert weight once, whatever the rows: per
  block the attention (q ``d x H D``, k and v ``d x G D`` each, o
  ``H D x d``), the indexer (``d x H_I D_I``, ``d x D_I``, ``d x H_I``,
  its key norm's gain and bias), the router (``d x num_experts``), two
  block norms and two head norms; the final norm and the WHOLE head.  Of
  the routed experts ONLY THOSE HIT (``lm.expert_hits``), one expert's
  ``3 x d x moe_intermediate`` each.  For each row of the program (a
  padded row is read like a real one) its embedding row, the index keys
  it SCORED (``lm.keys_scored_decode``: every cached one it may see, 128
  B each a block), the keys and values it ATTENDED to
  (``lm.keys_attended``: the ``topk`` it selected, 2 KiB each a block --
  not the cache's length), and the key, value and index key it writes in
  each block.
* a PREFILL is matrix-matrix work, bound by compute.  Its least FLOPs:
  the non-expert products over every position THE PROGRAM COUNTED
  (``lm.prefill_positions``; NOT the configuration's ``prompt_tokens``:
  2 a weight a position); the experts over the LOCAL pairs the program
  counted (``lm.expert_pairs_local_prefill``: a pair through one
  expert's three matrices); the index scores over the query-key pairs
  the program scored (``lm.keys_scored_prefill``: the causal triangle of a
  row's real positions less the queries that see no more than ``topk``
  and need no score: ``2 H_I D_I`` a pair, and ``2 H_I`` for the ReLU's
  weighted sum); attention over the keys SELECTED
  (``lm.keys_attended_prefill``: ``4 H D`` a query and key), not the
  triangle; the head for one position a row.

Keye-VL-2.0-30B-A3B's stage (6 blocks, 128 of 128 experts, 151,936
rows), bf16: 0.440 B non-expert values = 0.88 GB, plus 9.44 MB an expert
hit, 128 B an index key scored and 2 KiB a key attended to.
"""

from __future__ import annotations

from .lm_bytes import BYTES_PER_VALUE
from .lm_swa_moe_bytes import attention_params, expert_params, \
    key_bytes  # noqa: F401

DECODE_COUNTERS = ("lm.keys_scored_decode", "lm.keys_attended",
                   "lm.expert_hits")
PREFILL_COUNTERS = ("lm.prefill_positions", "lm.keys_scored_prefill",
                    "lm.keys_attended_prefill",
                    "lm.expert_pairs_local_prefill")


def indexer_matrices(lm: dict) -> int:
    sa, d = lm["sa_config"], lm["hidden_size"]
    return d * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def index_key_bytes(lm: dict) -> int:
    """The one index key of a position in one block's cache."""
    return lm["sa_config"]["indexer_head_dim"] * BYTES_PER_VALUE


def block_matrices(lm: dict) -> int:
    """The non-expert MATRICES of every block held: what a position of a
    prefill meets (2 FLOPs a value) and a decode step reads."""
    return lm["num_hidden_layers"] * (
        attention_params(lm) + indexer_matrices(lm)
        + lm["hidden_size"] * lm["num_experts"])


def resident_params(lm: dict) -> int:
    """Every non-expert weight a decode step reads: the blocks' matrices
    and gains (two of the width, two of a head, the index key norm's gain
    and bias), the final norm, the whole head."""
    d = lm["hidden_size"]
    gains = lm["num_hidden_layers"] * 2 * (
        d + lm["head_dim"] + lm["sa_config"]["indexer_head_dim"]) + d
    return block_matrices(lm) + gains + d * lm["vocab_size"]


def decode_bytes_per_step(lm: dict, rows: float = 1.0, scored: float = 0.0,
                          attended: float = 0.0, hits: float = 0.0
                          ) -> float:
    """Least bytes of one decode step of a program of ``rows`` rows that
    together score ``scored`` index keys and attend to ``attended`` keys
    over all blocks, and whose routing hit ``hits`` experts over them."""
    per_row = BYTES_PER_VALUE * lm["hidden_size"] \
        + lm["num_hidden_layers"] * (key_bytes(lm) + index_key_bytes(lm))
    return BYTES_PER_VALUE * (resident_params(lm)
                              + hits * expert_params(lm)) \
        + rows * per_row + scored * index_key_bytes(lm) \
        + attended * key_bytes(lm)


def prefill_flops(lm: dict, positions: float, rows: float, scored: float,
                  attended: float, local_pairs: float) -> float:
    """Least FLOPs of a prefill that computed ``positions`` positions in
    ``rows`` rows, scored ``scored`` and attended to ``attended``
    query-key pairs over all blocks, and routed ``local_pairs``
    token-expert pairs."""
    sa = lm["sa_config"]
    heads = lm["num_attention_heads"] * lm["head_dim"]
    index = 2.0 * sa["indexer_num_heads"] * (sa["indexer_head_dim"] + 1)
    return 2.0 * block_matrices(lm) * positions \
        + 2.0 * expert_params(lm) * local_pairs \
        + index * scored + 4.0 * heads * attended \
        + 2.0 * lm["hidden_size"] * lm["vocab_size"] * rows


def counted(ctx, names=DECODE_COUNTERS + PREFILL_COUNTERS) -> dict | None:
    """What the program counted, a mean EXECUTION of the window's, under
    the counters' names less ``lm.``.  None where the program counts no
    index keys (every family but this one)."""
    counters = ctx.metrics_window["pipeline"]["counters"]
    executions = counters.get("lm.executions")
    if not executions or "lm.keys_scored_decode" not in counters:
        return None
    return {name[3:]: counters.get(name, 0) / executions for name in names}
