"""Bytes a decode step and FLOPs a prefill of the decoder-hybrid-decoder
must move and make, computed from shapes and from what the program
counted: what the algorithm requires, not what an implementation does.

With ``n`` layers: ``n/4 + 1`` Mamba-1 layers (the last the memory
layer), ``n/4`` window layers, one full layer, ``n/4 - 1`` gated memory
units and as many cross layers; one MLP shape behind every mixer, a TIED
embedding.

* a DECODE step of a few rows is matrix-vector work, bound by memory.  It
  reads every resident weight once, whatever the rows, the embedding ONCE
  (it is the head).  For each row of the PROGRAM (a padded row is
  computed like a real one) the state and the convolution tail of every
  Mamba layer READ AND WRITTEN at their stored width (float32 ``d_inner
  x N``; bf16 ``(taps - 1) x d_inner``), its embedding row, and the key
  and value it writes in each window layer's ring and in the one cache;
  and ``key_bytes`` (5,120 B: 20 heads of 64, bf16, key and value) for
  every key the program's masks let a real row attend to, ONCE FOR EACH
  LAYER THAT READS IT (``lm.keys_attended_ring`` over the window layers,
  ``lm.keys_attended_full`` over the full layer and the cross layers: one
  cache, read by all of them).
* a PREFILL is matrix-matrix work, bound by compute.  Its least FLOPs:
  the products with a weight of the FRONT (the Mamba and window layers
  and the full layer's key / value projection) over every position the
  program computed there (``lm.prefill_positions``: 2 a value a
  position), and of the rest (the full layer's query and output
  projections and MLP, every gated memory unit and cross layer) over the
  positions it computed THERE (``lm.cross_positions``: one a row); the
  recurrence in its sequential form, ``6 d_inner N`` a position a Mamba
  layer (the decay's product, the input's two, the update's add, the
  read's multiply and add; the ``exp`` not counted); the BAND of each
  window layer over a row's real positions (``min(W, p + 1)`` keys for
  the query at ``p``) and the last position's row of the one cache for
  each of its readers, ``4 H D`` a query and key (the scores of ``H``
  heads of ``D``, and ``H / 2`` differential heads' ``2 D``-wide value
  product: the two maps of a pair subtracted BEFORE they meet the value,
  the least); the head for one position a row.

Phi-4-mini-flash-reasoning, bf16: 3,852,562,944 values = 7.71 GB, plus
6.5 MB a program row a step (its states read and written) and 5,120 B a
key and reader.
"""

from __future__ import annotations

from .lm_bytes import BYTES_PER_VALUE

STATE_BYTES_PER_VALUE = 4       # the recurrent state is float32


def layers(lm: dict) -> dict:
    """How many layers of each kind."""
    quarter = lm["num_hidden_layers"] // 4
    return {"mamba": quarter + 1, "swa": quarter, "full": 1,
            "gmu": quarter - 1, "cross": quarter - 1}


def d_inner(lm: dict) -> int:
    return lm["mamba_expand"] * lm["hidden_size"]


def inner(lm: dict) -> int:
    """Values of a position's queries (and of its output)."""
    return lm["hidden_size"]


def kv_values(lm: dict) -> int:
    """Values of a position's keys and values together."""
    return 2 * lm["num_key_value_heads"] \
        * (lm["hidden_size"] // lm["num_attention_heads"])


def key_bytes(lm: dict) -> int:
    return BYTES_PER_VALUE * kv_values(lm)


def mlp_matrices(lm: dict) -> int:
    return 3 * lm["hidden_size"] * lm["intermediate_size"]


def mamba_matrices(lm: dict) -> int:
    d, C = lm["hidden_size"], d_inner(lm)
    R, N = lm["mamba_dt_rank"], lm["mamba_d_state"]
    return d * 2 * C + C * (R + 2 * N) + R * C + C * d


def front_matrices(lm: dict) -> int:
    """The matrices a position of the prompt meets: the Mamba and window
    layers whole, and the full layer's key / value projection."""
    d, count = lm["hidden_size"], layers(lm)
    window = d * (inner(lm) + kv_values(lm)) + inner(lm) * d
    return count["mamba"] * (mamba_matrices(lm) + mlp_matrices(lm)) \
        + count["swa"] * (window + mlp_matrices(lm)) + d * kv_values(lm)


def back_matrices(lm: dict) -> int:
    """The matrices a row's LAST position meets besides: the full layer's
    query and output projections and its MLP, the gated memory units and
    the cross layers."""
    d, count = lm["hidden_size"], layers(lm)
    queries = 2 * d * inner(lm)
    return queries + mlp_matrices(lm) \
        + count["gmu"] * (2 * d * d_inner(lm) + mlp_matrices(lm)) \
        + count["cross"] * (queries + mlp_matrices(lm))


def resident_params(lm: dict) -> int:
    """Every weight a decode step reads: the matrices, and what is none
    (norm gains and biases, the projections' biases, the taps, ``A_log``,
    ``D``, the lambdas), the tied embedding once."""
    d, C, count = lm["hidden_size"], d_inner(lm), layers(lm)
    D = d // lm["num_attention_heads"]
    n = sum(count.values())
    mamba = (lm["mamba_d_conv"] + 1) * C + C + C * lm["mamba_d_state"] + C
    attention = inner(lm) + kv_values(lm) + d + 4 * D + 2 * D
    cross = inner(lm) + d + 4 * D + 2 * D
    return front_matrices(lm) + back_matrices(lm) + n * 4 * d \
        + count["mamba"] * mamba + (count["swa"] + 1) * attention \
        + count["cross"] * cross + 2 * d + d * lm["vocab_size"]


def state_bytes_per_row(lm: dict) -> int:
    """The recurrent states and the convolution tails of one row, over
    the Mamba layers, as stored."""
    C = d_inner(lm)
    return layers(lm)["mamba"] * C * (
        lm["mamba_d_state"] * STATE_BYTES_PER_VALUE
        + (lm["mamba_d_conv"] - 1) * BYTES_PER_VALUE)


def decode_bytes_per_step(lm: dict, rows: float = 1.0,
                          keys: float = 0.0) -> float:
    """Least bytes of one decode step of a program of ``rows`` rows whose
    real rows attend, together, to ``keys`` keys (a key counted once for
    each layer that reads it)."""
    per_row = 2 * state_bytes_per_row(lm) \
        + BYTES_PER_VALUE * lm["hidden_size"] \
        + (layers(lm)["swa"] + 1) * key_bytes(lm)
    return BYTES_PER_VALUE * resident_params(lm) + rows * per_row \
        + keys * key_bytes(lm)


def band_pairs(window: int, real: float) -> float:
    """Query-key pairs of one row of ``real`` ids in one window layer:
    the query at ``p`` sees ``min(window, p + 1)`` keys."""
    full = max(real - window, 0.0)
    ramp = min(real, window)
    return ramp * (ramp + 1) / 2.0 + full * window


def prefill_flops(lm: dict, positions: float, last: float, rows: float,
                  real: float) -> float:
    """Least FLOPs of a prefill whose front computed ``positions``
    positions in all and whose back ``last``, over ``rows`` rows of
    ``real`` real ids each."""
    count = layers(lm)
    pair = 4.0 * inner(lm)
    recurrence = 6.0 * d_inner(lm) * lm["mamba_d_state"]
    attention = count["swa"] * band_pairs(lm["sliding_window"], real) \
        + (1 + count["cross"]) * real
    return (2.0 * front_matrices(lm) + count["mamba"] * recurrence) \
        * positions + 2.0 * back_matrices(lm) * last \
        + pair * rows * attention \
        + 2.0 * lm["hidden_size"] * lm["vocab_size"] * rows


def counted(ctx) -> dict | None:
    """What the program counted of an execution, mean over the window's:
    the positions its prefill computed in the front and behind it, the
    keys its real rows attended to in ALL its decode steps, a key once
    for each layer that reads it.  None where the program counts no such
    thing (every family but this one)."""
    counters = ctx.metrics_window["pipeline"]["counters"]
    executions = counters.get("lm.executions")
    if not executions or "lm.cross_positions" not in counters \
            or "lm.prefill_positions" not in counters:
        return None
    return {"prefill_positions": counters["lm.prefill_positions"]
            / executions,
            "cross_positions": counters["lm.cross_positions"] / executions,
            "keys": (counters.get("lm.keys_attended_ring", 0)
                     + counters.get("lm.keys_attended_full", 0))
            / executions}
