"""Bytes a decode step and FLOPs a prefill of the decoder of state-space
(Mamba-2) and attention layers must move and make, computed from shapes
and from what the program counted: what the algorithm requires, not what
an implementation does.

With ``L_m`` Mamba blocks and ``L_a`` attention blocks (``layer_types``),
one gated MLP shape behind both, a TIED embedding:

* a DECODE step of a few rows is matrix-vector work, bound by memory.  It
  reads every resident weight once, whatever the rows: per Mamba block
  the mixer (``in_proj`` ``d x (2 d_inner + 2 n + heads)``, the
  convolution's taps and bias, ``dt_bias``, ``A_log``, ``D``, the gated
  norm's gain, ``out_proj`` ``d_inner x d``), per attention block q, k,
  v, o, per block the MLP (``d x 2 f`` and ``f x d``) and two norm gains;
  the final norm; the embedding ONCE (it is the head).  For each row of
  the PROGRAM (a padded row is computed like a real one) the recurrent
  state and the convolution tail of every Mamba layer READ AND WRITTEN at
  their stored width (float32 ``heads x d_head x n``; bf16 ``(taps - 1)
  x (d_inner + 2 n)``), its embedding row, and the key and value it
  writes in each attention layer; and 2 KiB (8 heads of 64, bf16, key and
  value) for every key the program's masks let a row attend to
  (``lm.keys_attended_full``, summed over the attention layers).
* a PREFILL is matrix-matrix work, bound by compute.  Its least FLOPs:
  the products with a weight over every position the program computed
  (``lm.prefill_positions``: 2 a value a position; NOT the
  configuration's ``prompt_tokens``, so that a prefix served from a
  snapshot cannot read as work done); the recurrence in its SEQUENTIAL
  form, ``4 d_head n`` a head a position (the state's update and its
  read: what a fused scan would make; the chunked form makes more, and
  that is the implementation's); attention over the keys each query MAY
  SEE (the triangle over a row's real positions: ``4 H D`` a query and
  key); the head for one position a row.

granite-4.0-h-micro, bf16: 3,191,396,096 values = 6.38 GB, plus 152.9 MB
a program row a step (its state read and written) and 2 KiB a key.
"""

from __future__ import annotations

from .lm_bytes import BYTES_PER_VALUE
from .lm_swa_moe_bytes import attention_params, key_bytes  # noqa: F401

MAMBA = "mamba"
STATE_BYTES_PER_VALUE = 4       # the recurrent state is float32


def blocks(lm: dict) -> tuple:
    """``(Mamba blocks, attention blocks)``."""
    mamba = sum(kind == MAMBA for kind in lm["layer_types"])
    return mamba, len(lm["layer_types"]) - mamba


def d_inner(lm: dict) -> int:
    return lm["mamba_n_heads"] * lm["mamba_d_head"]


def conv_channels(lm: dict) -> int:
    return d_inner(lm) + 2 * lm["mamba_n_groups"] * lm["mamba_d_state"]


def mixer_matrices(lm: dict) -> int:
    """``in_proj`` and ``out_proj``: what a position of a prefill meets
    in a Mamba mixer."""
    d = lm["hidden_size"]
    return d * (d_inner(lm) + conv_channels(lm) + lm["mamba_n_heads"]) \
        + d_inner(lm) * d


def mixer_params(lm: dict) -> int:
    """Every weight of a Mamba mixer: the two matrices, the taps and the
    bias of the convolution, ``dt_bias``, ``A_log``, ``D``, the gated
    norm's gain."""
    return mixer_matrices(lm) \
        + (lm["mamba_d_conv"] + 1) * conv_channels(lm) \
        + 3 * lm["mamba_n_heads"] + d_inner(lm)


def mlp_params(lm: dict) -> int:
    return 3 * lm["hidden_size"] * lm["shared_intermediate_size"]


def block_matrices(lm: dict) -> int:
    """The MATRICES of every block: what a position of a prefill meets
    (2 FLOPs a value)."""
    mamba, attention = blocks(lm)
    return mamba * mixer_matrices(lm) + attention * attention_params(lm) \
        + (mamba + attention) * mlp_params(lm)


def resident_params(lm: dict) -> int:
    """Every weight a decode step reads: the blocks' and their norm
    gains, the final norm, the tied embedding once."""
    mamba, attention = blocks(lm)
    d = lm["hidden_size"]
    return mamba * mixer_params(lm) + attention * attention_params(lm) \
        + (mamba + attention) * (mlp_params(lm) + 2 * d) \
        + d + d * lm["vocab_size"]


def state_bytes_per_row(lm: dict) -> int:
    """The recurrent state and the convolution tails of one row, over the
    Mamba layers, as stored."""
    mamba, _ = blocks(lm)
    return mamba * (d_inner(lm) * lm["mamba_d_state"] * STATE_BYTES_PER_VALUE
                    + (lm["mamba_d_conv"] - 1) * conv_channels(lm)
                    * BYTES_PER_VALUE)


def decode_bytes_per_step(lm: dict, rows: float = 1.0,
                          keys: float = 0.0) -> float:
    """Least bytes of one decode step of a program of ``rows`` rows whose
    rows attend, together, to ``keys`` cached keys over the attention
    layers."""
    _, attention = blocks(lm)
    per_row = 2 * state_bytes_per_row(lm) \
        + BYTES_PER_VALUE * lm["hidden_size"] + attention * key_bytes(lm)
    return BYTES_PER_VALUE * resident_params(lm) + rows * per_row \
        + keys * key_bytes(lm)


def prefill_flops(lm: dict, positions: float, rows: float, real: float
                  ) -> float:
    """Least FLOPs of a prefill that computed ``positions`` positions in
    all, over ``rows`` rows of ``real`` real ids each."""
    mamba, attention = blocks(lm)
    heads = lm["num_attention_heads"] * lm["head_dim"]
    recurrence = 4.0 * d_inner(lm) * lm["mamba_d_state"]
    return (2.0 * block_matrices(lm) + mamba * recurrence) * positions \
        + 4.0 * heads * attention * rows * real * (real + 1) / 2.0 \
        + 2.0 * lm["hidden_size"] * lm["vocab_size"] * rows


def counted(ctx) -> dict | None:
    """What the program counted of an execution, mean over the window's:
    the positions its prefill computed, the keys its real rows attended
    to in ALL its decode steps.  None where the program counts no such
    thing (every family but this one)."""
    counters = ctx.metrics_window["pipeline"]["counters"]
    executions = counters.get("lm.executions")
    if not executions or "lm.prefill_positions" not in counters \
            or "lm.state_steps" not in counters:
        return None
    return {"prefill_positions": counters["lm.prefill_positions"]
            / executions,
            "keys": counters.get("lm.keys_attended_full", 0) / executions,
            "state_steps": counters["lm.state_steps"] / executions}
