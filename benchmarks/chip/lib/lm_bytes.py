"""Bytes one decoded token of the looped language model must move,
computed from shapes: what the algorithm requires, not what a compiler
emitted.  And what the language-model readers share.

A decode step at batch 1 is matrix-vector work, bound by memory: every
weight is read once per use and nothing else comes near it.  With ``R``
loops over ``L`` layers (``total_ut_steps``, ``num_hidden_layers``), a
token reads

* the layers' weights ``R`` times: per layer the q/k/v/o projections
  (4 x hidden x heads x head_dim), the gated MLP (3 x hidden x
  intermediate) and four norm gains;
* the final norm's gain and the exit gate, ``R`` times;
* the output head (hidden x vocabulary) and one row of the embedding,
  once;
* the cache: keys and values of the ``cached_positions`` it attends to
  in each of the ``R x L`` slots, and it writes its own entry to each.

Ouro-2.6B, bf16, empty cache: 4 x 48 x 51,388,416 x 2 B + 2048 x 49,152
x 2 B = 19.73 + 0.20 = 19.9 GB.
"""

from __future__ import annotations

import re

from .profile import summary
from .server import BenchFailure

BYTES_PER_VALUE = 2         # bf16: weights and cache


def layer_params(lm: dict) -> int:
    d, f = lm["hidden_size"], lm["intermediate_size"]
    inner = lm["num_attention_heads"] * lm["head_dim"]
    return 4 * d * inner + 3 * d * f + 4 * d


def cache_values_per_position(lm: dict) -> int:
    """Keys and values of one position over all ``R x L`` slots."""
    return lm["total_ut_steps"] * lm["num_hidden_layers"] * 2 \
        * lm["num_key_value_heads"] * lm["head_dim"]


def decode_bytes_per_token(lm: dict, cached_positions: float = 0.0) -> float:
    """Least bytes of one decode step at batch 1 that attends to
    ``cached_positions`` earlier positions."""
    d, loops = lm["hidden_size"], lm["total_ut_steps"]
    weights = loops * (lm["num_hidden_layers"] * layer_params(lm)
                       + d + d + 1)                 # final norm, exit gate
    weights += d * lm["vocab_size"] + d             # head, one embedding row
    cache = cache_values_per_position(lm) * (cached_positions + 1)
    return BYTES_PER_VALUE * (weights + cache)


def per_request(ctx, counter: str) -> float | None:
    """A window counter of the program over the generate executions the
    window's stages count."""
    pipeline = ctx.metrics_window["pipeline"]
    row = pipeline["stages"].get("lm_generate")
    value = pipeline["counters"].get(counter)
    if not row or not row["count"] or value is None:
        return None
    return value / row["count"]


def program_s(ctx) -> float | None:
    """Mean device seconds of one whole execution of the generate
    program in the trace slice.  None with no trace, or where the
    configuration names no such program."""
    if ctx.trace is None or "lm_generate" not in ctx.config["programs"]:
        return None
    prog = ctx.program("lm_generate")
    return prog["total_s"] / prog["count"]


def class_s(ctx, *classes: str) -> float | None:
    """Device seconds per execution of the generate program in kernel
    classes of the program's own trace summary."""
    prof = summary(ctx)
    if prof is None or "lm_generate" not in ctx.config["programs"]:
        return None
    pattern = re.compile(ctx.config["programs"]["lm_generate"])
    rows = [p for name, p in prof["programs"].items() if pattern.search(name)]
    if len(rows) != 1:
        raise BenchFailure(
            f"{len(rows)} programs of the summary match "
            f"{pattern.pattern!r}; it has {sorted(prof['programs'])}")
    return sum(rows[0]["classes"].get(c, 0.0) for c in classes)
