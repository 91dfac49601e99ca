"""What every language-model family's served program shares: the decode
driver, the sampler, the writer of a shared prefix and the jitted closure.

A family (`registry.LM_FAMILIES`: ``looplm``, ``mla_moe``, ``swa_moe``,
``ssm_hybrid``, ``dsa_moe``, ``sambay``, ``mla_scmoe``) supplies what
differs, as two closures over its own arguments: ``prefill()``, the prompt
through its blocks (or, where the layers behind a shared cache own no
state, through half of them: only the logits behind the last prompt id are
asked for), and ``step(token, i, state)``, one new position; its state (a
cache, a latent, a ring beside a cache, a recurrence beside a cache, an
index-key cache beside a cache, recurrences and rings in front of one
cache that several layers read, two latent slots a layer) is its own and
opaque here.  `generate` is the loop around them, written once, so a
mechanism of the loop (a chunk of steps, a row that joins, a state slot)
is made in one place.  A family whose state behind a prompt's first ids
can stand for them adds a ``from_prefix`` hook over `write_at_offsets` and
`own_entries`.

This module imports ``jax`` and nothing of the families; they import it.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp


def draw(key, logits, temperature, i):
    """Token ``i`` of one row from its ``logits [V]``: greedy where its
    ``temperature`` is 0, else sampled at that temperature from the row's
    ``key`` folded with ``i``."""
    drawn = jax.random.categorical(
        jax.random.fold_in(key, i),
        logits / jnp.maximum(temperature, 1e-6))
    return jnp.where(temperature > 0, drawn,
                     jnp.argmax(logits)).astype(jnp.int32)


def generate(scope: str, rows: int, prefill: Callable, step: Callable,
             max_new_tokens: int, seed, temperature
             ) -> Tuple[jax.Array, jax.Array, Any, Any, Any]:
    """A prefill, then ``max_new_tokens`` decode steps, for ``rows`` rows
    under ``jax.named_scope(scope)``.  ``seed`` and ``temperature`` are
    ``[rows]``, or scalars where every row has the same.

    ``prefill()`` (it opens its own ``prefill`` scope) -> ``(logits
    [B, V]`` behind every row's last prompt id, ``chosen``, ``state``,
    ``counts``, ``kept)``; ``step(token [B], i, state)`` -> ``(logits,
    chosen, state, now)`` behind that token, the ``i``-th new one.
    ``chosen`` is a tuple of arrays ``[B, ...]``, what the blocks recorded
    where those logits were computed; ``counts`` a tuple to which every
    step's ``now`` is added; ``kept`` whatever of the prefill the caller
    wants back.  Any of the three may be empty.

    Token ``i`` of a row is drawn (`draw`) from the logits before it, so
    record ``i`` is what stood beside the logits token ``i`` was drawn
    from: the prefill's for ``i = 0``.  Returns the new ids ``[B, N]``,
    those float32 logits ``[B, N, V]``, the records each ``[B, N, ...]``,
    the counts summed and ``kept``."""
    seed, temperature = (jnp.broadcast_to(a, (rows,))
                         for a in (seed, temperature))
    keys = jax.vmap(jax.random.PRNGKey)(seed)

    with jax.named_scope(scope):
        logits, chosen, state, counts, kept = prefill()

        def one(carry, i):
            logits, chosen, state, counts = carry
            with jax.named_scope("sample"):
                token = jax.vmap(draw, (0, 0, 0, None))(
                    keys, logits, temperature, i)
            nxt, nxt_chosen, state, now = step(token, i, state)
            return (nxt, nxt_chosen, state,
                    tuple(a + b for a, b in zip(counts, now))), \
                (token, logits, *chosen)

        with jax.named_scope("decode"):
            (*_, counts), (tokens, logits, *chosen) = jax.lax.scan(
                one, (logits, chosen, state, counts),
                jnp.arange(max_new_tokens))
    return (tokens.swapaxes(0, 1), logits.swapaxes(0, 1),
            tuple(c.swapaxes(0, 1) for c in chosen), counts, kept)


def make_program(generate: Callable):
    """The jitted program every family serves, named ``lm_generate``
    (``jit_lm_generate`` in a device trace) whatever the family and its
    configuration: ``generate(params, prompt_ids, prompt_len, seed,
    temperature)`` and, where the family takes a snapshot
    (``make_prefix_program``'s) as a sixth argument, ``prompt_ids`` holds
    what follows the prefix."""

    def lm_generate(params, prompt_ids, prompt_len, seed, temperature,
                    *prefix):
        return generate(params, prompt_ids, prompt_len, seed, temperature,
                        *prefix)

    return jax.jit(lm_generate)


# --- a prefix shared between requests ----------------------------------------
#
# A SNAPSHOT is what a prompt's first K ids leave behind in a family's
# state, for one row and with no axis of rows: a dict whose ``keys`` are
# ``[L, K, ...]``.  Rows whose prompts start with those ids start from
# copies of it, its positional parts written at each row's own offset
# (the buffer is laid out ``padding | prefix | row's own ids``), and
# prefill their own suffix only.

def prefix_length(snapshot) -> int:
    """The positions a snapshot stands for; 0 for none."""
    return 0 if snapshot is None else snapshot["keys"].shape[1]


def write_at_offsets(buffer, block, first, rows: int = 1):
    """``buffer`` (its axis ``rows`` the rows, the next their positions:
    a cache ``[L, B, T, ...]``, records ``[B, T, ...]`` with ``rows=0``)
    with ``block`` (no axis of rows; or a function of ``b`` that gives row
    ``b``'s) written into every row ``b`` from its own position
    ``first[b]`` on, for a buffer of any width behind the positions."""
    at = (0,) * rows
    tail = (0,) * (buffer.ndim - rows - 2)
    for b in range(buffer.shape[rows]):
        piece = block(b) if callable(block) else block
        buffer = jax.lax.dynamic_update_slice(
            buffer, jnp.expand_dims(piece, rows).astype(buffer.dtype),
            (*at, b, first[b], *tail))
    return buffer


def own_entries(own, new, cache, l, at):
    """``new [B, N, ...]``, to be written into layer ``l`` of ``cache`` at
    index ``at``, with what the cache HOLDS there wherever a position is
    not a row's ``own [B, N]``: behind a prefix a row's padded positions
    lie where the end of its prefix's entries stands."""
    tail = (0,) * (new.ndim - 2)
    held = jax.lax.dynamic_slice(cache, (l, 0, at, *tail),
                                 (1, *new.shape))[0]
    return jnp.where(jnp.expand_dims(own, tuple(range(2, new.ndim))), new,
                     held.astype(new.dtype))
