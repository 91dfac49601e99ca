"""A few rows times a stacked weight, streamed once (Pallas TPU kernel).

The path a language model's decode products take on a TPU when an
execution carries 2 to 8 rows (``models/looplm.py:dense_path`` decides
from the shapes).  ``x [rows, K]`` stays resident in VMEM; each weight
``[K, N]`` is read out of its STACKED leaf ``[L, K, N]`` in place, tile
by tile through a double-buffered ``BlockSpec`` whose index map takes the
layer from a prefetched scalar, so no slice of a leaf is ever
materialised for the call; a tile meets the rows on the MXU
(``jnp.dot``, float32 accumulation) in about half the time its DMA
takes on a v5e, so the call is bound by HBM, as the one-row
multiply-and-reduce fusions XLA writes are.  Same operands, same
accumulation type and same result dtype as ``jnp.dot(x, w[l],
preferred_element_type=float32)``.

Several weights of one shape that meet the same ``x`` (q / k / v;
gate / up) go through ONE call: a launch whose first tile's DMA nothing
hides costs a quarter of an 8 MB product.

A TIED embedding ``[V, K]`` read as the head is the same product with the
weight transposed: `fewrow_dense_t` streams it in blocks of whole rows
(``[tv, K]``: contiguous as it lies) and contracts the second axis of
both operands on the MXU, so no transposed copy of the leaf is made.

The routed experts of a block are the same product one axis more:
`fewrow_grouped` walks ``S`` static SLOTS, slot ``s`` reading expert
``ids[s]`` of layer ``l`` out of leaves ``[L, E, K, N]`` in place (both
prefetched scalars in the weight's index map), so the double buffer
carries ONE stream from an expert's last tile into the next expert's
first.  Only the first ``hits`` slots are live: a dead slot's index maps
stand still on the last live slot's last blocks (the pipeline copies
nothing for a block index that did not change) and its product is skipped.

Blocks: ``block_sizes`` keeps a tile's columns whole (``[tk, N]``: one
contiguous run of the tiled HBM layout) where ``N`` allows and walks
``K``; a wider weight is walked over ``N`` first, ``K`` inside, the
float32 output block resident over ``K``.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what one weight's tile may take of VMEM; every weight of a call has a
# tile in flight and one in use.  Sized on a v5e (flash_attention.py's
# note on VMEM_LIMIT_BYTES holds here too).
TILE_BYTES = 4 * 1024 * 1024
VMEM_LIMIT_BYTES = 48 * 1024 * 1024
LANES = 128


def _largest_divisor(total: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``total`` (a multiple of
    128) and is at most ``cap``; 128 where ``cap`` is smaller still."""
    units = total // LANES
    return LANES * max((u for u in range(1, units + 1)
                        if units % u == 0 and u * LANES <= cap), default=1)


def block_sizes(k: int, n: int, count: int = 1,
                itemsize: int = 2) -> Tuple[int, int]:
    """``(tk, tn)`` for ``count`` weights ``[k, n]`` streamed together:
    tiles of at most ``TILE_BYTES / count`` each, columns whole where a
    128-row tile of them fits, else as wide as fits with 512 rows."""
    budget = TILE_BYTES // count // itemsize          # elements a tile
    if n * LANES <= budget:
        return _largest_divisor(k, budget // n), n
    tk = _largest_divisor(k, 512)
    return tk, _largest_divisor(n, budget // tk)


def _tiles(x_ref, refs, count: int, k_steps: int, k_step):
    """One grid step's products: every weight's tile against the rows.
    ``refs``: ``count`` weight tiles ``[tk, tn]``, then ``count`` float32
    output blocks ``[rows, tn]``, resident over the K steps; ``k_step()``
    gives the step along K."""
    x = x_ref[...]
    for w_ref, o_ref in zip(refs[:count], refs[count:]):
        part = jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
        if k_steps == 1:
            o_ref[...] = part
        else:
            @pl.when(k_step() == 0)
            def _(o_ref=o_ref, part=part):
                o_ref[...] = part

            @pl.when(k_step() > 0)
            def _(o_ref=o_ref, part=part):
                o_ref[...] += part


def _kernel(layer_ref, x_ref, *refs, count: int, k_steps: int):
    """One (N block, K block) grid step of `fewrow_dense`."""
    del layer_ref                                  # the index maps' own
    _tiles(x_ref, refs, count, k_steps, functools.partial(pl.program_id, 1))


def fewrow_dense(x: jax.Array, leaves: Sequence[jax.Array],
                 layer: Optional[jax.Array] = None, *,
                 blocks: Optional[Tuple[int, int]] = None,
                 name: str = "fewrow_dense",
                 interpret: bool = False) -> Tuple[jax.Array, ...]:
    """``x [rows, K]`` times layer ``layer`` of every leaf ``[L, K, N]``
    (all of one shape and of ``x``'s dtype; a leaf ``[K, N]`` is its own
    only layer), each ``[rows, N]`` in float32.  ``K`` and ``N`` are
    multiples of 128; the rows are few (the whole ``x`` is one block).
    ``blocks`` overrides `block_sizes` (tests walk K and N with small
    ones); ``interpret=True`` runs the Pallas interpreter (CPU tests)."""
    leaves = [w if w.ndim == 3 else w[None] for w in leaves]
    count, (_, k, n) = len(leaves), leaves[0].shape
    if x.ndim != 2 or x.shape[1] != k or k % LANES or n % LANES \
            or any(w.shape != leaves[0].shape or w.dtype != x.dtype
                   for w in leaves):
        raise ValueError(
            f"fewrow_dense: {x.dtype}{list(x.shape)} against "
            f"{[f'{w.dtype}{list(w.shape)}' for w in leaves]}: one shape "
            f"[L, K, N] and one dtype, K and N multiples of {LANES}")
    rows = x.shape[0]
    itemsize = jnp.dtype(x.dtype).itemsize
    tk, tn = blocks or block_sizes(k, n, count, itemsize)
    layer = jnp.zeros((1,), jnp.int32) if layer is None \
        else jnp.asarray(layer, jnp.int32).reshape(1)
    weight = pl.BlockSpec((None, tk, tn), lambda j, i, l: (l[0], i, j))
    out = pl.BlockSpec((rows, tn), lambda j, i, l: (0, j))
    return tuple(pl.pallas_call(
        functools.partial(_kernel, count=count, k_steps=k // tk),
        out_shape=[jax.ShapeDtypeStruct((rows, n), jnp.float32)] * count,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tn, k // tk),
            in_specs=[pl.BlockSpec((rows, tk), lambda j, i, l: (0, i)),
                      *[weight] * count],
            out_specs=[out] * count),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * count * rows * k * n, transcendentals=0,
            bytes_accessed=count * (k * n * itemsize + rows * n * 4)
            + rows * k * itemsize * (n // tn)),
        interpret=interpret,
        name=name,
    )(layer, x, *leaves))


def fewrow_grouped(x: jax.Array, leaves: Sequence[jax.Array], layer,
                   ids: jax.Array, hits, *,
                   blocks: Optional[Tuple[int, int]] = None,
                   name: str = "fewrow_grouped",
                   interpret: bool = False) -> Tuple[jax.Array, ...]:
    """For each of the first ``hits`` of ``S = len(ids)`` slots, ``x``
    (``[rows, K]``, the same for every slot, or ``[S, rows, K]``, a slot
    its own) times expert ``ids[s]`` of layer ``layer`` of every leaf
    ``[L, E, K, N]`` (all of one shape and of ``x``'s dtype): each
    ``[S, rows, N]`` in float32, what ``jnp.dot(x_s, leaf[layer, ids[s]],
    preferred_element_type=float32)`` gives.  A slot behind ``hits`` reads
    no weight (``ids[s]`` is not looked at) and writes nothing: its part
    of the result is whatever the buffer held, and the caller masks it.
    ``K``, ``N``, ``blocks`` and ``interpret`` as `fewrow_dense`'s."""
    count, (_, _, k, n) = len(leaves), leaves[0].shape
    slots, shared = ids.shape[0], x.ndim == 2
    if x.ndim not in (2, 3) or x.shape[-1] != k or k % LANES or n % LANES \
            or not (shared or x.shape[0] == slots) \
            or any(w.shape != leaves[0].shape or w.dtype != x.dtype
                   for w in leaves):
        raise ValueError(
            f"fewrow_grouped: {x.dtype}{list(x.shape)} over {slots} slots "
            f"against {[f'{w.dtype}{list(w.shape)}' for w in leaves]}: "
            f"[rows, K] or [slots, rows, K], one shape [L, E, K, N] and "
            f"one dtype, K and N multiples of {LANES}")
    rows = x.shape[-2]
    itemsize = jnp.dtype(x.dtype).itemsize
    tk, tn = blocks or block_sizes(k, n, count, itemsize)
    k_steps, n_steps = k // tk, n // tn
    meta = jnp.asarray([layer, hits], jnp.int32)

    def at(s, j, i, meta):
        """The slot, N block and K block a grid step reads and writes:
        its own while the slot is live, behind that the last live
        step's, so that nothing is copied in or out."""
        live = s < meta[1]
        return (jnp.where(live, s, jnp.maximum(meta[1] - 1, 0)),
                jnp.where(live, j, n_steps - 1),
                jnp.where(live, i, k_steps - 1))

    def x_map(s, j, i, ids, meta):
        s, _, i = at(s, j, i, meta)
        return (0, i) if shared else (s, 0, i)

    def w_map(s, j, i, ids, meta):
        s, j, i = at(s, j, i, meta)
        return meta[0], ids[s], i, j

    def o_map(s, j, i, ids, meta):
        s, j, _ = at(s, j, i, meta)
        return s, 0, j

    def kernel(ids_ref, meta_ref, x_ref, *refs):
        """One (slot, N block, K block) grid step."""
        del ids_ref                                # the index maps' own
        # (read here: the interpreter knows the grid at a kernel's top
        # level, not under a ``pl.when``)
        k_step = pl.program_id(2)

        @pl.when(pl.program_id(0) < meta_ref[1])
        def _():
            _tiles(x_ref, refs, count, k_steps, lambda: k_step)

    weight = pl.BlockSpec((None, None, tk, tn), w_map)
    out = pl.BlockSpec((None, rows, tn), o_map)
    return tuple(pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((slots, rows, n), jnp.float32)]
        * count,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots, n_steps, k_steps),
            in_specs=[pl.BlockSpec((rows, tk) if shared
                                   else (None, rows, tk), x_map),
                      *[weight] * count],
            out_specs=[out] * count),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * slots * count * rows * k * n, transcendentals=0,
            bytes_accessed=slots * (count * (k * n * itemsize + rows * n * 4)
                                    + rows * k * itemsize * n_steps)),
        interpret=interpret,
        name=name,
    )(jnp.asarray(ids, jnp.int32), meta, x, *leaves))


def _kernel_t(x_ref, w_ref, o_ref):
    """One grid step: a block of the weight's ROWS against the rows of
    ``x``, both contracted over their second axis."""
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def fewrow_dense_t(x: jax.Array, leaf: jax.Array, *,
                   block_rows: Optional[int] = None,
                   name: str = "fewrow_dense_t",
                   interpret: bool = False) -> jax.Array:
    """``x [rows, K]`` times the TRANSPOSE of ``leaf [N, K]`` (of ``x``'s
    dtype), ``[rows, N]`` in float32: what ``jnp.dot(x, leaf.T,
    preferred_element_type=float32)`` gives, the leaf read once, in
    blocks of ``block_rows`` whole rows (default: as many as
    ``TILE_BYTES`` hold).  ``K`` and ``N`` are multiples of 128."""
    n, k = leaf.shape
    if x.ndim != 2 or x.shape[1] != k or k % LANES or n % LANES \
            or leaf.dtype != x.dtype:
        raise ValueError(
            f"fewrow_dense_t: {x.dtype}{list(x.shape)} against the "
            f"transpose of {leaf.dtype}{list(leaf.shape)}: one dtype, "
            f"[rows, K] and [N, K], K and N multiples of {LANES}")
    rows = x.shape[0]
    itemsize = jnp.dtype(x.dtype).itemsize
    tn = block_rows or _largest_divisor(n, TILE_BYTES // itemsize // k)
    return pl.pallas_call(
        _kernel_t,
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        grid=(n // tn,),
        in_specs=[pl.BlockSpec((rows, k), lambda j: (0, 0)),
                  pl.BlockSpec((tn, k), lambda j: (j, 0))],
        out_specs=pl.BlockSpec((rows, tn), lambda j: (0, j)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=k * n * itemsize + rows * n * 4
            + rows * k * itemsize),
        interpret=interpret,
        name=name,
    )(x, leaf)
