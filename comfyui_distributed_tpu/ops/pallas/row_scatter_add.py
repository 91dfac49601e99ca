"""Rows added into a resident float32 sum, in place (Pallas TPU kernel).

The path the expert layer's tiles take back into the sum on a TPU
(``models/mla_moe.py:scatter_path`` decides).  A tile of a hit expert's
results ``update [T, d]`` belongs to ``count`` distinct token rows of the
sum ``y [t, d]``, which lives in HBM.  XLA's row scatter walks the rows
one after the other, each a read-modify-write that waits out HBM's
latency (1.9 us a row on a v5e: 240 us a tile of 128 rows, four times
the tile's three products).  Here every row's read is in flight at once,
the tile is added in VMEM in one pass, and every row's write is in flight
at once: the latency is paid twice a tile, not twice a row.

``y`` is handed over as ``[t, d / 128, 128]``: a row is then a whole
number of the tiled layout's ``(8, 128)`` tiles, one contiguous run of
HBM that a DMA may start at any row.  The operand is aliased to the
result, so nothing else of ``y`` is read or written.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def _kernel(rows_ref, count_ref, update_ref, y_ref, out_ref, buf, sems):
    """``out[rows[i]] += update[i]`` for ``i < count``; ``y_ref`` is
    ``out_ref``'s own buffer (aliased)."""
    del y_ref
    tile = buf.shape[0]

    def over_live_rows(do):
        def body(i, carry):
            @pl.when(i < count_ref[0])
            def _():
                do(i)
            return carry
        jax.lax.fori_loop(0, tile, body, 0)

    def row_in(i):
        return pltpu.make_async_copy(out_ref.at[rows_ref[i]], buf.at[i],
                                     sems.at[i])

    def row_out(i):
        return pltpu.make_async_copy(buf.at[i], out_ref.at[rows_ref[i]],
                                     sems.at[i])

    over_live_rows(lambda i: row_in(i).start())
    over_live_rows(lambda i: row_in(i).wait())
    # (the rows past ``count`` of ``buf`` hold whatever was there: they
    # are added to and never written back)
    buf[...] += update_ref[...]
    over_live_rows(lambda i: row_out(i).start())
    over_live_rows(lambda i: row_out(i).wait())


def row_scatter_add(y: jax.Array, rows: jax.Array, count: jax.Array,
                    update: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """``y [t, g, 128]`` (float32) with ``update[i] [g, 128]`` added to
    row ``rows[i]`` for every ``i < count``; the first ``count`` of
    ``rows [T]`` are distinct rows of ``y`` (the others are not looked
    at).  ``y`` is updated in place where the caller lets go of it.
    ``interpret=True`` runs the Pallas interpreter (CPU tests)."""
    if y.ndim != 3 or y.shape[2] != LANES or y.dtype != jnp.float32 \
            or update.shape != (rows.shape[0], *y.shape[1:]) \
            or update.dtype != y.dtype:
        raise ValueError(
            f"row_scatter_add: {update.dtype}{list(update.shape)} into "
            f"{y.dtype}{list(y.shape)} at {list(rows.shape)} rows: a "
            f"float32 sum [t, g, {LANES}] and an update [T, g, {LANES}]")
    tile = rows.shape[0]
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM(update.shape, y.dtype),
                            pltpu.SemaphoreType.DMA((tile,))]),
        # operands count the prefetched scalars: rows, count, update, y
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=update.size, transcendentals=0,
            bytes_accessed=3 * update.size * 4),
        interpret=interpret,
        name="row_scatter_add",
    )(jnp.asarray(rows, jnp.int32),
      jnp.asarray(count, jnp.int32).reshape(1), update, y)
