"""GEGLU as one product with its gate on the output side (Pallas TPU kernel).

The path the UNet's feed-forward takes on a TPU
(``models/layers.py:geglu_path`` decides from the shapes).  The module as
written is ``h = x @ W + b`` with ``W [c, 8c]``, then ``h[:, :4c] *
gelu(h[:, 4c:])``.  An output element ``(t, j)`` needs the product's
columns ``j`` and ``j + 4c``, which no output fusion of one matmul can
hold, so XLA writes the whole ``[rows, 8c]`` projection to HBM and fuses
the split, the erf and the multiply in FRONT of the next product
(``ff/out``), as the producer of its left operand (PERF.md §6, PR 39).

Here one program per (row block, column block) holds an ``x`` tile
``[tm, c]`` (the contraction whole: ``c`` is at most 1280) and TWO tiles
``[c, tn]`` of the one published ``proj`` kernel, picked by two index maps
at column blocks ``j`` and ``j + 4c / tn``: the leaf keeps its name, shape
and layout, nothing is split or copied.  Two ``bf16 x bf16 -> fp32``
products, the bias's two halves added in fp32, the exact (erf) gelu on the
fp32 gate, the multiply, ONE rounding, a ``[tm, tn]`` store: ``[rows, 4c]``
is all that reaches HBM and each gate element meets the erf once.

Precision is the module's: the operands go to the MXU in the dtype they
arrive in (bf16 in the serving families) and accumulate in fp32.  The gate
sees that accumulator, where the XLA path sees it rounded to the compute
dtype first: one rounding fewer, none more.  Mosaic has no lowering for
``lax.erf``; `_erf` is the float32 rational approximation XLA's own
expansion of it uses (3e-7 absolute against the true erf, as
``jax.lax.erf`` reads), so the gelu is the exact one, not the tanh form.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# candidates for a block's rows and columns, largest first
_ROW_BLOCKS = (1024, 512, 256, 128)
_COL_BLOCKS = (512, 256, 128)
# Sized on and for a v5e (flash_attention.py's note on VMEM_LIMIT_BYTES
# holds here too).  What one program holds: the double-buffered x, weight,
# bias and output blocks, and the float32 products and gate.
VMEM_LIMIT_BYTES = 48 * 1024 * 1024
BLOCK_BYTES = 24 * 1024 * 1024

# erf(x) ~ x * P(x^2) / Q(x^2) on [-c, c], c = erfinv(1 - 2^-23); +-1
# outside it.  The coefficients of XLA's float32 expansion of `erf`.
_ERF_CLAMP = 3.7439211627767994
_ERF_P = (0.00022905065861350646, 0.0034082910107109506,
          0.050955695062380861, 0.18520832239976145, 1.128379143519084)
_ERF_Q = (-1.1791602954361697e-7, 0.000023547966471313185,
          0.0010179625278914885, 0.014070470171167667,
          0.11098505178285362, 0.49746925110067538, 1.0)


def _erf(x: jax.Array) -> jax.Array:
    x = jnp.clip(x, -_ERF_CLAMP, _ERF_CLAMP)
    x2 = x * x

    def horner(coefficients):
        acc = jnp.full_like(x2, coefficients[0])
        for k in coefficients[1:]:
            acc = acc * x2 + k
        return acc

    return x * horner(_ERF_P) / horner(_ERF_Q)


def gate(a: jax.Array, g: jax.Array) -> jax.Array:
    """``a * gelu(g)`` with the exact (erf) gelu, float32 in and out."""
    return a * (0.5 * g * (1.0 + _erf(g * 0.7071067811865476)))


def block_sizes(rows: int, c: int, n: int,
                itemsize: int = 2) -> Optional[Tuple[int, int]]:
    """``(tm, tn)`` for ``x [rows, c]`` against ``proj [c, 2n]``, or None
    where no block divides: the largest row block that divides ``rows``
    and the largest column block that divides ``n`` whose program stays
    inside BLOCK_BYTES.  A rule over the shape; no option reaches it."""
    for tn in _COL_BLOCKS:
        if n % tn:
            continue
        for tm in _ROW_BLOCKS:
            if rows % tm:
                continue
            held = 2 * itemsize * (tm * c + 2 * c * tn + tm * tn) \
                + 4 * 4 * tm * tn
            if held <= BLOCK_BYTES:
                return tm, tn
    return None


def _kernel(x_ref, wa_ref, wg_ref, *refs, bias: bool):
    """One (row block, column block) grid step.  x_ref: [tm, c]; wa_ref,
    wg_ref: [c, tn], the value and gate columns of the one leaf; with a
    bias its two halves [1, tn] float32; o_ref: [tm, tn]."""
    o_ref = refs[-1]
    x = x_ref[...]
    a = jnp.dot(x, wa_ref[...], preferred_element_type=jnp.float32)
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    if bias:
        a = a + refs[0][...]
        g = g + refs[1][...]
    o_ref[...] = gate(a, g).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def _fused_geglu(x: jax.Array, kernel: jax.Array,
                 bias: Optional[jax.Array], *, blocks: Tuple[int, int],
                 interpret: bool) -> jax.Array:
    """The kernel over ``x [rows, c]``, under one ``jax.jit`` with the
    blocks static: a program that calls it at seventy sites traces and
    lowers it once per distinct shape."""
    rows, c = x.shape
    n = kernel.shape[1] // 2
    tm, tn = blocks
    half = n // tn
    itemsize = jnp.dtype(x.dtype).itemsize
    operands = [x, kernel, kernel]
    in_specs = [pl.BlockSpec((tm, c), lambda i, j: (i, 0)),
                pl.BlockSpec((c, tn), lambda i, j: (0, j)),
                pl.BlockSpec((c, tn), lambda i, j: (0, j + half))]
    if bias is not None:
        b = bias.astype(jnp.float32).reshape(1, 2 * n)
        operands += [b, b]
        in_specs += [pl.BlockSpec((1, tn), lambda i, j: (0, j)),
                     pl.BlockSpec((1, tn), lambda i, j: (0, j + half))]
    return pl.pallas_call(
        functools.partial(_kernel, bias=bias is not None),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        grid=(rows // tm, half),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=4 * rows * c * n, transcendentals=rows * n,
            bytes_accessed=itemsize * (
                rows * c + 2 * c * n * (rows // tm) + rows * n)),
        interpret=interpret,
        name="geglu",
    )(*operands)


def xla_geglu(x: jax.Array, kernel: jax.Array,
              bias: Optional[jax.Array]) -> jax.Array:
    """The module as written (``nn.Dense``, split, ``a * gelu(b)``) on the
    same operands: the path of everything `geglu_path` does not send to
    the kernel, the kernel's backward pass, and the oracle it is checked
    against."""
    h = jax.lax.dot_general(x, kernel, (((x.ndim - 1,), (0,)), ((), ())))
    if bias is not None:
        h = h + bias
    a, g = jnp.split(h, 2, axis=-1)
    return a * jax.nn.gelu(g, approximate=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def geglu(x: jax.Array, kernel: jax.Array, bias: Optional[jax.Array],
          interpret: bool = False) -> jax.Array:
    """``x [..., c]`` through ``proj [c, 2n]`` (``bias [2n]`` or None) and
    the gate: ``[..., n]`` in ``x``'s dtype.  The leading dimensions
    together and ``n`` must divide a block (`block_sizes`).

    ``interpret=True`` runs the Pallas interpreter (CPU tests pass it);
    nothing selects it on its own.  Differentiable: the kernel is the
    forward pass, and the backward pass is `xla_geglu`'s, recomputed from
    the operands (`_geglu_bwd`)."""
    return _forward(x, kernel, bias, interpret)


def _forward(x, kernel, bias, interpret):
    c, n = x.shape[-1], kernel.shape[1] // 2
    rows = x.size // c
    blocks = block_sizes(rows, c, n, jnp.dtype(x.dtype).itemsize)
    if blocks is None or kernel.shape != (c, 2 * n) \
            or kernel.dtype != x.dtype:
        raise ValueError(
            f"geglu: {x.dtype}{list(x.shape)} against "
            f"{kernel.dtype}{list(kernel.shape)}: one dtype, and rows and "
            f"half the columns multiples of {LANES}")
    out = _fused_geglu(x.reshape(rows, c), kernel, bias, blocks=blocks,
                       interpret=interpret)
    return out.reshape(*x.shape[:-1], n)


def _geglu_fwd(x, kernel, bias, interpret):
    return _forward(x, kernel, bias, interpret), (x, kernel, bias)


def _geglu_bwd(interpret, residuals, g):
    """The training step (parallel/train.py) differentiates through the
    UNet.  No backward kernel is written: the cotangents are those of the
    module as written on the same operands, which is what a training step
    paid before the rule sent its feed-forwards here."""
    del interpret
    return jax.vjp(xla_geglu, *residuals)[1](g)


geglu.defvjp(_geglu_fwd, _geglu_bwd)
