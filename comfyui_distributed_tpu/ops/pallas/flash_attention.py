"""Fused flash attention (forward) as a Pallas TPU kernel.

Selected via ``UNetConfig.attn_impl = "pallas"``
(``models/layers.py:scaled_dot_product_attention``).  The SD UNet's
self-attention at the top resolution level is the largest non-conv cost;
this kernel keeps the [BLOCK_Q, N] logits tile in VMEM and streams K/V
blocks with the online-softmax recurrence, so the full [N, N] attention
matrix never touches HBM.  Same math as the cross-device ring
(``parallel/ring.py``) — that rotates shards over ICI, this loops blocks
inside one chip.

Per the TPU tiling rules (pallas_guide.md): last dim padded to 128 lanes,
block sizes multiples of the fp32 (8, 128) tile, grid over (batch*heads,
query blocks), fp32 accumulation.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

BLOCK_Q = 128
BLOCK_K = 512

# Per-program VMEM budget (bytes), under Mosaic's default 16 MiB scoped
# limit.  Each program holds its q tile, the FULL padded K/V for its head
# and the output tile — every one of them twice, because the pipeline
# double-buffers each BlockSpec'd operand — plus the kernel's fp32
# temporaries.  A shape over budget raises (see `flash_attention`).
VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float,
                  kv_len: int, block_k: int):
    """One (batch*head, q-block) program: stream K/V blocks, online softmax.

    q_ref: [1, BLOCK_Q, Dp]; k_ref/v_ref: [1, Nk_pad, Dp]; o_ref like q_ref.
    """
    q = q_ref[0].astype(jnp.float32) * scale
    block_q, dp = q.shape
    num_kb = k_ref.shape[1] // block_k

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [BLOCK_Q, block_k]
        # mask padded kv rows (kv_len may not fill the last block)
        col = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(col < kv_len, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p, v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, dp), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_kb, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    target = ((size + mult - 1) // mult) * mult
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads)


def vmem_bytes(kv_pad: int, block_k: int, dp: int, itemsize: int) -> int:
    """What one program of the kernel keeps in VMEM: the double-buffered
    q/out tiles and full K/V, and the fp32 temporaries of `_flash_kernel`
    (scaled q, one k and one v block, the logits and probability tiles,
    the accumulator)."""
    pipelined = 2 * itemsize * (2 * BLOCK_Q * dp + 2 * kv_pad * dp)
    temporaries = 4 * (2 * BLOCK_Q * dp + 2 * block_k * dp
                       + 2 * BLOCK_Q * block_k)
    return pipelined + temporaries


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    scale: Optional[float] = None,
                    interpret: bool = False) -> jax.Array:
    """[B, N, H, D] attention, q vs k/v (cross-attention allowed: M != N).

    Pads N to BLOCK_Q, M to BLOCK_K, D to 128 lanes; grid is
    (B*H, N/BLOCK_Q); each program holds its q tile and streams the full
    K/V for its head out of VMEM.

    ``interpret=True`` runs the Pallas interpreter (CPU tests pass it);
    nothing selects it on its own.  A caller that asked for this kernel
    gets this kernel: a shape whose K/V do not fit the VMEM budget raises
    ``ValueError`` naming the shape, and is never handed to another
    implementation behind the caller's back.
    """
    B, N, H, D = q.shape
    M = k.shape[1]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)

    # [B, N, H, D] -> [B*H, N, D]
    def to_bhnd(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)

    qf, kf, vf = to_bhnd(q), to_bhnd(k), to_bhnd(v)
    block_k = min(BLOCK_K, ((M + 127) // 128) * 128)
    qf = _pad_to(_pad_to(qf, 1, BLOCK_Q), 2, 128)
    kf = _pad_to(_pad_to(kf, 1, block_k), 2, 128)
    vf = _pad_to(_pad_to(vf, 1, block_k), 2, 128)
    n_pad, dp = qf.shape[1], qf.shape[2]

    vmem = vmem_bytes(kf.shape[1], block_k, dp,
                      jnp.dtype(q.dtype).itemsize)
    if vmem > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"flash_attention: q {tuple(q.shape)} against kv length {M} "
            f"needs about {vmem / 2**20:.1f} MiB of VMEM per program "
            f"(the full K and V of a head, double-buffered), over the "
            f"{VMEM_BUDGET_BYTES / 2**20:.0f} MiB budget; use "
            f"attn_impl='xla' for this shape")

    grid = (B * H, n_pad // BLOCK_Q)
    kernel = functools.partial(_flash_kernel, scale=scale, kv_len=M,
                               block_k=block_k)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B * H, n_pad, dp), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BLOCK_Q, dp), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, kf.shape[1], dp), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, vf.shape[1], dp), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_Q, dp), lambda b, i: (b, i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(qf, kf, vf)

    out = out[:, :N, :D].reshape(B, H, N, D).transpose(0, 2, 1, 3)
    return out
