"""Fused flash attention (forward) as a Pallas TPU kernel.

The path the UNet's large self-attentions take on a TPU
(``models/layers.py:attention_path`` decides from the shapes; ``attn_impl
= "pallas"`` forces it).  One program per (batch*head, query block) walks
the key/value blocks of its head along a third, sequential grid
dimension with the online-softmax recurrence: the score tile lives in
VMEM for one grid step, the running maximum and the output accumulator
live in VMEM scratch across the steps, and neither scores nor
probabilities ever reach HBM.  VMEM use does not grow with the sequence,
and the pipeline fetches the next K/V block while this one is computed.
Same math as the cross-device ring (``parallel/ring.py``) — that rotates
shards over ICI, this streams blocks inside one chip.

The score tile is held TRANSPOSED, ``k @ q.T`` = [block_k, block_q]:
queries run along the 128 lanes and keys along the sublanes.  The
softmax's maximum then reduces across vregs on the VPU (elementwise)
instead of across lanes on the XLU, a row's statistics are one lane-dense
[1, block_q] vector, and the accumulator ``v.T @ p`` = [D, block_q] fills
its vregs at any head width (D = 40 or 64 would fill 40 or 64 of 128
lanes the other way round).  A row of ones under ``v.T`` makes the MXU
produce the probabilities' sum as one more accumulator row, for free: an
MXU pass costs the same at 65 rows as at 64.  On a v5e that is worth a
quarter of the kernel's time against the row-major form (PERF.md §6,
PR 25).

Precision is the XLA path's (``models/layers.py:xla_attention``): the
operands go to the MXU in the dtype they arrive in (bf16 in the serving
families), both matmuls accumulate in fp32, the maximum and the
exponential are fp32 (v5e has no bf16 VPU or EUP), and the probabilities
are cast to the value dtype before PV; their sum is taken over those cast
values, so the weights applied to v add up to one.  A softmax scale that
is a power of two (head widths 16, 64, 256) is folded into q once, before
the kernel, where it is exact; any other multiplies the fp32 scores in
the kernel, because rounding ``q * scale`` to bf16 costs up to half again
the XLA path's error at head widths 40 and 160.

Layout: q and k go in as ``[B*H, T, D]``, v as ``[B*H, D, T]`` and the
output comes back as ``[B*H, D, N]``; XLA makes those copies around the
call and they are part of the attention's time.  The head dimension is a
full dimension of its blocks and is NOT padded in HBM; tokens are padded
to the block sizes, which ``block_sizes`` picks from the shape, and
padded keys are masked.
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

# candidates for a block's token count, largest first
_BLOCKS = (2048, 1024, 512, 256, 128)
# What one program may hold in VMEM: the fp32 score and probability tiles
# of the largest blocks `block_sizes` picks are 8 MiB each, the bf16
# probabilities 4 MiB, the double-buffered q/k/v/out blocks and the
# scratch about 3 MiB.  Sized on and for a v5e (128 MiB of VMEM, 16 MiB
# the compiler's default scoped limit), the one TPU this repo has run
# on; the rule in models/layers.py asks only for "tpu", so a generation
# with less VMEM a core (v7x: 64 MiB) needs this and the 2048-query cap
# of `block_sizes` measured again before it serves.
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _pick_block(length: int, cap: int) -> int:
    """The largest block (a multiple of 128, at most ``cap``) that pads
    ``length`` to no more than an eighth over what blocks of 128 would."""
    least = _round_up(length, 128)
    return next(b for b in _BLOCKS
                if b <= cap and 8 * _round_up(length, b) <= 9 * least)


def block_sizes(n: int, m: int) -> tuple[int, int]:
    """(query block, key block) for ``n`` query and ``m`` key tokens: as
    large as the lengths allow (PERF.md §6, PR 25: at 4096 tokens 2048 x
    1024 runs in 0.97 ms where 512 x 512 takes 1.35 ms; every head width
    agrees).  A rule over the shape; no option reaches it."""
    return _pick_block(n, 2048), _pick_block(m, 1024)


def _flash_kernel(q_ref, k_ref, vt_ref, o_ref, m_scr, acc_scr, *,
                  scale: float, kv_len: int, block_k: int,
                  mask_tail: bool):
    """One (batch*head, q block, kv block) grid step.

    q_ref: [1, block_q, D]; k_ref: [1, block_k, D]; vt_ref: [1, Dv,
    block_k], v transposed with a row of ones at D (and zeros to Dv);
    o_ref: [1, D, block_q]; m_scr: [8, block_q] fp32, the running maximum
    (every row the same); acc_scr: [Dv, block_q] fp32, row D the running
    sum.  ``scale`` is what is left to apply to the scores (1.0 where q
    arrives scaled)."""
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = jax.lax.dot_general(
        k_ref[0], q_ref[0], dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # [block_k, block_q]
    if scale != 1.0:
        s = s * scale
    if mask_tail:
        key = kv * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(key < kv_len, s, NEG_INF)
    m_prev = m_scr[...]
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    m_scr[...] = m_next
    p = jnp.exp(s - m_next[:1])
    vt = vt_ref[0]
    acc_scr[...] = acc_scr[...] * jnp.exp(m_prev - m_next)[:1] \
        + jax.lax.dot_general(
            vt, p.astype(vt.dtype),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Dv, block_q]

    @pl.when(kv == pl.num_programs(2) - 1)
    def _():
        d = o_ref.shape[1]
        acc = acc_scr[...]
        o_ref[0] = (acc[:d] / acc[d:d + 1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_scale", "score_scale",
                                             "block_q", "block_k",
                                             "interpret"))
def _fused_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     q_scale: float, score_scale: float, block_q: int,
                     block_k: int, interpret: bool) -> jax.Array:
    """The kernel with its layout copies, under one ``jax.jit`` with the
    blocks static: a program that calls it at seventy sites traces and
    lowers it once per distinct shape, and the sites call that one
    function.  ``q_scale`` multiplies q (1.0 folds away), ``score_scale``
    the fp32 scores in the kernel."""
    B, N, H, D = q.shape
    M = k.shape[1]
    n_pad, m_pad = _round_up(N, block_q), _round_up(M, block_k)
    q = (q * q_scale).astype(q.dtype)

    def rows(x, pad):                   # [B, T, H, D] -> [B*H, T + pad, D]
        x = x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    dv = _round_up(D + 1, 16)           # v.T, a row of ones, zeros
    vt = v.transpose(0, 2, 3, 1).reshape(B * H, D, M)
    vt = jnp.concatenate(
        [vt, jnp.ones((B * H, 1, M), v.dtype),
         jnp.zeros((B * H, dv - D - 1, M), v.dtype)], axis=1)
    if m_pad != M:
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, m_pad - M)))
    kernel = functools.partial(_flash_kernel, scale=score_scale, kv_len=M,
                               block_k=block_k, mask_tail=m_pad != M)
    itemsize = jnp.dtype(q.dtype).itemsize
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B * H, D, n_pad), q.dtype),
        grid=(B * H, n_pad // block_q, m_pad // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, dv, block_k), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, D, block_q), lambda b, i, j: (b, 0, i)),
        scratch_shapes=[
            pltpu.VMEM((8, block_q), jnp.float32),      # running max
            pltpu.VMEM((dv, block_q), jnp.float32),     # accumulator, sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=4 * B * H * n_pad * m_pad * D,
            transcendentals=B * H * n_pad * m_pad,
            bytes_accessed=itemsize * B * H * (
                2 * n_pad * D + (D + dv) * m_pad * (n_pad // block_q))),
        interpret=interpret,
        name="flash_attention",
    )(rows(q, n_pad - N), rows(k, m_pad - M), vt)
    return out[:, :, :N].reshape(B, H, D, N).transpose(0, 3, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    scale: Optional[float] = None,
                    interpret: bool = False) -> jax.Array:
    """[B, N, H, D] attention, q against k/v [B, M, H, D] (M != N
    allowed), any lengths: tokens are padded to the blocks and padded
    keys masked.

    ``interpret=True`` runs the Pallas interpreter (CPU tests pass it);
    nothing selects it on its own.  A caller that asked for this kernel
    gets this kernel, at any sequence length: K/V are streamed, so no
    shape is too long for VMEM.  Differentiable: the kernel is the
    forward pass, and the backward pass is `xla_attention`'s, recomputed
    from q, k and v (`_flash_bwd`)."""
    return _forward(q, k, v, scale, interpret)


def _forward(q, k, v, scale, interpret):
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    # a power of two is exact in q; anything else stays with the scores
    q_scale, score_scale = (scale, 1.0) if math.frexp(scale)[0] == 0.5 \
        else (1.0, scale)
    block_q, block_k = block_sizes(q.shape[1], k.shape[1])
    return _fused_attention(q, k, v, q_scale=q_scale,
                            score_scale=score_scale, block_q=block_q,
                            block_k=block_k, interpret=interpret)


def _flash_fwd(q, k, v, scale, interpret):
    return _forward(q, k, v, scale, interpret), (q, k, v)


def _flash_bwd(scale, interpret, residuals, g):
    """The training step (parallel/train.py) differentiates through the
    UNet.  No backward kernel is written: the cotangents are those of the
    XLA path on the same operands, which is what a training step paid
    before the rule sent its self-attentions here."""
    from comfyui_distributed_tpu.models.layers import xla_attention

    del interpret
    q = residuals[0]
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    return jax.vjp(lambda q, k, v: xla_attention(q, k, v, scale),
                   *residuals)[1](g)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
