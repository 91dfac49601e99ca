"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

The reference has no attention-level sharding at all (SURVEY.md §5 —
"long-context / sequence parallelism: ABSENT"); its spatial analog is tile
scatter.  This framework makes sequence parallelism first-class: token axes
shard over the ``seq`` mesh axis, and attention runs as a ring — each device
holds its Q shard resident while K/V shards rotate around the ring via
``lax.ppermute`` (ICI neighbor exchange), with flash-style online-softmax
accumulation so no device ever materializes the full sequence or the full
attention matrix.

Math: per incoming K/V block, logits ``s = qk^T * scale`` update the running
``(max, denominator, accumulator)`` triple:

    m'   = max(m, max(s))
    corr = exp(m - m')
    l'   = l * corr + sum(exp(s - m'))
    acc' = acc * corr + exp(s - m') @ v

which is exactly blockwise-stable softmax — the same recurrence the Pallas
flash kernel uses intra-device (``ops/pallas/flash_attention.py``).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from comfyui_distributed_tpu.parallel import sharding as shd

from comfyui_distributed_tpu.utils.constants import (
    DATA_AXIS,
    SEQ_AXIS,
    TENSOR_AXIS,
)

NEG_INF = -1e30


def _block_update(q, k, v, m, l, acc, scale, mask=None):
    """One online-softmax accumulation step.

    q: [B, Nq, H, D]; k/v: [B, Nk, H, D]; m/l: [B, H, Nq]; acc like q.
    """
    s = jnp.einsum("bnhd,bmhd->bhnm", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None].transpose(0, 2, 1, 3) + jnp.einsum(
        "bhnm,bmhd->bnhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _ring_body(q, k, v, axis_name: str, n_shards: int, causal: bool,
               scale: float):
    """Per-shard ring attention (runs inside shard_map).

    q/k/v: [B, n_local, H, D] — the local sequence shard."""
    B, n_local, H, D = q.shape
    my_idx = jax.lax.axis_index(axis_name)

    q_pos = my_idx * n_local + jnp.arange(n_local)          # global q rows

    m = jnp.full((B, H, n_local), NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, n_local), jnp.float32)
    acc = jnp.zeros((B, n_local, H, D), jnp.float32)

    def step(carry, step_i):
        k_cur, v_cur, m, l, acc = carry
        # the block arriving at step t originated at shard (my_idx - t) % n
        src = jnp.mod(my_idx - step_i, n_shards)
        if causal:
            k_pos = src * n_local + jnp.arange(n_local)
            mask = q_pos[:, None] >= k_pos[None, :]          # [Nq, Nk]
            mask = mask[None, None, :, :]
        else:
            mask = None
        m, l, acc = _block_update(q, k_cur, v_cur, m, l, acc, scale, mask)
        # rotate K/V to the next neighbor over ICI
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m, l, acc), None

    (k, v, m, l, acc), _ = jax.lax.scan(
        step, (k, v, m, l, acc), jnp.arange(n_shards))
    out = acc / jnp.maximum(l, 1e-20)[..., None].transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mesh: Mesh, axis_name: str = SEQ_AXIS,
                   causal: bool = False,
                   scale: Optional[float] = None,
                   batch_axis: Optional[str] = DATA_AXIS,
                   head_axis: Optional[str] = TENSOR_AXIS) -> jax.Array:
    """Sequence-parallel attention over ``mesh[axis_name]``.

    q/k/v: [B, N, H, D] with the token axis N sharded over ``axis_name``
    (replicated inputs are fine too — shard_map partitions them).  Returns
    [B, N, H, D] with the same sharding.  N must divide evenly by the axis
    size (pad upstream — same pad-and-mask stance as the tile scatter,
    ``parallel/collectives.py``).

    Composes with the other mesh axes: when the batch dim divides
    ``batch_axis`` (dp) and/or the head dim divides ``head_axis`` (tp),
    those dims shard too instead of forcing an all-gather of dp-sharded
    activations into every seq shard — so dp x tp x sp runs as one
    shard_map with the K/V ring riding only the ``seq`` axis."""
    n_shards = mesh.shape[axis_name]
    if q.shape[1] % n_shards:
        raise ValueError(f"sequence length {q.shape[1]} not divisible by "
                         f"{axis_name} axis size {n_shards}")
    if k.shape[1] != v.shape[1]:
        raise ValueError(f"k/v length mismatch: {k.shape[1]} vs {v.shape[1]}")
    if k.shape[1] % n_shards:
        raise ValueError(f"k/v length {k.shape[1]} not divisible by "
                         f"{axis_name} axis size {n_shards}")
    if causal and k.shape[1] != q.shape[1]:
        # causal cross-attention (Nq != Nk) has no well-defined position
        # alignment; silently masking by local index would be wrong
        raise ValueError(f"causal ring attention requires Nq == Nk, got "
                         f"{q.shape[1]} vs {k.shape[1]}")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if n_shards == 1:
        m = jnp.full(q.shape[:1] + (q.shape[2], q.shape[1]), NEG_INF,
                     jnp.float32)
        l = jnp.zeros_like(m)
        acc = jnp.zeros(q.shape, jnp.float32)
        mask = None
        if causal:
            n = q.shape[1]
            mask = (jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
                    )[None, None, :, :]
        m, l, acc = _block_update(q, k, v, m, l, acc, scale, mask)
        return (acc / jnp.maximum(l, 1e-20)[..., None].transpose(0, 2, 1, 3)
                ).astype(q.dtype)

    def _axis_if_divisible(name: Optional[str], dim: int) -> Optional[str]:
        if not name or name == axis_name or name not in mesh.shape:
            return None
        size = int(mesh.shape[name])
        return name if size > 1 and dim % size == 0 else None

    b_ax = _axis_if_divisible(batch_axis, q.shape[0])
    h_ax = _axis_if_divisible(head_axis, q.shape[2])
    spec = shd.mesh_spec(b_ax, axis_name, h_ax, None)
    body = partial(_ring_body, axis_name=axis_name, n_shards=n_shards,
                   causal=causal, scale=scale)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def attention_reference(q, k, v, causal: bool = False,
                        scale: Optional[float] = None):
    """Plain softmax attention — the oracle ring_attention must match."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bnhd,bmhd->bhnm", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        n, mkv = q.shape[1], k.shape[1]
        mask = jnp.arange(n)[:, None] >= jnp.arange(mkv)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhnm,bmhd->bnhd", w.astype(v.dtype), v)
