"""Shared neural blocks (flax.linen, NHWC, bf16-friendly).

TPU-first conventions used across the model zoo:
- channels-last (NHWC) everywhere — XLA's native conv layout on TPU;
- compute dtype bfloat16 by default with fp32 params and fp32 normalization
  statistics (GroupNorm in fp32 to avoid bf16 variance underflow);
- attention shaped as large batched matmuls for the MXU; heads stay a
  separate dim so tensor-parallel sharding can split them.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from comfyui_distributed_tpu.parallel import sharding as shd
from comfyui_distributed_tpu.utils.constants import (DATA_AXIS, SEQ_AXIS,
                                                     TENSOR_AXIS)
from comfyui_distributed_tpu.utils.trace import ATTENTION_PATHS, GEGLU_PATHS

Dtype = Any


def timestep_embedding(t: jax.Array, dim: int,
                       max_period: float = 10000.0) -> jax.Array:
    """Sinusoidal timestep embedding (DDPM convention)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None, :]
    emb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    if dim % 2:
        emb = jnp.concatenate([emb, jnp.zeros_like(emb[:, :1])], axis=-1)
    # pin: time_fc1's kernel layout (input-dim fallback split) must not
    # back-propagate a tensor sharding onto the cos/sin concat dim
    # (tp-concat-cpu-miscompile); the embedding is tiny, replication
    # is free
    return shd.replicate(emb)


class GroupNorm32(nn.Module):
    """GroupNorm computed in fp32 regardless of compute dtype."""
    num_groups: int = 32
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        orig = x.dtype
        groups = min(self.num_groups, x.shape[-1])
        while x.shape[-1] % groups:
            groups -= 1
        out = nn.GroupNorm(num_groups=groups, epsilon=self.epsilon,
                           dtype=jnp.float32)(x.astype(jnp.float32))
        return out.astype(orig)


class Attention(nn.Module):
    """Multi-head attention over flattened tokens.

    Self-attention when ``context`` is None, cross-attention otherwise.
    Shapes: q from ``x [B, N, C]``, k/v from ``context [B, M, Cc]``.
    How the math runs is decided per call site, at trace time, by
    `scaled_dot_product_attention`: with ``attn_impl="xla"`` (what every
    config carries) `attention_path` reads the platform and the operands'
    shapes and sends the large self-attentions on a TPU to the fused
    Pallas flash kernel (ops/pallas/flash_attention.py; inside
    ``shard_map`` under a multi-device mesh) and everything else — the
    77-token text cross-attention, the small levels, every CPU run — to
    `xla_attention`.  ``"pallas"`` forces the kernel; ``"ring"`` asks for
    sequence parallelism over the mesh's ``seq`` axis (parallel/ring.py;
    falls back to the rule when the sequence is short, indivisible, or
    the mesh has no seq axis).
    """
    num_heads: int
    head_dim: Optional[int] = None
    dtype: Dtype = jnp.bfloat16
    attn_impl: str = "xla"
    # SAG capture: materialize + sow the softmax weights so the sampler
    # can read them back (mutable=["intermediates"]).  Only the UNet
    # mid-block's self-attention sets this — its token count is small,
    # so the explicit [B, H, N, N] weights are cheap
    sow_probs: bool = False

    @nn.compact
    def __call__(self, x: jax.Array,
                 context: Optional[jax.Array] = None,
                 context_v: Optional[jax.Array] = None) -> jax.Array:
        """``context_v``: separate value-side context (hypernetworks
        transform the k and v context streams independently); defaults
        to ``context``."""
        c = x.shape[-1]
        hd = self.head_dim or c // self.num_heads
        inner = hd * self.num_heads
        ctx = x if context is None else context
        ctx_v = ctx if context_v is None else context_v

        q = nn.Dense(inner, use_bias=False, dtype=self.dtype, name="to_q")(x)
        k = nn.Dense(inner, use_bias=False, dtype=self.dtype, name="to_k")(ctx)
        v = nn.Dense(inner, use_bias=False, dtype=self.dtype,
                     name="to_v")(ctx_v)

        B, N, _ = q.shape
        M = k.shape[1]
        # megatron head split: q/k/v heads ride the tensor axis (inert on
        # dp-only meshes; see parallel/sharding.py rule table)
        q = shd.constrain(q.reshape(B, N, self.num_heads, hd),
                          "batch", None, "heads", None)
        k = shd.constrain(k.reshape(B, M, self.num_heads, hd),
                          "batch", None, "heads", None)
        v = shd.constrain(v.reshape(B, M, self.num_heads, hd),
                          "batch", None, "heads", None)

        if self.sow_probs:
            logits = jnp.einsum("bnhd,bmhd->bhnm", q, k,
                                preferred_element_type=jnp.float32) \
                * (1.0 / math.sqrt(hd))
            weights = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            self.sow("intermediates", "attn_probs", weights)
            out = jnp.einsum("bhnm,bmhd->bnhd", weights.astype(v.dtype),
                             v)
        else:
            out = scaled_dot_product_attention(q, k, v,
                                               impl=self.attn_impl)
        out = out.reshape(B, N, inner)
        return nn.Dense(c, dtype=self.dtype, name="to_out")(out)


# Token counts (queries and keys both) from which the fused kernel beats
# the XLA path on a TPU: PERF.md §6, PR 25, the step-0 table.  Below it a
# grid step's fixed cost and the layout copies outweigh the score bytes
# the kernel saves; M = 77 cross-attention stays on the XLA path with it.
FUSED_MIN_TOKENS = 1024


def _query_chunk(b: int, n: int, m: int, h: int) -> Optional[int]:
    """The query chunk `xla_attention` scans over, or None where the
    whole fp32 score tensor [b, h, n, m] stays under the ceiling
    (``DTPU_ATTN_SCORES_BYTES``, default 512 MB): the largest divisor of
    ``n`` whose score block does."""
    import os

    limit = int(os.environ.get("DTPU_ATTN_SCORES_BYTES",
                               str(512 * 1024 * 1024)))
    if 4 * b * h * n * m <= limit or n <= 128:
        return None
    want = max(1, limit // (4 * b * h * m))
    return next(c for c in range(min(want, n), 0, -1) if n % c == 0)


def attention_path(platform: str, b: int, n: int, m: int, h: int,
                   mesh_axes: Optional[dict] = None, masked: bool = False,
                   banded: bool = False, selected: bool = False) -> str:
    """Which implementation ``impl="xla"`` (the default) runs for q
    [b, n, h, *] against k/v [b, m, h, *]: ``fused`` (the Pallas flash
    kernel), ``xla_whole`` / ``xla_chunked`` (`xla_attention`, the scores
    whole or scanned over query chunks); for a ``masked`` call (a language
    model's: a query sees the keys up to its own position) ``xla_decode``
    where one query meets a cache, else ``xla_causal``; ``banded`` besides
    (a query sees its last ``window`` keys) ``xla_ring`` where one query
    meets a ring of slots, else ``xla_banded``; ``selected`` besides (a
    query sees the keys a learned index chose) ``xla_gathered`` where one
    query meets the keys gathered for it, else ``xla_selected``.  A masked
    call stays with `xla_attention` everywhere: the kernel knows no mask.

    A function of what the code can see at trace time and nothing else:
    the backend's platform, the operands' static shapes and the live
    mesh's ``{axis: size}`` (None on one device).  The kernel takes
    self-attention-sized calls on a TPU (both token counts at least
    FUSED_MIN_TOKENS) at every head width.  Under a multi-device mesh
    each chip runs it on its own rows and heads (`_fused_on_mesh`), which
    beats what the partitioned XLA path does per chip (PERF.md §6, PR
    25); a mesh that `shard_map` could not split the call over — a live
    ``seq`` axis, rows that do not divide ``data``, heads that do not
    divide ``tensor`` — would run the whole call on every peer, so there
    the call stays with XLA, which partitions it."""
    if masked:
        kind = ("ring", "banded") if banded else ("gathered", "selected") \
            if selected else ("decode", "causal")
        return "xla_" + kind[n != 1]
    axes = mesh_axes or {}
    splits = (axes.get(SEQ_AXIS, 1) == 1 and b % axes.get(DATA_AXIS, 1) == 0
              and h % axes.get(TENSOR_AXIS, 1) == 0)
    if platform == "tpu" and min(n, m) >= FUSED_MIN_TOKENS and splits:
        return "fused"
    return "xla_whole" if _query_chunk(b, n, m, h) is None \
        else "xla_chunked"


def scaled_dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                                 impl: str = "xla",
                                 q_positions: Optional[jax.Array] = None,
                                 kv_start: Optional[jax.Array] = None
                                 ) -> jax.Array:
    """[B, N, H, D] attention, fp32 softmax accumulation.

    ``q_positions [N]`` masks the call: query ``i`` sees the keys whose
    index is at most ``q_positions[i]``.  That is both a causal prefill
    (``arange(N)`` against the call's own keys) and a decode step (one
    query at position ``t`` against a cache of any length, valid to
    ``t``).  With ``kv_start [B]`` beside it, row ``b`` sees no key in
    front of index ``kv_start[b]`` either (a left-padded row's padding).

    ``impl="xla"`` — what every model config carries — leaves the choice
    to `attention_path`: the fused Pallas kernel for the large
    self-attentions on a TPU, `xla_attention` for everything else and for
    every CPU run.  ``"pallas"`` forces the kernel and ``"ring"`` asks for
    the sequence-parallel ring (tests and `bench.py --attn`).  Every path
    is differentiable (the training step runs this).  Each call site
    counts the path it took, once, at trace time (``attention_paths`` on
    ``GET /distributed/metrics``)."""
    if impl == "ring":
        out = _maybe_ring_attention(q, k, v)
        if out is not None:
            ATTENTION_PATHS.bump("ring")
            return out
        impl = "xla"
    B, N, H, D = q.shape
    mesh = _live_mesh()
    masked = q_positions is not None
    path = "fused" if impl == "pallas" and not masked else attention_path(
        jax.default_backend(), B, N, k.shape[1], H,
        dict(mesh.shape) if mesh is not None else None, masked=masked)
    ATTENTION_PATHS.bump(path)
    if path == "fused":
        return _fused_on_mesh(q, k, v, mesh)
    return xla_attention(q, k, v, 1.0 / math.sqrt(D), q_positions, kv_start)


def _live_mesh():
    """The live runtime's mesh if it spans several devices, else None."""
    from comfyui_distributed_tpu.parallel.mesh import get_live_runtime

    mesh = getattr(get_live_runtime(), "mesh", None)
    return mesh if mesh is not None and mesh.size > 1 else None


def _fused_on_mesh(q: jax.Array, k: jax.Array, v: jax.Array,
                   mesh) -> jax.Array:
    """The flash kernel, each chip on its own share.  XLA cannot
    partition a Mosaic custom call: handed batch-sharded operands inside
    a program over the mesh it would gather them and run every row on
    every chip.  So under a multi-device mesh the call goes through
    ``jax.shard_map``: batch rows over ``data`` and heads over ``tensor``,
    and each chip runs the one-chip kernel.  The rule sends only calls
    that split so; a caller that forces the kernel (``impl="pallas"``)
    gets what does not divide replicated."""
    from comfyui_distributed_tpu.ops.pallas.flash_attention import (
        flash_attention)

    if mesh is None:
        return flash_attention(q, k, v)

    def axis(name: str, dim: int) -> Optional[str]:
        size = int(mesh.shape.get(name, 1))
        return name if size > 1 and dim % size == 0 else None

    spec = shd.mesh_spec(axis(DATA_AXIS, q.shape[0]), None,
                         axis(TENSOR_AXIS, q.shape[2]), None)
    return jax.shard_map(flash_attention, mesh=mesh,
                         in_specs=(spec, spec, spec), out_specs=spec,
                         check_vma=False)(q, k, v)


def visible_keys(m: int, q_positions: jax.Array,
                 kv_start: Optional[jax.Array] = None,
                 kv_positions: Optional[jax.Array] = None,
                 window: Optional[int] = None,
                 selected: Optional[jax.Array] = None) -> jax.Array:
    """The mask of a masked call, ``[b or 1, n, m]``: query ``i`` sees
    the keys whose position is at most ``q_positions[i]``, in row ``b``
    none in front of ``kv_start[b]``, and with ``window`` only the last
    ``window`` of them, its own counted.  A key's position is its index,
    or ``kv_positions [m]`` where the keys lie elsewhere (the slots of a
    ring: a slot never written holds a position below every row's
    start).  With ``selected [b, n or 1, m]`` a query sees of those keys
    the ones a selection made from the DATA names for it (a learned
    index's best), and no other."""
    at = jnp.arange(m) if kv_positions is None else kv_positions
    seen = at[None, :] <= q_positions[:, None]
    if window is not None:
        seen = seen & (at[None, :] > q_positions[:, None] - window)
    seen = seen[None]
    if kv_start is not None:
        seen = seen & (at >= kv_start[:, None])[:, None, :]
    return seen if selected is None else seen & selected


def _attn_scores_block(q: jax.Array, k: jax.Array, v: jax.Array,
                       scale: float,
                       q_positions: Optional[jax.Array] = None,
                       kv_start: Optional[jax.Array] = None,
                       kv_positions: Optional[jax.Array] = None,
                       window: Optional[int] = None,
                       selected: Optional[jax.Array] = None) -> jax.Array:
    """One materialized-score attention block (einsum -> fp32 softmax ->
    einsum); with ``q_positions [n]`` under the mask `visible_keys`
    gives; with ``selected`` and no positions under the selection alone
    (the keys were gathered for the query: each is one it may see).

    Under a selection the softmax is normalised BEHIND the product with
    the values (``exp(s - max) v`` summed, then divided by the sum of the
    exponentials, once a query and head and not once a key).  Written the
    usual way, a prefill's ``[4, 4, 1024, 8192]`` scores come out of the
    TPU compiler with the keys on the sublanes and the row maximum as a
    ``reduce-window`` 2 M - 1 wide over them: 21 ms a block where the
    bytes take 2 (PERF.md section 6, PR 42); this way the maximum is an
    output of the score product's own fusion, the exponential is
    recomputed inside the value product's, and the scores are written
    once and read twice.  Every other call keeps the arithmetic it had."""
    logits = jnp.einsum("bnhd,bmhd->bhnm", q, k,
                        preferred_element_type=jnp.float32) * scale
    seen = selected
    if q_positions is not None:
        seen = visible_keys(k.shape[1], q_positions, kv_start, kv_positions,
                            window, selected)
    if seen is not None:
        logits = jnp.where(seen[:, None], logits,
                           jnp.finfo(jnp.float32).min)
    if selected is None:
        weights = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.einsum("bhnm,bmhd->bnhd", weights.astype(v.dtype), v)
    weights = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    out = jnp.einsum("bhnm,bmhd->bnhd", weights.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return (out / jnp.sum(weights, axis=-1).swapaxes(1, 2)[..., None]
            ).astype(v.dtype)


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  scale: float,
                  q_positions: Optional[jax.Array] = None,
                  kv_start: Optional[jax.Array] = None,
                  kv_positions: Optional[jax.Array] = None,
                  window: Optional[int] = None,
                  selected: Optional[jax.Array] = None) -> jax.Array:
    """The reference attention math with a memory ceiling: the path of
    everything `attention_path` does not send to the flash kernel, and
    the oracle the kernel is checked against.

    The fp32 score tensor is [B, H, N, M]; at SDXL 1024px (N=M=4096)
    with a CFG-stacked batch that is 1.3 GB per attention.  Softmax is
    per-QUERY-row, so scanning over query chunks is numerically EXACT (no online rescaling
    needed); each chunk materializes only [B, H, chunk, M].  The chunk
    choice is static (shapes + env), so there is no dynamic control
    flow under jit; ``DTPU_ATTN_SCORES_BYTES`` tunes the ceiling
    (default 512 MB)."""
    B, N, H, D = q.shape
    chunk = _query_chunk(B, N, k.shape[1], H)
    if chunk is None:
        return _attn_scores_block(q, k, v, scale, q_positions, kv_start,
                                  kv_positions, window, selected)
    n_chunks = N // chunk
    qr = q.reshape(B, n_chunks, chunk, H, D).transpose(1, 0, 2, 3, 4)
    pos = None if q_positions is None \
        else q_positions.reshape(n_chunks, chunk)
    # a selection is a mask a QUERY: it is walked with the queries
    sel = None if selected is None else jnp.broadcast_to(
        selected, (B, N, k.shape[1])).reshape(B, n_chunks, chunk, -1
                                              ).swapaxes(0, 1)

    def body(_, qc):
        qc, pc, sc = qc
        return None, _attn_scores_block(qc, k, v, scale, pc, kv_start,
                                        kv_positions, window, sc)

    _, out = jax.lax.scan(body, None, (qr, pos, sel))
    # (values may be narrower than the keys: a latent attention's 128 to 192)
    return out.transpose(1, 0, 2, 3, 4).reshape(B, N, H, -1)


def _maybe_ring_attention(q: jax.Array, k: jax.Array,
                          v: jax.Array) -> Optional[jax.Array]:
    """Ring attention over the runtime mesh's ``seq`` axis when it applies.

    Returns None (-> caller falls back to "xla") when the mesh has no seq
    axis, the token count is below ``DTPU_RING_MIN_TOKENS`` (ring's ICI
    rotation only pays off on long sequences), or either sequence length
    doesn't divide the axis.  All conditions are static shapes/env, so the
    choice is fixed at trace time — no dynamic control flow under jit."""
    import os

    from comfyui_distributed_tpu.parallel.mesh import get_runtime
    from comfyui_distributed_tpu.parallel.ring import ring_attention

    mesh = get_runtime().mesh
    n = int(mesh.shape.get(SEQ_AXIS, 1))
    min_tokens = int(os.environ.get("DTPU_RING_MIN_TOKENS", "256"))
    if (n <= 1 or q.shape[1] < min_tokens
            or q.shape[1] % n or k.shape[1] % n):
        return None
    return ring_attention(q, k, v, mesh)


def geglu_path(platform: str, rows: int, c: int,
               mesh_axes: Optional[dict] = None) -> str:
    """Which implementation `GEGLU` runs for ``rows`` tokens of width
    ``c`` (the batch's and the sequence's together) against ``proj [c,
    8c]``: ``fused`` (the Pallas kernel, ops/pallas/geglu.py: one product
    with its gate on the output side) or ``xla`` (the module as written).

    A function of what the code can see at trace time and nothing else,
    as `attention_path` is: the backend's platform, the operands' static
    shapes and the live mesh's ``{axis: size}`` (None on one device).
    The kernel takes the call on a TPU where the rows and the ``4c``
    columns divide its blocks.  Under a multi-device mesh each chip runs
    it on its own rows (`_geglu_on_mesh`); a live ``tensor`` axis splits
    the hidden columns (rule table "mlp") and a live ``seq`` axis the
    tokens, so there, and where the rows do not divide ``data``, the call
    stays with XLA, which partitions it."""
    from comfyui_distributed_tpu.ops.pallas.geglu import block_sizes

    axes = mesh_axes or {}
    data = axes.get(DATA_AXIS, 1)
    splits = (axes.get(SEQ_AXIS, 1) == 1 and axes.get(TENSOR_AXIS, 1) == 1
              and rows % data == 0)
    if platform == "tpu" and splits \
            and block_sizes(rows // data, c, 4 * c) is not None:
        return "fused"
    return "xla"


def _geglu_on_mesh(x: jax.Array, kernel: jax.Array,
                   bias: Optional[jax.Array], mesh) -> jax.Array:
    """The GEGLU kernel, each chip on its own rows: XLA cannot partition
    a Mosaic custom call (`_fused_on_mesh`), so under a multi-device mesh
    the call goes through ``jax.shard_map``, batch rows over ``data`` and
    the weight whole on every chip, as it is held.  A batch that does not
    divide ``data`` is replicated there, and stays so."""
    from comfyui_distributed_tpu.ops.pallas.geglu import geglu

    if mesh is None:
        return geglu(x, kernel, bias)
    data = int(mesh.shape.get(DATA_AXIS, 1))
    rows = shd.mesh_spec(DATA_AXIS if x.shape[0] % data == 0 else None,
                         *[None] * (x.ndim - 1))
    whole = shd.mesh_spec()
    return jax.shard_map(geglu, mesh=mesh, in_specs=(rows, whole, whole),
                         out_specs=rows, check_vma=False)(x, kernel, bias)


class GEGLU(nn.Module):
    """``proj`` to ``2 * dim_out`` columns, then the first half times the
    exact (erf) gelu of the second.  `geglu_path` decides per call site,
    at trace time, whether that is one fused product (a TPU) or the
    module as written; either way the one leaf ``proj`` keeps its
    published name, shape and layout.  Each call site counts the path it
    took, once (``geglu_paths`` on ``GET /distributed/metrics``)."""
    dim_out: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        proj = nn.Dense(self.dim_out * 2, dtype=self.dtype, name="proj")
        c = x.shape[-1]
        mesh = _live_mesh()
        # the kernel reads the leaf the Dense made: while a model is being
        # initialised there is none yet, and that trace is no call site
        path = "xla" if self.is_initializing() or self.dim_out != 4 * c \
            else geglu_path(jax.default_backend(), x.size // c, c,
                            dict(mesh.shape) if mesh is not None else None)
        if not self.is_initializing():
            GEGLU_PATHS.bump(path)
        if path == "fused":
            leaf = self.get_variable("params", "proj")
            x, kernel, bias = nn.dtypes.promote_dtype(
                x, leaf["kernel"], leaf["bias"], dtype=self.dtype)
            # Behind a conditional, for the convolutions' sake.  The TPU
            # compiler rewrites the UNet's 3x3 convolutions space-to-batch
            # (2 x 64 x 64 becomes 64 tiles of 16 x 9) and gives that up
            # for every convolution whose result reaches a custom call
            # through at most one product.  The residual stream runs from
            # each ResBlock through ``proj_in`` into every GEGLU, so with a
            # bare kernel call here the convolutions lost 0.33 s a SDXL
            # denoise where the kernel won 0.22 (PERF.md §6, PR 39).  A
            # conditional is opaque to that search.  Its predicate cannot
            # be folded (a NaN is not equal to itself) and holds for every
            # bias that is a number; the other branch is the module as
            # written, which is as right for one that is not.
            from comfyui_distributed_tpu.ops.pallas.geglu import xla_geglu
            return jax.lax.cond(
                bias[0] == bias[0],
                lambda: _geglu_on_mesh(x, kernel, bias, mesh),
                lambda: xla_geglu(x, kernel, bias))
        h = proj(x)
        # column-split ffn hidden over the tensor axis (rule table "mlp");
        # the gate/value halves split at dim_out, which is also a shard
        # boundary for any tensor size dividing dim_out
        h = shd.constrain(h, "batch", None, "mlp")
        a, b = jnp.split(h, 2, axis=-1)
        # exact (erf) gelu: torch F.gelu's default, what SD was trained
        # with — flax's default tanh approximation drifts ~1e-3
        return a * nn.gelu(b, approximate=False)


class FeedForward(nn.Module):
    mult: int = 4
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c = x.shape[-1]
        h = GEGLU(dim_out=c * self.mult, dtype=self.dtype, name="geglu")(x)
        return nn.Dense(c, dtype=self.dtype, name="out")(h)


class GatedSelfAttention(nn.Module):
    """GLIGEN fuser (GatedSelfAttentionDense): self-attention over
    [visual tokens; grounding tokens] and a FF, each gated by a learned
    tanh(alpha) scalar so an untrained fuser starts as a near-no-op.
    Grounding tokens project from their 768-d space to the block width
    first (the reference layout's ``linear``)."""
    num_heads: int
    dtype: Dtype = jnp.bfloat16
    attn_impl: str = "xla"

    @nn.compact
    def __call__(self, x: jax.Array, objs: jax.Array) -> jax.Array:
        n = x.shape[1]
        o = nn.Dense(x.shape[-1], dtype=self.dtype, name="linear")(objs)
        alpha_attn = self.param("alpha_attn", nn.initializers.zeros, ())
        alpha_dense = self.param("alpha_dense", nn.initializers.zeros,
                                 ())
        h = jnp.concatenate([x, o.astype(x.dtype)], axis=1)
        h = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="norm1")(h)
        att = Attention(self.num_heads, dtype=self.dtype,
                        attn_impl=self.attn_impl,
                        name="attn")(h)[:, :n]
        x = x + jnp.tanh(alpha_attn).astype(x.dtype) * att
        h = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="norm2")(x)
        x = x + jnp.tanh(alpha_dense).astype(x.dtype) \
            * FeedForward(dtype=self.dtype, name="ff")(h)
        return x


class TransformerBlock(nn.Module):
    """Self-attn -> cross-attn -> FF, pre-LN residuals (SD spatial
    transformer block layout)."""
    num_heads: int
    dtype: Dtype = jnp.bfloat16
    attn_impl: str = "xla"
    sow_probs: bool = False        # SAG: capture attn1's softmax weights
    # ToMe: merge this fraction of attn1's QUERY tokens into their most
    # similar destinations (models/tome.py); needs the token grid dims
    tome_ratio: float = 0.0
    hw: Optional[tuple] = None
    gligen: int = 0      # >0: create the GLIGEN fuser (grounding dim)

    @nn.compact
    def __call__(self, x: jax.Array, context: Optional[jax.Array],
                 context_v: Optional[jax.Array] = None,
                 objs: Optional[jax.Array] = None) -> jax.Array:
        xn = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32,
                          name="norm1")(x)
        attn1 = Attention(self.num_heads, dtype=self.dtype,
                          attn_impl=self.attn_impl,
                          sow_probs=self.sow_probs, name="attn1")
        if (self.tome_ratio > 0.0 and self.hw is not None
                and not self.sow_probs):
            from comfyui_distributed_tpu.models.tome import build_merge
            th, tw = self.hw
            merge, unmerge, r = build_merge(
                xn.astype(jnp.float32), th, tw, self.tome_ratio)
            if r > 0:
                # merged queries attend the FULL token set (k/v
                # unmerged, the reference's attn1 patch): kept tokens'
                # outputs are exact, merged ones adopt their dst's
                x = x + unmerge(attn1(merge(xn), context=xn))
            else:
                x = x + attn1(xn)
        else:
            x = x + attn1(xn)
        if self.gligen:
            # GLIGEN fuser between attn1 and attn2 (the reference's
            # insertion point); zero grounding tokens + zero-init gates
            # make the untrained/unused case a near-no-op
            o = objs if objs is not None \
                else jnp.zeros((x.shape[0], 1, int(self.gligen)),
                               x.dtype)
            x = GatedSelfAttention(self.num_heads, dtype=self.dtype,
                                   attn_impl=self.attn_impl,
                                   name="fuser")(x, o)
        x = x + Attention(self.num_heads, dtype=self.dtype,
                          attn_impl=self.attn_impl, name="attn2")(
            nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="norm2")(x), context=context,
            context_v=context_v)
        x = x + FeedForward(dtype=self.dtype, name="ff")(
            nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="norm3")(x))
        return x


def _hypertile_divisor(n: int, min_tile: int) -> int:
    """Largest divisor d of n with n // d >= min_tile (the most tiling
    that keeps tiles at least ``min_tile`` on a side).  Static shapes:
    deterministic, unlike the reference ecosystem's random divisor."""
    best = 1
    for d in range(1, n + 1):
        if n % d == 0 and n // d >= min_tile:
            best = d
    return best


class SpatialTransformer(nn.Module):
    """Project NHWC feature map to tokens, run transformer blocks with
    text cross-attention, project back (SD UNet attention block).

    ``hypertile_tile`` > 0 (HyperTile patch): the token grid splits into
    spatial tiles of >= that many latent units per side, riding the
    BATCH axis through the blocks — self-attention then costs
    O(tiles * (N/tiles)^2).  Cross-attention and the FF are per-token /
    per-query, so tiling changes nothing for them (context repeats per
    tile); only self-attention is approximated, by construction."""
    num_heads: int
    depth: int = 1
    dtype: Dtype = jnp.bfloat16
    attn_impl: str = "xla"
    hypertile_tile: int = 0
    sow_probs: bool = False        # SAG: first block's attn1 sows
    tome_ratio: float = 0.0        # ToMe query merging (models/tome.py)
    gligen: int = 0                # GLIGEN fusers (grounding dim)

    @nn.compact
    def __call__(self, x: jax.Array, context: Optional[jax.Array],
                 context_v: Optional[jax.Array] = None,
                 objs: Optional[jax.Array] = None) -> jax.Array:
        B, H, W, C = x.shape
        # CompVis attention.py Normalize: GroupNorm eps=1e-6 (the UNet's
        # ResBlock GroupNorm32 uses torch's 1e-5 default instead)
        h = GroupNorm32(epsilon=1e-6, name="norm")(x)
        h = nn.Dense(C, dtype=self.dtype, name="proj_in")(h)
        nh = nw = 1
        if self.hypertile_tile > 0:
            nh = _hypertile_divisor(H, self.hypertile_tile)
            nw = _hypertile_divisor(W, self.hypertile_tile)
        ctx = context
        ctx_v = context_v
        if nh * nw > 1:
            th, tw = H // nh, W // nw
            h = h.reshape(B, nh, th, nw, tw, C) \
                .transpose(0, 1, 3, 2, 4, 5) \
                .reshape(B * nh * nw, th * tw, C)
            if context is not None:
                ctx = jnp.repeat(context, nh * nw, axis=0)
            if context_v is not None:
                ctx_v = jnp.repeat(context_v, nh * nw, axis=0)
            if objs is not None:
                objs = jnp.repeat(objs, nh * nw, axis=0)
        else:
            h = h.reshape(B, H * W, C)
        th, tw = (H // nh, W // nw) if nh * nw > 1 else (H, W)
        for i in range(self.depth):
            h = TransformerBlock(self.num_heads, dtype=self.dtype,
                                 attn_impl=self.attn_impl,
                                 sow_probs=self.sow_probs and i == 0,
                                 tome_ratio=self.tome_ratio,
                                 hw=(th, tw), gligen=self.gligen,
                                 name=f"blocks_{i}")(h, ctx,
                                                     context_v=ctx_v,
                                                     objs=objs)
        if nh * nw > 1:
            th, tw = H // nh, W // nw
            h = h.reshape(B, nh, nw, th, tw, C) \
                .transpose(0, 1, 3, 2, 4, 5) \
                .reshape(B, H, W, C)
        else:
            h = h.reshape(B, H, W, C)
        h = nn.Dense(C, dtype=self.dtype, name="proj_out")(h)
        return x + h


class ResBlock(nn.Module):
    """UNet residual block with timestep-embedding injection."""
    out_channels: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, emb: jax.Array) -> jax.Array:
        h = GroupNorm32(name="in_norm")(x)
        h = nn.silu(h)
        h = nn.Conv(self.out_channels, (3, 3), padding=1, dtype=self.dtype,
                    name="in_conv")(h)
        eproj = nn.Dense(self.out_channels, dtype=self.dtype,
                         name="emb_proj")(nn.silu(emb))
        h = h + eproj[:, None, None, :]
        h = GroupNorm32(name="out_norm")(h)
        h = nn.silu(h)
        h = nn.Conv(self.out_channels, (3, 3), padding=1, dtype=self.dtype,
                    name="out_conv")(h)
        if x.shape[-1] != self.out_channels:
            x = nn.Conv(self.out_channels, (1, 1), dtype=self.dtype,
                        name="skip")(x)
        return x + h


class Downsample(nn.Module):
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        return nn.Conv(x.shape[-1], (3, 3), strides=(2, 2), padding=1,
                       dtype=self.dtype, name="conv")(x)


class Upsample(nn.Module):
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        B, H, W, C = x.shape
        x = jax.image.resize(x, (B, H * 2, W * 2, C), method="nearest")
        return nn.Conv(C, (3, 3), padding=1, dtype=self.dtype, name="conv")(x)
