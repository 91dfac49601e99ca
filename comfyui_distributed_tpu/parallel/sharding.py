"""Sharding rules: how params and activations lay out over the mesh.

The reference has no model parallelism — every participant holds a full model
copy (its README requires identical checkpoints on all machines,
``/root/reference/README.md:189-193``).  On TPU, tensor parallelism is nearly
free to offer because it is *layout, not code*: we annotate parameter and
activation shardings with :class:`jax.sharding.NamedSharding` and GSPMD
inserts the collectives.  This module centralises those annotations:

- **dp** — batch dims over the ``data`` axis (the reference's worker axis);
- **tp** — weight matrices over the ``tensor`` axis (output-feature dim of
  large kernels; megatron-style column split, with XLA choosing the matching
  row splits/reductions);
- **sp** — token/sequence dims over the ``seq`` axis (context tensors and
  attention inputs; ring attention in :mod:`.ring` keeps the shards resident).

Rules are shape-driven rather than name-driven so they apply uniformly to any
param tree (UNet, CLIP, VAE) without per-module tables.  The language model's
tree (models/looplm.py) takes them as it is: its layers' leaves carry a
leading ``L`` axis that is never split (a kernel ``[L, in, out]`` is
column-split over ``tensor`` like any ``[in, out]``), everything is a full
replica over ``data``, and its q/k/v and MLP activations name the same
logical axes (``"heads"``, ``"mlp"``) the UNet's do.  The expert model's
routed experts (models/mla_moe.py, leaves ``experts/* [L, E_here, in, out]``)
are the one exception to "shape-driven": their second axis is the logical
axis ``"expert"``, which no mesh the repo runs splits yet (a chip is told
which experts it holds and runs without the exchange), and the leaf is NOT
column-split as if ``E_here`` were a batch of kernels.

Activation placement (ISSUE 16) goes through a **logical-axis rule table**
instead of hand-built specs: model code names what a dim *is* (``"batch"``,
``"heads"``, ``"mlp"``, ``"seq"``) and :func:`constrain` resolves it against
:data:`LOGICAL_AXIS_RULES` + the live mesh, engaging only when a tensor axis
is actually up.  This module is the ONLY place in the package that may build
a raw :class:`PartitionSpec`/:class:`NamedSharding` — dtpu-lint's
``tp-spec-discipline`` rule holds every other module to the table.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from comfyui_distributed_tpu.utils.constants import DATA_AXIS, SEQ_AXIS, TENSOR_AXIS

# Don't bother sharding tensors smaller than this many elements: the gather
# traffic would cost more than the HBM saved.
MIN_SHARD_ELEMENTS = 2 ** 11

# --- logical-axis rule table --------------------------------------------------
#
# Model code annotates dims with *logical* names; this table maps them onto
# mesh axes.  One table for the whole package means retargeting the layout
# (e.g. sharding "mlp" over a combined axis on a bigger slice) is a one-line
# change here, not a hunt through every module.

LOGICAL_BATCH = "batch"   # per-image rows (the reference's worker axis)
LOGICAL_HEADS = "heads"   # attention heads (megatron: split across tensor)
LOGICAL_MLP = "mlp"       # feed-forward hidden features (column split)
LOGICAL_SEQ = "seq"       # token axis (ring attention / sp)
LOGICAL_EXPERT = "expert"  # routed experts (expert parallelism)

LOGICAL_AXIS_RULES = {
    LOGICAL_BATCH: DATA_AXIS,
    LOGICAL_HEADS: TENSOR_AXIS,
    LOGICAL_MLP: TENSOR_AXIS,
    LOGICAL_SEQ: SEQ_AXIS,
    # unsplit on every mesh the repo runs today: each chip of an
    # expert-parallel deployment is a server of its own that holds its
    # experts whole, and no program has the all-to-all a split would need
    LOGICAL_EXPERT: None,
}

# a parameter leaf under this key carries the expert axis behind its
# leading layer axis: ``[L, E_here, in, out]``
EXPERT_LEAVES = "['experts']"


def mesh_spec(*parts: Optional[str]) -> P:
    """Raw mesh-axis PartitionSpec — the package's single constructor.

    Entries are mesh axis names (``data``/``tensor``/``seq``) or None.
    Modules that genuinely speak mesh axes (shard_map in/out specs in
    collectives/ring) build their specs here instead of importing
    PartitionSpec themselves, keeping the lint discipline airtight."""
    return P(*parts)


def logical_spec(*logical: Optional[str]) -> P:
    """Resolve logical dim names through the rule table into a PartitionSpec.

    Each entry is a :data:`LOGICAL_AXIS_RULES` key or None (replicated dim).
    Unknown names raise — a typo'd logical axis must not silently replicate."""
    parts = []
    for name in logical:
        if name is None:
            parts.append(None)
            continue
        if name not in LOGICAL_AXIS_RULES:
            raise ValueError(
                f"unknown logical axis {name!r}; known: "
                f"{sorted(LOGICAL_AXIS_RULES)}")
        parts.append(LOGICAL_AXIS_RULES[name])
    return P(*parts)


def batch_axis_spec(ndim: int, batch_dim: int = 0) -> P:
    """Rows-on-``data`` spec for an ``ndim``-rank array: the bucket/batch
    layout (everything but the batch dim replicated)."""
    parts: list = [None] * ndim
    parts[batch_dim] = DATA_AXIS
    return P(*parts)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    """The package's single NamedSharding constructor."""
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def put_on_mesh(x: Any, mesh: Mesh, spec: P) -> Any:
    """device_put one array onto the mesh with an explicit spec — the
    MeshHelper-style chokepoint for host->mesh placement."""
    return jax.device_put(x, NamedSharding(mesh, spec))


def mesh_of(x: Any) -> Optional[Mesh]:
    """The multi-device mesh an array is laid out over, else None (host
    arrays, single-device arrays, one-device meshes)."""
    mesh = getattr(getattr(x, "sharding", None), "mesh", None)
    return mesh if isinstance(mesh, Mesh) and mesh.size > 1 else None


def serving_mesh() -> Optional[Mesh]:
    """The live runtime's mesh IFF tensor parallelism is engaged (a built
    runtime whose ``tensor`` axis is > 1); None otherwise.  The gate every
    activation constraint and the CB bucket layout share with
    ``DiffusionPipeline._ensure_tp_sharded`` — on pure data-parallel meshes
    (every pre-ISSUE-16 configuration) all of it stays inert, so the
    single-chip and dp-only paths compile exactly the HLO they always did."""
    from comfyui_distributed_tpu.parallel.mesh import get_live_runtime
    rt = get_live_runtime()
    if rt is None or getattr(rt, "mesh", None) is None:
        return None
    mesh = rt.mesh
    if int(mesh.shape.get(TENSOR_AXIS, 1)) <= 1:
        return None
    return mesh


def _resolve_constraint(mesh: Mesh, shape: Sequence[int],
                        logical: Sequence[Optional[str]]) -> Optional[P]:
    """Logical names -> a spec valid for ``shape`` on ``mesh``: axes whose
    mesh size is 1 or that don't divide the dim drop to replicated (shapes
    are static under trace, so this is a trace-time decision — e.g. a
    pad-1 bucket keeps its rows replicated while pad-4 rows ride ``data``).
    Returns None when nothing shards (skip the constraint entirely)."""
    parts: list = []
    any_sharded = False
    for dim, name in enumerate(logical):
        ax = LOGICAL_AXIS_RULES.get(name) if name is not None else None
        if ax is None:
            parts.append(None)
            continue
        size = int(mesh.shape.get(ax, 1))
        if size > 1 and int(shape[dim]) % size == 0:
            parts.append(ax)
            any_sharded = True
        else:
            parts.append(None)
    return P(*parts) if any_sharded else None


def constrain(x: Any, *logical: Optional[str]) -> Any:
    """with_sharding_constraint through the rule table (SNIPPETS [1]-[3]
    pattern): ``constrain(q, "batch", None, "heads", None)``.

    No-op unless :func:`serving_mesh` reports an engaged tensor axis, and
    per-dim no-op when the mesh axis wouldn't divide the dim.  Safe inside
    jit — all gates are trace-time (jit re-lowers when input shardings
    change, so a mesh coming up between calls is a fresh trace anyway)."""
    mesh = serving_mesh()
    if mesh is None:
        return x
    if len(logical) != x.ndim:
        raise ValueError(f"constrain got {len(logical)} logical axes for a "
                         f"rank-{x.ndim} array")
    spec = _resolve_constraint(mesh, x.shape, logical)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_rows(x: Any) -> Any:
    """Rows-on-``data``, everything else replicated — the canonical layout
    of a CB bucket batch, AND the replicate-before-concat workaround for
    **tp-concat-cpu-miscompile** (ROADMAP item 8): XLA's CPU SPMD partitioner
    miscompiles ``concatenate`` when one operand is tensor-sharded along the
    concat dim and the other replicated (both output halves wrong, upstream
    repro in tests/test_parallel.py).  Constraining both operands here forces
    the gather BEFORE the concat while keeping batch rows on ``data``."""
    mesh = serving_mesh()
    if mesh is None:
        return x
    spec = _resolve_constraint(mesh, x.shape,
                               (LOGICAL_BATCH,) + (None,) * (x.ndim - 1))
    if spec is None:
        spec = P()  # still dissolve any tensor sharding on the other dims
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def replicate(x: Any) -> Any:
    """Pin fully replicated (engaged mesh only) — the concat-dim firewall.
    with_sharding_constraint is a hard pin: consumer-side propagation
    cannot push a sharding back through it, which is exactly what the
    concat workarounds below need."""
    mesh = serving_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


def stack_rows(parts: Sequence[Any], axis: int = 0) -> Any:
    """Concatenate along the batch/row dim WITHOUT sharding the concat dim
    (tp-concat-cpu-miscompile, ROADMAP item 8): XLA's CPU SPMD partitioner
    miscompiles a concatenate whose concat dim carries a mesh axis — on the
    CFG row-stack the operand seams land mid-shard after the reshuffle.
    Pin every operand AND the result replicated so neither operand layouts
    nor consumer back-propagation (e.g. an attention "batch" constraint
    downstream) can shard the concat itself.  Inert without an engaged
    tensor axis."""
    mesh = serving_mesh()
    if mesh is None:
        return jnp.concatenate(list(parts), axis=axis)
    return replicate(jnp.concatenate([replicate(p) for p in parts],
                                     axis=axis))


def unstack_rows(out: Any, reps: int) -> list:
    """split's dual of :func:`stack_rows`: gather the CFG-stacked model
    output before slicing it back into per-side blocks, so the split seams
    never cross a shard boundary."""
    return jnp.split(replicate(out), reps, axis=0)


def rows_sharding(mesh: Mesh, rows: int, ndim: int) -> NamedSharding:
    """Placement for a rows-leading array: dim 0 over ``data`` when the row
    count divides the axis, fully replicated otherwise (device_put — unlike
    with_sharding_constraint — refuses uneven shards, and pad-1 buckets on a
    data=2 mesh are legal)."""
    if int(mesh.shape.get(DATA_AXIS, 1)) > 1 \
            and rows % int(mesh.shape[DATA_AXIS]) == 0:
        return NamedSharding(mesh, batch_axis_spec(ndim))
    return NamedSharding(mesh, P())


def put_rows(x: Any, mesh: Mesh) -> Any:
    """Normalize a rows-leading array onto its canonical bucket layout.
    Also the chokepoint the CB executor uses after repads/writes so every
    steady-state step sees ONE input sharding per pad (anything else would
    re-lower the step executable and break the zero-retrace invariant)."""
    return jax.device_put(x, rows_sharding(mesh, int(x.shape[0]), x.ndim))


# --- parameter layout ---------------------------------------------------------

def param_spec(path: str, shape: tuple, tensor_size: int,
               min_elements: int = MIN_SHARD_ELEMENTS) -> P:
    """PartitionSpec for one parameter leaf.

    Megatron-style column parallelism by shape heuristic: shard the trailing
    (output-feature) dim of rank>=2 kernels over ``tensor`` when divisible;
    fall back to the second-to-last (input-feature) dim; replicate biases,
    norm scales, and anything too small to be worth the traffic.
    """
    if tensor_size <= 1 or len(shape) < 2:
        return P()
    if EXPERT_LEAVES in path:
        # split by experts or not at all: never by columns
        return logical_spec(None, LOGICAL_EXPERT, None, None)
    n = 1
    for d in shape:
        n *= d
    if n < min_elements:
        return P()
    none_prefix = [None] * (len(shape) - 1)
    if shape[-1] % tensor_size == 0:
        return P(*none_prefix, TENSOR_AXIS)
    if shape[-2] % tensor_size == 0:
        return P(*none_prefix[:-1], TENSOR_AXIS, None)
    return P()


def param_sharding(mesh: Mesh, path: str, shape: tuple,
                   min_elements: int = MIN_SHARD_ELEMENTS) -> NamedSharding:
    """NamedSharding for one parameter leaf on ``mesh`` (the train-step and
    optimizer layout entry point)."""
    return NamedSharding(mesh, param_spec(
        path, shape, int(mesh.shape[TENSOR_AXIS]), min_elements))


def params_shardings(params: Any, mesh: Mesh,
                     min_elements: int = MIN_SHARD_ELEMENTS) -> Any:
    """NamedSharding tree matching ``params`` — tp over ``tensor``, replicated
    over ``data``/``seq`` (dp keeps full replicas, exactly the reference's
    every-worker-loads-the-checkpoint model, just within one program)."""
    tensor_size = mesh.shape[TENSOR_AXIS]

    def leaf(path, x):
        spec = param_spec(jax.tree_util.keystr(path), tuple(x.shape),
                          tensor_size, min_elements)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf, params)


def batch_spec(ndim: int, seq_dim: Optional[int] = None) -> P:
    """Activation spec: dim 0 over ``data``; optionally one dim over ``seq``
    (for token axes — sequence parallelism)."""
    parts = [DATA_AXIS] + [None] * (ndim - 1)
    if seq_dim is not None and 0 < seq_dim < ndim:
        parts[seq_dim] = SEQ_AXIS
    return P(*parts)


def batch_shardings(tree: Any, mesh: Mesh, seq_dims: Optional[dict] = None) -> Any:
    """NamedSharding tree for a batch pytree (dict of arrays).  ``seq_dims``
    maps top-level key -> which dim is the token axis (sp)."""
    seq_dims = seq_dims or {}

    def leaf(path, x):
        key = path[0].key if path and hasattr(path[0], "key") else None
        return NamedSharding(mesh, batch_spec(x.ndim, seq_dims.get(key)))

    return jax.tree_util.tree_map_with_path(leaf, tree)


def apply_shardings(tree: Any, shardings: Any) -> Any:
    """device_put a pytree onto its sharding tree."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), tree, shardings)


def spec_of(x: Any) -> Optional[P]:
    """The PartitionSpec an array actually carries (None when it has no
    NamedSharding) — the bench/test probe for per-array spec assertions."""
    s = getattr(x, "sharding", None)
    return getattr(s, "spec", None)
