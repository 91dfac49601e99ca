"""Collective data plane.

TPU-native replacement for the reference's entire HTTP+PNG data plane
(SURVEY.md §2.4): scatter = batch sharding over the ``data`` mesh axis,
gather = XLA ``all_gather`` riding ICI, ordering = mesh axis order.  Tensors
never leave HBM; there is no serialization, no queue, no timeout-per-image.

Reference semantics preserved:
- seed fan-out: worker *i* samples with ``seed + i + 1``, master with ``seed``
  (``DistributedSeed.distribute``, reference ``distributed.py:1491-1514``) —
  here replica ``r`` uses ``seed + r`` with ``r = 0`` the master slot.
- collection order: master images first, then workers sorted by id
  (reference ``distributed.py:1424-1438``) — here simply the data-axis order.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from comfyui_distributed_tpu.parallel import sharding as shd
from comfyui_distributed_tpu.utils.constants import DATA_AXIS


def replica_seeds(base_seed: int, num_replicas: int,
                  batch_per_replica: int = 1) -> np.ndarray:
    """Per-sample seed array for a fanned-out batch.

    Replica ``r`` (0 = master) uses ``base_seed + r`` for every image in its
    sub-batch — semantic parity with the reference's ``seed`` /
    ``seed + worker_index + 1`` split (``distributed.py:1505-1508``), where
    our ``r`` enumerates master (0) then workers (1..N).  Shape:
    ``[num_replicas * batch_per_replica]``, replica-major — i.e. exactly the
    master-first gather order of reference ``distributed.py:1424-1438``."""
    seeds = np.arange(num_replicas, dtype=np.uint64) + np.uint64(base_seed)
    return np.repeat(seeds, batch_per_replica)


def sample_keys(seeds: jnp.ndarray) -> jnp.ndarray:
    """Fold per-sample indices into per-replica seeds so each image in a
    replica's sub-batch gets an independent stream (canonical impl lives
    with the samplers)."""
    from comfyui_distributed_tpu.models.samplers import sample_keys as _sk
    return _sk(seeds)


def shard_batch(x: Any, mesh: Mesh, spec: Optional[P] = None) -> jax.Array:
    """Scatter: place a host array on the mesh, batch dim over ``data``.

    The analog of the reference's dispatch fan-out (POST the workflow to every
    worker, ``gpupanel.js:1313-1362``) — except no data moves per-participant;
    XLA lays each shard directly into its device's HBM."""
    spec = spec if spec is not None else shd.mesh_spec(DATA_AXIS)
    return shd.put_on_mesh(x, mesh, spec)


def gather_batch(x: jax.Array) -> np.ndarray:
    """Gather: fetch a (possibly sharded) array to host, preserving axis
    order — the analog of the reference's collector drain + ordered
    ``torch.cat`` (``distributed.py:1281-1459``), with ordering guaranteed by
    construction instead of by sorting worker ids.  This is a device->host
    EDGE and is counted as such (utils.trace)."""
    from comfyui_distributed_tpu.utils.trace import record_transfer
    arr = np.asarray(jax.device_get(x))
    record_transfer("d2h", arr.nbytes)
    return arr


def all_gather_data(x: jax.Array, mesh: Mesh) -> jax.Array:
    """In-program all-gather over the data axis: every participant ends up
    with the full batch (what the reference cannot do — its workers never see
    each other's results)."""
    def f(shard):
        return jax.lax.all_gather(shard, DATA_AXIS, axis=0, tiled=True)
    # check_vma=False: replication over the unused tensor/seq axes (size 1)
    # can't be statically inferred by shard_map's rep checker.
    return jax.shard_map(f, mesh=mesh, in_specs=shd.mesh_spec(DATA_AXIS),
                         out_specs=shd.mesh_spec(), check_vma=False)(x)


def psum_data(x: jax.Array, mesh: Mesh) -> jax.Array:
    """Sum-reduce over the data axis (building block for overlap-add tile
    gathering and for gradient reduction in the train step)."""
    def f(shard):
        return jax.lax.psum(shard, DATA_AXIS)
    return jax.shard_map(f, mesh=mesh, in_specs=shd.mesh_spec(DATA_AXIS),
                         out_specs=shd.mesh_spec(), check_vma=False)(x)


def pad_to_multiple(n: int, m: int) -> int:
    """Smallest multiple of ``m`` >= ``n`` — SPMD needs equal shards where the
    reference tolerated ragged per-worker tile counts via Python loops
    (``distributed_upscale.py:344-357``); we pad-and-mask instead."""
    return ((n + m - 1) // m) * m


def device_put_replicated(x: Any, mesh: Mesh) -> jax.Array:
    return shd.put_on_mesh(x, mesh, shd.mesh_spec())
