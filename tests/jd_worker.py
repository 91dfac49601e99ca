"""Child process for the jax.distributed multi-host integration test.

Joins the cluster through the framework's own entry points
(``force_cpu_platform`` + ``initialize_multihost`` + ``build_mesh``) —
the same path ``cli.py serve/worker/run`` takes on a real multi-host pod,
with CPU devices standing in for chips and gRPC/Gloo for DCN.  Runs a
cross-process psum and all_gather over the mesh's data axis and prints
JD_OK when the values prove both processes contributed.
"""

import numpy as np

from comfyui_distributed_tpu.parallel.mesh import (
    build_mesh,
    force_cpu_platform,
    initialize_multihost,
)

force_cpu_platform(2)          # 2 local devices/process -> 4 global
initialize_multihost()         # DTPU_COORDINATOR/_NUM_PROCESSES/_PROCESS_ID

import jax                     # noqa: E402  (after platform pin)
import jax.numpy as jnp        # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 4, jax.device_count()
assert jax.local_device_count() == 2

mesh = build_mesh({"data": 4})
sh = NamedSharding(mesh, P("data"))

# per-process payload: process 0 contributes 1s, process 1 contributes 2s
local = np.full((jax.local_device_count(), 4),
                float(jax.process_index() + 1), np.float32)
x = jax.make_array_from_process_local_data(sh, local)


def f(xs):
    total = jax.lax.psum(xs, "data")                   # cross-host reduce
    gathered = jax.lax.all_gather(xs, "data", axis=0)  # cross-host gather
    return total, gathered


total, gathered = jax.jit(
    jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                  out_specs=(P(), P("data"))))(x)

tv = np.asarray(jax.device_get(total.addressable_data(0)))
assert np.allclose(tv, 1 + 1 + 2 + 2), tv  # both processes contributed
gv = np.asarray(jax.device_get(gathered.addressable_data(0))).reshape(4, 4)
assert sorted(gv[:, 0].tolist()) == [1.0, 1.0, 2.0, 2.0], gv[:, 0]

print("JD_OK", flush=True)
