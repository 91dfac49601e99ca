"""Mesh runtime: device/topology discovery and mesh construction.

TPU-native replacement for the reference's worker topology.  Where the
reference spawns one ComfyUI process per CUDA device and tracks them in
``gpu_config.json`` (``WorkerProcessManager``, reference
``distributed.py:603-1021``), a TPU slice exposes all local chips to one
process; "cluster membership" becomes the shape of a
:class:`jax.sharding.Mesh`.  The reference's *enabled workers* toggle maps to
``data_parallel_size`` — how many mesh slots participate in a fan-out run.

Axes (see ``utils/constants.py``):
    data    replica fan-out + tile scatter (reference's worker axis)
    tensor  intra-op model parallelism (no reference analog; TPU extension)
    seq     sequence/context parallelism for ring attention
"""

from __future__ import annotations

import dataclasses
import math
import os
import threading
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from comfyui_distributed_tpu.utils.constants import (
    DATA_AXIS, MESH_SHAPE_ENV, SEQ_AXIS, TENSOR_AXIS, TP_ENV)
from comfyui_distributed_tpu.utils.logging import debug_log, log

AXIS_ORDER = (DATA_AXIS, TENSOR_AXIS, SEQ_AXIS)


def force_cpu_platform(n_devices: int) -> int:
    """Pin JAX to ``n_devices`` virtual CPU devices without initialising
    any other backend (tests, CPU bench phases, the multichip dry run).

    Works after a CPU backend already initialised with a different device
    count: the backends are cleared first so the new count applies.
    Returns ``n_devices``."""
    import jax.extend as jex
    jex.backend.clear_backends()
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    return n_devices


def cpu_requested() -> bool:
    """True when the operator asked for the CPU (``JAX_PLATFORMS=cpu``)."""
    return (os.environ.get("JAX_PLATFORMS") or "").strip().lower() == "cpu"


def require_backend() -> Dict[str, Any]:
    """Initialise the backend in THIS process and refuse a platform the
    operator did not ask for.

    A chip belongs to one process, so there is no probe in a child: the
    first ``jax.devices()`` here is the process's one TPU initialisation.
    JAX falls back to the CPU on its own when no accelerator comes up; a
    server that then answers at CPU speed and exits 0 hides the device, so
    unless ``JAX_PLATFORMS=cpu`` was given, anything but ``tpu`` ends the
    process non-zero with the reason.  Returns the device summary."""
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}
    if info["platform"] != "tpu" and not cpu_requested():
        raise SystemExit(
            f"no TPU: JAX came up on {info['platform']!r} "
            f"({info['count']} x {info['kind']}). Refusing to run on a "
            f"platform nobody asked for; set JAX_PLATFORMS=cpu to run on "
            f"the CPU on purpose.")
    log(f"backend: {info['count']} x {info['kind']} ({info['platform']})")
    return info


def describe_devices(devices: Optional[Sequence[jax.Device]] = None) -> Dict[str, Any]:
    """Topology discovery — the TPU analog of the reference's worker/CUDA
    enumeration (``CUDA_VISIBLE_DEVICES`` handling, reference
    ``distributed.py:672-677``).  Reports platform, counts, per-device
    metadata and multi-host process info."""
    devices = list(devices) if devices is not None else jax.devices()
    descr: List[Dict[str, Any]] = []
    for d in devices:
        entry: Dict[str, Any] = {
            "id": d.id,
            "platform": d.platform,
            "kind": getattr(d, "device_kind", "unknown"),
            "process_index": d.process_index,
        }
        coords = getattr(d, "coords", None)
        if coords is not None:
            entry["coords"] = tuple(coords)
        descr.append(entry)
    return {
        "platform": devices[0].platform if devices else "none",
        "num_devices": len(devices),
        "num_local_devices": jax.local_device_count(),
        "num_processes": jax.process_count(),
        "process_index": jax.process_index(),
        "devices": descr,
    }


def _resolve_axes(axes: Dict[str, int], n_devices: int) -> Dict[str, int]:
    """Resolve -1 ("fill with remaining devices") and validate the product."""
    resolved = {name: int(axes.get(name, 1)) for name in AXIS_ORDER}
    fills = [n for n, v in resolved.items() if v == -1]
    if len(fills) > 1:
        raise ValueError(f"only one axis may be -1, got {fills}")
    fixed = math.prod(v for v in resolved.values() if v != -1)
    if fills:
        if n_devices % fixed != 0:
            raise ValueError(
                f"fixed axes product {fixed} does not divide {n_devices} devices")
        resolved[fills[0]] = n_devices // fixed
    total = math.prod(resolved.values())
    if total != n_devices:
        raise ValueError(
            f"mesh axes {resolved} use {total} devices, have {n_devices}")
    return resolved


def _axis_size(raw: str, where: str) -> int:
    try:
        return int(raw)
    except ValueError:
        raise ValueError(
            f"{where}: axis size must be an integer (or -1 to fill), "
            f"got {raw.strip()!r}") from None


def axes_from_env() -> Optional[Dict[str, int]]:
    """Mesh shape from the serve-path environment (ISSUE 16).

    ``DTPU_MESH_SHAPE`` — full layout, either ``data=2,tensor=2`` pairs or
    positional ``2x2x1`` in AXIS_ORDER (data, tensor, seq); ``-1`` fills.
    ``DTPU_TP`` — shorthand: tensor-axis size, data fills the rest.  Returns
    None when neither is set, so every existing caller keeps the pure
    data-parallel default."""
    shape = os.environ.get(MESH_SHAPE_ENV, "").strip()
    if shape:
        axes: Dict[str, int] = {}
        if "=" in shape:
            for part in shape.split(","):
                part = part.strip()
                if not part:
                    continue
                name, _, val = part.partition("=")
                name = name.strip()
                if name not in AXIS_ORDER:
                    raise ValueError(
                        f"{MESH_SHAPE_ENV}: unknown axis {name!r} "
                        f"(axes: {AXIS_ORDER})")
                axes[name] = _axis_size(val, f"{MESH_SHAPE_ENV} axis {name}")
        else:
            sizes = [_axis_size(v, MESH_SHAPE_ENV)
                     for v in shape.replace("x", ",").split(",")
                     if v.strip()]
            if len(sizes) > len(AXIS_ORDER):
                raise ValueError(
                    f"{MESH_SHAPE_ENV}: {len(sizes)} sizes for "
                    f"{len(AXIS_ORDER)} axes {AXIS_ORDER}")
            axes = dict(zip(AXIS_ORDER, sizes))
        return axes
    tp = os.environ.get(TP_ENV, "").strip()
    if tp and _axis_size(tp, TP_ENV) > 1:
        return {TENSOR_AXIS: _axis_size(tp, TP_ENV), DATA_AXIS: -1}
    return None


def build_mesh(axes: Optional[Dict[str, int]] = None,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Construct a named mesh over the available devices.

    ``axes`` maps axis name -> size; ``-1`` means "all remaining devices"
    (default: ``DTPU_MESH_SHAPE``/``DTPU_TP`` from the environment when set
    — the serve path's 2-D data×tensor switch — else everything on the data
    axis, mirroring the reference's pure data-parallel fan-out)."""
    devices = list(devices) if devices is not None else jax.devices()
    axes = dict(axes if axes is not None else (axes_from_env() or {}))
    axes.setdefault(DATA_AXIS, -1)
    resolved = _resolve_axes(axes, len(devices))
    shape = tuple(resolved[name] for name in AXIS_ORDER)
    arr = np.asarray(devices).reshape(shape)
    debug_log(f"mesh axes={resolved} over {len(devices)} "
              f"{devices[0].platform} device(s)")
    return Mesh(arr, AXIS_ORDER)


@dataclasses.dataclass
class MeshRuntime:
    """The live cluster object: mesh + participation state.

    Capability parity with the reference's notion of "enabled workers"
    (cluster membership lives in UI checkboxes, reference
    ``gpupanel.js:110-116``): here membership is ``num_participants`` — how
    many data-axis slots a fan-out run uses.  Slot 0 is the master
    (ordering parity with reference ``distributed.py:1424-1438``)."""

    mesh: Mesh
    enabled: bool = True

    @property
    def data_size(self) -> int:
        return self.mesh.shape[DATA_AXIS]

    @property
    def num_participants(self) -> int:
        return self.data_size if self.enabled else 1

    def data_sharding(self, spec: Optional[P] = None) -> NamedSharding:
        """Sharding with the leading (batch) dim over the data axis."""
        from comfyui_distributed_tpu.parallel import sharding as shd
        return shd.named(self.mesh,
                         spec if spec is not None else shd.mesh_spec(DATA_AXIS))

    def replicated(self) -> NamedSharding:
        from comfyui_distributed_tpu.parallel import sharding as shd
        return shd.replicated(self.mesh)

    def status(self) -> Dict[str, Any]:
        """Cluster status payload (feeds the control plane's /status route —
        the analog of the reference's 2 s browser poll, ``gpupanel.js:1233``)."""
        topo = describe_devices(list(self.mesh.devices.flat))
        return {
            "enabled": self.enabled,
            "axes": {k: int(v) for k, v in self.mesh.shape.items()},
            "num_participants": self.num_participants,
            **topo,
        }


_runtime: Optional[MeshRuntime] = None
_runtime_lock = threading.Lock()
# True once the TP cache guard below has fired; the disable is STICKY
# for the remainder of the process.
_cc_disabled = False


def _tp_compile_cache_guard(rt: Optional[MeshRuntime]) -> None:
    """XLA CPU cannot round-trip this repo's tensor-parallel serving
    executables through the persistent compilation cache: a cached
    donated SPMD step deserializes into an executable that returns
    garbage rows (observed latents ~1e10) and corrupts the heap (later
    unrelated device_puts segfault).  Fresh compilation of the very
    same HLO is fine — only the serialize/deserialize path is broken
    (jaxlib 0.4.37) — so the first time a tensor>1 serving mesh goes
    live on the cpu backend the cache is switched off FOR THE REST OF
    THE PROCESS.  The disable is deliberately sticky: re-enabling after
    the mesh clears and then loading cached entries reproducibly aborts
    with glibc heap-corruption (even for replicated programs), so a
    process that has ever run the TP serve path never touches the cache
    again.  TPU backends are unaffected.  Callers hold _runtime_lock."""
    global _cc_disabled
    tp_cpu = (rt is not None
              and int(rt.mesh.shape.get(TENSOR_AXIS, 1)) > 1
              and rt.mesh.devices.flat[0].platform == "cpu")
    if tp_cpu and not _cc_disabled:
        _cc_disabled = True
        if bool(jax.config.jax_enable_compilation_cache):
            jax.config.update("jax_enable_compilation_cache", False)
            log("tp: persistent compilation cache disabled for the rest "
                "of this process — a tensor-parallel mesh went live on "
                "cpu (cached sharded executables deserialize corrupt)")


def get_runtime(axes: Optional[Dict[str, int]] = None,
                refresh: bool = False) -> MeshRuntime:
    """Process-wide mesh runtime singleton (the analog of the reference's
    ``WorkerProcessManager`` singleton, ``distributed.py:1021``).

    Passing ``axes`` that conflict with an existing runtime's mesh raises —
    silently returning a differently-shaped mesh would let sharded programs
    run on the wrong topology; use ``refresh=True`` to rebuild."""
    global _runtime
    with _runtime_lock:
        if _runtime is None or refresh:
            _runtime = MeshRuntime(mesh=build_mesh(axes))
            _tp_compile_cache_guard(_runtime)
        elif axes is not None:
            requested = dict(axes)
            requested.setdefault(DATA_AXIS, -1)  # same default build_mesh uses
            want = _resolve_axes(requested, len(list(_runtime.mesh.devices.flat)))
            have = {k: int(v) for k, v in _runtime.mesh.shape.items()}
            if want != have:
                raise ValueError(
                    f"mesh runtime already built with axes {have}, "
                    f"requested {want}; pass refresh=True to rebuild")
        return _runtime


def get_live_runtime() -> Optional[MeshRuntime]:
    """The runtime singleton IF one was set/built — never builds one.
    Hot serving paths use this to ask "is a mesh live?" without paying
    for (or side-effecting) a default mesh construction."""
    with _runtime_lock:
        return _runtime


def set_runtime(rt: Optional[MeshRuntime]) -> None:
    global _runtime
    with _runtime_lock:
        _runtime = rt
        _tp_compile_cache_guard(rt)


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None) -> None:
    """Multi-host (pod) initialization over DCN — the analog of the
    reference's *remote workers* (``README.md:169-202``), but via
    ``jax.distributed`` instead of HTTP dispatch.  No-op when single-host
    env vars are absent and no arguments are given."""
    if coordinator_address is None:
        coordinator_address = os.environ.get("DTPU_COORDINATOR")
    if coordinator_address is None:
        return
    num_processes = num_processes or int(os.environ.get("DTPU_NUM_PROCESSES", "1"))
    process_id = process_id if process_id is not None else int(
        os.environ.get("DTPU_PROCESS_ID", "0"))
    log(f"initializing multihost: coordinator={coordinator_address} "
        f"procs={num_processes} id={process_id}")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
