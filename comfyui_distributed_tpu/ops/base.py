"""Op protocol, registry and execution context.

Parity notes: each op mirrors a reference node's schema —
``WIDGETS`` encodes ComfyUI's widget order (including the ``control``
slots like "randomize" that occupy a position but carry no input), and
``HIDDEN`` lists the hidden inputs the reference's browser dispatcher
injects (``gpupanel.js:1074-1177``); here the dispatcher module injects the
same names.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from comfyui_distributed_tpu.utils import trace as trace_mod
from comfyui_distributed_tpu.utils.trace import record_transfer

# sentinel for widget slots that are UI chrome (control_after_generate)
CONTROL = "__control__"


class CBCapture(Exception):
    """Control-flow signal for the continuous-batching executor's bucket
    build (workflow/batch_executor.py): with ``OpContext.cb_capture``
    set, the KSampler records its resolved inputs (model, conditionings,
    latent, widget config) into the dict and raises this instead of
    sampling — the prefix run supplied everything the step executor
    needs, so the graph tail (decode/save) must NOT run yet."""


@dataclasses.dataclass
class Conditioning:
    """CLIP encoding result (comfy CONDITIONING)."""
    context: Any          # [1, T, C]
    pooled: Any = None    # [1, P]
    # attached ControlNet: (module, params, hint_image, strength);
    # ComfyUI hangs control on conditioning entries the same way
    control: Any = None
    # regional prompting (ComfyUI multi-entry cond lists): an optional
    # image-resolution mask array OR a rect spec ("px", x, y, w, h —
    # ComfyUI's //8 latent units) / ("pct", x, y, w, h — fractions),
    # a blend strength, and sibling entries bundled by
    # ConditioningCombine (each sibling is its own mask/strength entry;
    # all entries evaluate in one stacked model call at sample time)
    area_mask: Any = None
    area_strength: float = 1.0
    siblings: tuple = ()
    # prompt scheduling (ConditioningSetTimestepRange): (start, end)
    # sampling-percent pair, 0.0 = start of sampling, 1.0 = end; the
    # entry contributes only while the step sigma is inside the range
    timestep_range: Any = None
    # inpaint-MODEL channels (InpaintModelConditioning): [1_or_B, h, w,
    # 1 + C] latent-resolution array of [mask, masked-image latent],
    # concatenated to the UNet input every call (9-channel families)
    concat_latent: Any = None
    # unCLIP image conditioning: tuple of (image_embed [1, D], strength,
    # noise_augmentation) entries consumed by unclip-ADM families
    unclip: Any = None
    # GLIGEN grounding: (gligen_model, ((phrase_emb [1, D], box_xywh
    # latent-units), ...)) — GLIGENTextBoxApply appends; sampling turns
    # the entries into grounding tokens for the fusers
    gligen: Any = None
    # SDXL size conditioning (CLIPTextEncodeSDXL / ...Refiner): tuple of
    # scalars each embedded at 256 sinusoidal dims and appended to the
    # pooled text emb in the ADM vector — base order (height, width,
    # crop_h, crop_w, target_height, target_width); refiner (height,
    # width, crop_h, crop_w, aesthetic_score).  None -> the sampler
    # derives (H, W, 0, 0, H, W) from the actual latent dims
    size_cond: Any = None


@dataclasses.dataclass
class SeedValue:
    """INT seed that knows whether it came from a DistributedSeed node.

    Reference semantics: master passes the seed through, worker ``i`` uses
    ``seed + i + 1`` (``distributed.py:1491-1514``).  In SPMD mode this
    becomes a per-replica offset applied by the KSampler; a plain int seed
    replicates identically on every participant, exactly like a reference
    run without a DistributedSeed node."""
    base: int
    distributed: bool = False
    # batch-coalescing scheduler (workflow/scheduler.py): one seed PER
    # COALESCED PROMPT; _prepare_sample_inputs repeats each over its
    # prompt's local batch so every prompt keeps the exact noise stream
    # a serial run would have drawn
    per_prompt: Any = None

    def __index__(self) -> int:
        return int(self.base)


@dataclasses.dataclass
class OpContext:
    """Per-run execution context (what ComfyUI spreads across PromptServer,
    hidden inputs and folder_paths)."""
    runtime: Any = None                # MeshRuntime
    models_dir: Optional[str] = None
    input_dir: Optional[str] = None
    output_dir: Optional[str] = None
    fanout: int = 1                    # data-parallel replicas for this run
    # batch-coalescing scheduler: number of signature-identical prompts
    # merged into this run; EmptyLatentImage multiplies its batch by it
    coalesce: int = 1
    # overlapped pipeline (utils.net.HostIOPool): when set, OUTPUT-node
    # host edges (d2h fetch, PNG encode, disk write) defer onto the pool
    # and land in image_futures instead of saved_images — job N's encode
    # overlaps job N+1's denoise loop
    host_pool: Any = None
    image_futures: List[Any] = dataclasses.field(default_factory=list)
    # distributed identity (hidden-input defaults for all ops)
    is_worker: bool = False
    worker_id: str = ""
    master_url: str = ""
    enabled_worker_ids: str = "[]"
    # data plane (master mode): job store with asyncio queues + loop
    job_store: Any = None
    server_loop: Any = None
    # cluster control plane (runtime/cluster.py): worker registry with
    # leases + per-job work ledger — the collectors consult the registry
    # for dead owners and check completions in through the ledger so
    # lost units get reassigned/hedged instead of dropped.  None (CLI /
    # SPMD mode) keeps the pre-cluster behavior.
    cluster: Any = None
    ledger: Any = None
    # test/bench fault injection ({"drop_tiles_after": k, "stall_s": t});
    # empty in production
    fault_inject: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # collected artifacts
    saved_images: List[np.ndarray] = dataclasses.field(default_factory=list)
    node_timings: Dict[str, float] = dataclasses.field(default_factory=dict)
    interrupt_event: Any = None
    # PNG metadata (ComfyUI contract): the executing graph in API format
    # and the client's extra_pnginfo (typically {"workflow": <UI doc>}) —
    # SaveImage embeds both as tEXt chunks so saved images reload into
    # the same graph (reference ships extra_pnginfo with every dispatch,
    # gpupanel.js:1344-1358)
    prompt_json: Any = None
    extra_pnginfo: Any = None
    # per-run hidden-input overrides (executor.execute's ``hidden`` arg):
    # SaveImage reads the coalescing scheduler's per-prompt widget lists
    # out of this to embed per-prompt metadata
    hidden_overrides: Dict[str, Dict[str, Any]] = \
        dataclasses.field(default_factory=dict)
    # continuous batching (workflow/batch_executor.py): a dict arms the
    # KSampler's capture mode — it records its resolved inputs here and
    # raises CBCapture instead of sampling (bucket-build prefix run)
    cb_capture: Optional[Dict[str, Any]] = None
    # cross-request compute reuse (runtime/reuse.py): the EXECUTING
    # node's input-sub-graph content hash, set per node by the executor
    # when the subtree is content-addressable (else None) — the
    # sub-graph memo tiers (CLIPTextEncode embeddings, VAEEncode
    # conditioning latents) key their device caches on it
    content_key: Optional[str] = None
    # a server's look into its own queue for a LanguageModelGenerate node
    # (server/lm_handover.py): the requests waiting behind this one join
    # its execution.  None outside a server: the node runs its one row.
    lm_handover: Any = None
    # a server's count of the images the device still owes it: called at
    # this run's deferred host edge the moment the device has produced
    # the image (`fetch_image_array`), before the copy and the PNG
    device_ready: Optional[Callable[[], None]] = None

    def check_interrupt(self):
        if self.interrupt_event is not None and self.interrupt_event.is_set():
            raise InterruptedError("execution interrupted")

    def collect_images(self, make_host_images) -> None:
        """OUTPUT-node image collection point.  ``make_host_images()``
        performs the host edge (d2h fetch + optional encode/disk write)
        and returns the per-image list.  Without a host pool it runs
        inline into ``saved_images`` (the classic serial path); with one
        it defers onto the pool and the future lands in
        ``image_futures`` — submission order preserves collection order,
        and ``ExecutionResult.wait_host`` reassembles the list."""
        if self.host_pool is None:
            self.saved_images.extend(make_host_images())
        else:
            self.image_futures.append(self.host_pool.submit(
                make_host_images))


class Op:
    """Base class for workflow ops.

    Class attributes:
        TYPE: node class name (matches reference NODE_CLASS_MAPPINGS key)
        WIDGETS: widget names in UI order (CONTROL for chrome slots)
        DEFAULTS: default values for optional widgets
        HIDDEN: hidden input names this op accepts
        OUTPUT_NODE: terminal node (executed even with no consumers)
    """

    TYPE = ""
    WIDGETS: List[str] = []
    DEFAULTS: Dict[str, Any] = {}
    HIDDEN: List[str] = []
    OUTPUT_NODE = False

    def execute(self, ctx: OpContext, **inputs) -> Tuple:
        raise NotImplementedError


NODE_CLASS_MAPPINGS: Dict[str, type] = {}
_registry_lock = threading.Lock()


def register_op(cls: type) -> type:
    with _registry_lock:
        NODE_CLASS_MAPPINGS[cls.TYPE] = cls
    return cls


def get_op(type_name: str) -> Op:
    try:
        cls = NODE_CLASS_MAPPINGS[type_name]
    except KeyError:
        raise KeyError(
            f"unknown node type {type_name!r}; known: "
            f"{sorted(NODE_CLASS_MAPPINGS)}") from None
    return cls()


class DeviceTensor:
    """Device-resident tensor-plane value: a ``jax.Array`` plus fan-out
    metadata, handed BETWEEN ops without leaving the device.

    The wrapper exists so op boundaries stop being implicit host edges:
    device-aware consumers unwrap via :func:`as_device_array` (or
    ``jnp.asarray``, which takes the ``__jax_array__`` fast path — no
    transfer), while legacy numpy consumers keep working through
    ``__array__`` — paying, and *recording*, the device->host fetch.
    Every transfer is attributed to the executing workflow node via
    ``utils.trace``, which is what makes "zero host transfers between
    KSampler and Collector" an assertable property instead of a hope."""

    __slots__ = ("data", "local_batch", "fanout")

    def __init__(self, data, local_batch: Optional[int] = None,
                 fanout: int = 1):
        self.data = data if isinstance(data, jax.Array) \
            else put_device_array(np.asarray(data, np.float32))
        self.local_batch = local_batch
        self.fanout = int(fanout)

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self.data.shape)

    @property
    def ndim(self) -> int:
        return self.data.ndim

    @property
    def dtype(self):
        return self.data.dtype

    def __len__(self) -> int:
        return int(self.data.shape[0])

    def __jax_array__(self):
        # jnp.asarray()/device consumers: hand over the jax.Array directly
        # — NO host round trip
        return self.data

    def to_host(self) -> np.ndarray:
        """THE device->host edge: fetch, count, return float32 numpy."""
        # dtpu-lint: ignore[spine-host-fetch] the one designed d2h edge — counted
        arr = np.asarray(jax.device_get(self.data), dtype=np.float32)
        record_transfer("d2h", arr.nbytes)
        return arr

    def __array__(self, dtype=None, copy=None):
        # legacy numpy consumers (np.asarray, np.clip, ...): transparent
        # but COUNTED host fetch
        arr = self.to_host()
        return arr if dtype is None else arr.astype(dtype)

    def __repr__(self) -> str:
        return (f"{type(self).__name__}(shape={self.shape}, "
                f"local_batch={self.local_batch}, fanout={self.fanout})")


class DeviceImage(DeviceTensor):
    """IMAGE wire value resident on device ([B,H,W,C] float32 in [0,1])."""


class DeviceLatent(DeviceTensor):
    """LATENT ``samples`` value resident on device ([B,h,w,C] float32)."""


def put_device_array(x) -> jax.Array:
    """Host -> device put with transfer accounting (the counted inverse of
    ``DeviceTensor.to_host``)."""
    # dtpu-lint: ignore[spine-host-fetch] h2d put on an already-host value — counted
    arr = np.asarray(x)
    record_transfer("h2d", arr.nbytes)
    return jnp.asarray(arr)


def as_device_array(x) -> jax.Array:
    """Normalize a wire value to a ``jax.Array`` WITHOUT a host bounce when
    it is already device-resident (DeviceTensor / jax.Array); host arrays
    pay one counted h2d put."""
    if isinstance(x, DeviceTensor):
        return x.data
    if isinstance(x, jax.Array):
        return x
    return put_device_array(np.asarray(x, np.float32))


def as_device_image(x) -> jax.Array:
    """IMAGE value -> device [B,H,W,C] float32, staying on device when
    possible (device analog of :func:`as_image_array`)."""
    arr = as_device_array(x)
    if arr.ndim == 3:
        arr = arr[None]
    return arr


def fanout_meta(x) -> Dict[str, Any]:
    """Fan-out metadata riding an IMAGE value (DeviceImage or ImageBatch),
    in the LATENT-dict key convention."""
    meta: Dict[str, Any] = {}
    lb = getattr(x, "local_batch", None)
    if lb is not None:
        meta["local_batch"] = int(lb)
    meta["fanout"] = int(getattr(x, "fanout", 1) or 1)
    return meta


def fetch_image_array(x, ready: Optional[Callable[[], None]] = None
                      ) -> np.ndarray:
    """:func:`as_image_array` at a deferred host edge (PNG, HTTP wire),
    as the ``d2h`` stage it always was, now told apart inside: first
    ``device_wait`` until the device has produced ``x`` (dispatch is
    asynchronous, so this is where the host meets the still-running
    program; its end is the request's ``device_ready`` instant, and
    ``ready`` is called there), then ``d2h_copy`` for the copy alone."""
    with trace_mod.stage("d2h"):
        dev = x.data if isinstance(x, DeviceTensor) else x
        if isinstance(dev, jax.Array):
            with trace_mod.device_wait():
                jax.block_until_ready(dev)
        trace_mod.mark_instant("device_ready")
        if ready is not None:
            ready()
        with trace_mod.stage("d2h_copy"):
            return as_image_array(x)


def as_image_array(x) -> np.ndarray:
    """Normalize IMAGE values to numpy [B,H,W,C] float32.

    This is a HOST edge: device-resident values (DeviceTensor/jax.Array)
    pay a device->host fetch here, recorded against the executing node —
    legal at true host boundaries (PNG encode, HTTP wire, host-side
    compositing), a counted bug between device ops."""
    if isinstance(x, DeviceTensor):
        arr = x.to_host()
    elif isinstance(x, jax.Array):
        # dtpu-lint: ignore[spine-host-fetch] designed host edge — counted
        arr = np.asarray(jax.device_get(x), dtype=np.float32)
        record_transfer("d2h", arr.nbytes)
    else:
        arr = np.asarray(x, dtype=np.float32)
    if arr.ndim == 3:
        arr = arr[None]
    return arr
