"""Cross-request compute reuse plane (ISSUE 13).

Production diffusion traffic is massively redundant — retry storms,
seed-variant fans, and re-upscales of mostly-unchanged images re-pay
text-encode, VAE-encode, and even whole-graph compute that is
byte-identical to work this process just did.  The vLLM lesson
(PAPERS.md) is that memory/cache policy around an *unchanged kernel*
dominates serving throughput; this module is that policy, in three
content-addressed tiers plus a preview/cancellation channel:

- **Exact-hit result cache** (:attr:`ReusePlane.result`): key = the
  PR 2 structural signature + the FULL widget values (seed included) —
  a byte-identical re-submission replays the stored per-prompt images
  from host memory instead of re-running the graph.  The server stamps
  the replayed job's history/metrics/span as ``cache_hit``.
- **Sub-graph memoization** (:attr:`ReusePlane.subgraph`): text-encoder
  embeddings and VAE-encoded conditioning latents cached ON DEVICE
  across requests, keyed by a content hash of their input sub-graph
  (:func:`node_key`) — a retry/variant storm pays encode once;
  the continuous-batching bucket build's prefix run consumes the same
  cache, so new slots skip straight to denoise.
- **Changed-tile skipping** (:attr:`ReusePlane.tiles`): per-tile
  content hashes in the tiled-upscale path — a re-run of a
  mostly-unchanged image refines only the dirty tiles; the WorkLedger's
  pending set shrinks to the dirty units and the blend reuses stored
  refined windows bit-identically.

Every tier is an LRU bounded by its own byte budget (``DTPU_CACHE_*``
envs; the PR 5 resource telemetry samples the total into a
``cache_bytes`` ring so residency is observable next to RSS/HBM), and
``DTPU_CACHE=0`` is a true kill switch: the hot paths check
:func:`reuse_enabled` before any key is computed or any cache touched —
the PR 5 ``DTPU_RESOURCE=0`` pattern.

The **preview/cancellation channel** (:class:`PreviewBus`): step-wise
progressive previews streamed over SSE from the denoise loop (the
continuous-batching driver publishes a cheap latent->RGB projection at
step boundaries, only while a subscriber is attached), where a
disconnected client is the cancellation signal — the job is marked
abandoned, its CB slot exits at the next step boundary, queued copies
are purged, and the ledger/WAL record the abandonment.

Host-side hashing (``np.asarray`` et al.) lives HERE, outside the
dtpu-lint spine-host-fetch scope, so the ops layer calls helpers
instead of growing new host-fetch sites.
"""

from __future__ import annotations

import hashlib
import json
import os
import queue as queue_mod
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils import trace as trace_mod
from comfyui_distributed_tpu.utils.logging import debug_log


class AbandonedError(RuntimeError):
    """The job's last preview client disconnected (client-gone
    cancellation): the prompt is finalized as ``abandoned`` instead of
    executed to completion."""


# --- kill switches -----------------------------------------------------------

def reuse_enabled() -> bool:
    """``DTPU_CACHE=0`` disables every cache tier entirely: callers
    check this BEFORE computing keys or touching a cache, so the off
    state costs one env read on the hot path (the PR 5
    ``DTPU_RESOURCE=0`` pattern)."""
    return os.environ.get(C.CACHE_ENV, "1").lower() \
        not in ("0", "false", "off")


def previews_enabled() -> bool:
    return os.environ.get(C.PREVIEW_ENV, "1").lower() \
        not in ("0", "false", "off")


def _env_int(env: str, default: int) -> int:
    try:
        return int(os.environ.get(env, default))
    except (TypeError, ValueError):
        return int(default)


# --- content keys ------------------------------------------------------------

def _sha(blob: str) -> str:
    return hashlib.sha256(blob.encode()).hexdigest()


def hash_array(arr: Any) -> str:
    """Content hash of an array-like (host fetch happens here, outside
    the spine-lint scope; callers pass device arrays only for small
    conditioning tensors)."""
    a = np.ascontiguousarray(np.asarray(arr))
    return hashlib.sha1(
        a.tobytes() + str((a.shape, a.dtype.str)).encode()).hexdigest()


def result_key(prompt: Dict[str, Any],
               input_dir: Optional[str] = None,
               models_dir: Optional[str] = None,
               scope: Optional[str] = None) -> Optional[str]:
    """Exact-hit cache key: the canonical FULL node/widget structure
    (seed included — this is the PR 2 structural signature WITHOUT the
    seed mask) over the deterministic-safe node set, plus out-of-graph
    state salts (LoadImage file stat, the serving dirs).  A near-miss
    (ONE widget changed) produces a different key by construction;
    None = not cacheable (graphs with distributed nodes, hidden
    orchestration state, or any node type outside the safe set run
    normally, every time)."""
    nodes: Dict[str, Any] = {}
    salts: List[str] = [f"dirs:{input_dir or ''}:{models_dir or ''}"]
    if scope:
        # shard-owner-epoch scope (ISSUE 14 satellite): with N active
        # masters sharing this process-global plane, shard A must never
        # serve shard B's stored outputs, and entries a DEPOSED epoch
        # stored must go cold after a takeover (the new owner cannot
        # vouch the dead master finished storing them) — both fall out
        # of folding "<shard>:e<wal-epoch>" into the key.  Unset (the
        # single-master default) keys are unchanged bit-for-bit.
        salts.append(f"scope:{scope}")
    has_sampler = False
    for nid, node in prompt.items():
        if not isinstance(node, dict) or "class_type" not in node:
            continue  # metadata keys ride along untouched
        ct = node.get("class_type")
        if ct not in C.RESULT_CACHE_SAFE_NODE_TYPES:
            return None
        if node.get("hidden"):
            return None
        has_sampler |= ct in ("KSampler", "KSamplerAdvanced")
        if ct == "LoadImage":
            # the file's content can change between requests: fold the
            # stat identity in so a re-upload under the same name
            # misses instead of replaying stale outputs
            name = str(node.get("inputs", {}).get("image", ""))
            path = os.path.join(input_dir or "input", name)
            try:
                st = os.stat(path)
                salts.append(
                    f"{nid}:file:{name}:{st.st_mtime_ns}:{st.st_size}")
            except OSError:
                salts.append(f"{nid}:file:{name}:absent")
        nodes[str(nid)] = {"class_type": ct,
                           "inputs": node.get("inputs", {})}
    if not nodes or not has_sampler:
        return None
    try:
        blob = json.dumps(nodes, sort_keys=True, default=str)
    except (TypeError, ValueError):
        return None
    return _sha(blob + "|" + "|".join(sorted(salts)))


_LOADER_TYPES = ("CheckpointLoaderSimple", "LoraLoader",
                 "LoraLoaderModelOnly")


def _node_salt(node: Any, input_dir: Optional[str],
               models_dir: Optional[str]) -> Optional[str]:
    """Extra key material for nodes whose output depends on state
    outside the graph.  None = the node type disqualifies its subtree
    from content addressing."""
    if node.class_type == "LoadImage":
        # the file's content can change between requests: fold the stat
        # identity in so a re-upload under the same name misses
        name = str(node.inputs.get("image", ""))
        path = os.path.join(input_dir or "input", name)
        try:
            st = os.stat(path)
            return f"file:{name}:{st.st_mtime_ns}:{st.st_size}"
        except OSError:
            return f"file:{name}:absent"
    if node.class_type in _LOADER_TYPES:
        # two ServerStates with different model dirs in one process must
        # not alias each other's checkpoints
        return f"mdir:{models_dir or ''}"
    return ""


def node_key(graph: Any, nid: str, hidden: Dict[str, Dict[str, Any]],
             keys: Dict[str, str], input_dir: Optional[str] = None,
             models_dir: Optional[str] = None,
             resolved: Optional[Dict[Tuple[str, int], str]] = None
             ) -> Optional[str]:
    """Content hash of one node's input SUB-GRAPH: node type + widget
    values + the content keys (``keys``) of every upstream producer.
    None unless the node and its whole subtree are in
    ``REUSE_KEY_NODE_TYPES`` (pure functions of their widgets/inputs), so
    a cache hit can never alias differing inputs.  Nodes carrying per-run
    hidden overrides (coalesced seeds, recovery state) get none either.

    ``resolved`` maps ``(node id, slot)`` to the text a STRING output
    turned out to be in this run: a link to one is keyed on that text, as
    a widget holding the same text would be, whatever produced it (a
    language model's expansion into ``CLIPTextEncode.text``)."""
    node = graph.nodes[nid]
    if node.class_type not in C.REUSE_KEY_NODE_TYPES:
        return None
    if node.hidden or hidden.get(nid):
        return None
    salt = _node_salt(node, input_dir, models_dir)
    if salt is None:
        return None
    parts: List[str] = [node.class_type, salt]
    for name in sorted(node.inputs):
        if name == "__widgets__":
            continue
        value = node.inputs[name]
        if isinstance(value, (list, tuple)) and len(value) == 2 \
                and not isinstance(value[0], (list, dict)) \
                and isinstance(value[1], int) \
                and str(value[0]) in graph.nodes:
            src = (str(value[0]), int(value[1]))
            if resolved and src in resolved:
                value = resolved[src]
            else:
                up = keys.get(src[0])
                if up is None:
                    return None
                parts.append(f"{name}<-{up}:{src[1]}")
                continue
        try:
            parts.append(f"{name}={json.dumps(value, sort_keys=True, default=str)}")
        except (TypeError, ValueError):
            return None
    return _sha("|".join(parts))


def subgraph_keys(graph: Any, hidden: Dict[str, Dict[str, Any]],
                  input_dir: Optional[str] = None,
                  models_dir: Optional[str] = None) -> Dict[str, str]:
    """:func:`node_key` of every node that has one, in topo order, from
    the graph alone (no STRING output is resolved before a run)."""
    keys: Dict[str, str] = {}
    for nid in graph.topo_order():
        key = node_key(graph, nid, hidden, keys, input_dir, models_dir)
        if key is not None:
            keys[nid] = key
    return keys


# --- the bounded LRU ---------------------------------------------------------

class ByteLRU:
    """Thread-safe LRU keyed by content hash, bounded by a byte budget
    and an entry cap.  Values are opaque (host numpy for the result and
    tile tiers, device arrays for the sub-graph tier — jax buffers free
    when the entry drops).  Every decision lands in per-tier counters
    AND the process-global event counters (both metrics surfaces)."""

    def __init__(self, name: str, max_bytes: int, max_entries: int):
        self.name = str(name)
        self.max_bytes = max(int(max_bytes), 0)
        self.max_entries = max(int(max_entries), 1)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, Tuple[Any, int]]" = \
            OrderedDict()                      # guarded-by: self._lock
        self._bytes = 0                        # guarded-by: self._lock
        self.hits = 0                          # guarded-by: self._lock
        self.misses = 0                        # guarded-by: self._lock
        self.stores = 0                        # guarded-by: self._lock
        self.evictions = 0                     # guarded-by: self._lock

    def get(self, key: str) -> Optional[Any]:
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                self.misses += 1
                trace_mod.GLOBAL_COUNTERS.bump(
                    f"cache_{self.name}_misses")
                return None
            self._entries.move_to_end(key)
            self.hits += 1
        trace_mod.GLOBAL_COUNTERS.bump(f"cache_{self.name}_hits")
        return ent[0]

    def put(self, key: str, value: Any, nbytes: int) -> bool:
        """Insert (no-op when the single value exceeds the whole
        budget — caching it would just evict everything else)."""
        nbytes = max(int(nbytes), 0)
        if self.max_bytes and nbytes > self.max_bytes:
            return False
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            self._entries[key] = (value, nbytes)
            self._bytes += nbytes
            self.stores += 1
            while len(self._entries) > self.max_entries or \
                    (self.max_bytes and self._bytes > self.max_bytes
                     and len(self._entries) > 1):
                _, (_, nb) = self._entries.popitem(last=False)
                self._bytes -= nb
                self.evictions += 1
                trace_mod.GLOBAL_COUNTERS.bump(
                    f"cache_{self.name}_evictions")
        return True

    def clear(self) -> int:
        """Drop everything; returns the freed bytes."""
        with self._lock:
            freed = self._bytes
            self._entries.clear()
            self._bytes = 0
        return freed

    @property
    def bytes(self) -> int:
        with self._lock:
            return self._bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def keys(self) -> List[str]:
        with self._lock:
            return list(self._entries)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"entries": len(self._entries), "bytes": self._bytes,
                    "max_bytes": self.max_bytes,
                    "max_entries": self.max_entries,
                    "hits": self.hits, "misses": self.misses,
                    "stores": self.stores, "evictions": self.evictions}


# --- the plane ---------------------------------------------------------------

class ReusePlane:
    """The three cache tiers plus the invalidation generation.  Budgets
    resolve from env at construction so tests pin them per instance."""

    def __init__(self,
                 result_bytes: Optional[int] = None,
                 device_bytes: Optional[int] = None,
                 tile_bytes: Optional[int] = None,
                 max_entries: Optional[int] = None):
        entries = max_entries if max_entries is not None else \
            _env_int(C.CACHE_ENTRIES_ENV, C.CACHE_ENTRIES_DEFAULT)
        self.result = ByteLRU(
            "result",
            result_bytes if result_bytes is not None
            else _env_int(C.CACHE_BYTES_ENV, C.CACHE_BYTES_DEFAULT),
            entries)
        self.subgraph = ByteLRU(
            "embed",
            device_bytes if device_bytes is not None
            else _env_int(C.CACHE_DEVICE_BYTES_ENV,
                          C.CACHE_DEVICE_BYTES_DEFAULT),
            entries)
        self.tiles = ByteLRU(
            "tile",
            tile_bytes if tile_bytes is not None
            else _env_int(C.CACHE_TILE_BYTES_ENV,
                          C.CACHE_TILE_BYTES_DEFAULT),
            entries)
        # bumped on clear: folded into model-identity salts so a
        # post-clear reload can never alias a stale entry
        self._generation = 0
        # stable per-pipeline identity tokens: a WeakKeyDictionary keyed
        # by the LIVE pipe object — unlike id(), a token is never
        # recycled when a pipeline is evicted/freed and CPython reuses
        # its address (a recycled id could replay another model's
        # refined tiles)
        import itertools
        import weakref
        self._salt_lock = threading.Lock()
        self._model_ids: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()     # guarded-by: self._salt_lock
        self._model_next = itertools.count()  # guarded-by: self._salt_lock

    def bytes_total(self) -> int:
        return self.result.bytes + self.subgraph.bytes + self.tiles.bytes

    def clear(self) -> int:
        """Invalidate every tier (the /distributed/clear_memory hook);
        returns the freed bytes."""
        freed = self.result.clear() + self.subgraph.clear() \
            + self.tiles.clear()
        self._generation += 1
        return freed

    def model_salt(self, pipe: Any) -> Optional[str]:
        """Process-local identity of a loaded pipeline for tile keys: a
        monotonic token held in a weak-keyed registry (dies with the
        object, never recycled) plus the clear generation.  None when
        the object can't carry a stable identity (unhashable /
        non-weakrefable) — the caller skips the tier rather than risk
        aliasing."""
        try:
            with self._salt_lock:
                tok = self._model_ids.get(pipe)
                if tok is None:
                    tok = next(self._model_next)
                    self._model_ids[pipe] = tok
        except TypeError:
            return None
        return f"m{tok}g{self._generation}"

    def snapshot(self) -> Dict[str, Any]:
        return {
            "enabled": reuse_enabled(),
            "bytes_total": self.bytes_total(),
            "generation": self._generation,
            "result": self.result.snapshot(),
            "embed": self.subgraph.snapshot(),
            "tile": self.tiles.snapshot(),
        }


_PLANE: Optional[ReusePlane] = None
_plane_lock = threading.Lock()


def get_reuse() -> ReusePlane:
    """The process-global plane (ONE per process, like the resource
    monitor: caches are process facts, not per-ServerState)."""
    global _PLANE
    with _plane_lock:
        if _PLANE is None:
            _PLANE = ReusePlane()
        return _PLANE


def reset_reuse() -> ReusePlane:
    """Tests: rebuild the plane so env-pinned budgets take effect."""
    global _PLANE
    with _plane_lock:
        _PLANE = ReusePlane()
        return _PLANE


def cache_bytes_total() -> int:
    """Total cached bytes across tiers — the ResourceMonitor's
    ``cache_bytes`` series provider (0 when nothing was ever cached, so
    sampling never constructs a plane just to measure it)."""
    plane = _PLANE
    return plane.bytes_total() if plane is not None else 0


# --- preview / client-gone cancellation channel ------------------------------

# latent->RGB projection (the standard cheap preview trick: a fixed
# linear map from the 4 SD latent channels to RGB, normalized into
# [0,1]) — good enough to watch composition emerge, no VAE decode
_LATENT_RGB = np.asarray([[0.298, 0.207, 0.208],
                          [0.187, 0.286, 0.173],
                          [-0.158, 0.189, 0.264],
                          [-0.184, -0.271, -0.473]], np.float32)


def latent_preview_png(latent: Any) -> bytes:
    """One latent sample -> small PNG bytes (host fetch happens here)."""
    from comfyui_distributed_tpu.utils.image import encode_png
    lat = np.asarray(latent, np.float32)
    if lat.ndim == 4:
        lat = lat[0]
    ch = lat.shape[-1]
    if ch >= 4:
        rgb = lat[..., :4] @ _LATENT_RGB
    else:
        rgb = np.repeat(lat[..., :1], 3, axis=-1)
    rgb = np.clip(rgb / 6.0 + 0.5, 0.0, 1.0)
    return encode_png(rgb[None], compress_level=3)


class PreviewBus:
    """Per-prompt SSE fan-out + the abandonment registry.

    The denoise driver asks :meth:`wants` at each step boundary (one
    dict lookup while nobody is subscribed) and :meth:`publish_latent`
    only for watched prompts; SSE handlers :meth:`subscribe` a bounded
    queue each.  A handler whose client disconnects calls
    :meth:`abandon` — the flag is consumed by the queue purge and the
    CB driver's slot scan, which finalize the job as ``abandoned``."""

    def __init__(self, max_clients: Optional[int] = None):
        # None = resolve from env PER CALL (the module-global bus is
        # built at import, and the cap must respond to the env like the
        # sibling DTPU_PREVIEW/_EVERY knobs do); tests pin an explicit
        # value
        self._max_clients = max_clients
        self._lock = threading.Lock()
        self._subs: Dict[str, List[queue_mod.Queue]] = {}  # guarded-by: self._lock
        self._abandoned: set = set()                       # guarded-by: self._lock

    @property
    def max_clients(self) -> int:
        return self._max_clients if self._max_clients is not None else \
            _env_int(C.PREVIEW_MAX_CLIENTS_ENV,
                     C.PREVIEW_MAX_CLIENTS_DEFAULT)

    # -- subscription ---------------------------------------------------------

    def subscribe(self, pid: str) -> Optional[queue_mod.Queue]:
        """A bounded per-client event queue, or None at the client cap
        (the SSE route then 429s)."""
        q: queue_mod.Queue = queue_mod.Queue(maxsize=16)
        with self._lock:
            if sum(len(v) for v in self._subs.values()) \
                    >= self.max_clients:
                return None
            self._subs.setdefault(str(pid), []).append(q)
        trace_mod.GLOBAL_COUNTERS.bump("preview_clients")
        return q

    def unsubscribe(self, pid: str, q: queue_mod.Queue) -> int:
        """Detach; returns how many subscribers REMAIN for the prompt
        (0 = this was the last client — the caller decides whether that
        means abandonment)."""
        with self._lock:
            subs = self._subs.get(str(pid), [])
            if q in subs:
                subs.remove(q)
            n = len(subs)
            if not subs:
                self._subs.pop(str(pid), None)
        return n

    def wants(self, pid: str) -> bool:
        with self._lock:
            return str(pid) in self._subs

    def client_count(self) -> int:
        with self._lock:
            return sum(len(v) for v in self._subs.values())

    # -- publishing -----------------------------------------------------------

    def _fan_out(self, pid: str, event: Dict[str, Any]) -> None:
        with self._lock:
            subs = list(self._subs.get(str(pid), ()))
        for q in subs:
            try:
                q.put_nowait(event)
            except queue_mod.Full:
                # a slow client drops frames, never backpressures the
                # denoise loop
                trace_mod.GLOBAL_COUNTERS.bump("preview_drops")

    def publish_latent(self, pid: str, step: int, total: int,
                       latent: Any) -> None:
        """Encode + fan out one step's preview (called only when
        :meth:`wants` said someone is watching)."""
        import base64
        try:
            png = latent_preview_png(latent)
        except Exception as e:  # noqa: BLE001 - preview must never kill a step
            debug_log(f"preview encode failed for {pid}: {e}")
            return
        trace_mod.GLOBAL_COUNTERS.bump("preview_events")
        self._fan_out(str(pid), {
            "type": "preview", "prompt_id": str(pid),
            "step": int(step), "total_steps": int(total),
            "png_b64": base64.b64encode(png).decode()})

    def finish(self, pid: str, status: str) -> None:
        """Terminal event: push to remaining clients, clear the
        abandonment flag (the job is settled either way)."""
        self._fan_out(str(pid), {"type": "done", "prompt_id": str(pid),
                                 "status": str(status)})
        with self._lock:
            self._abandoned.discard(str(pid))

    # -- client-gone cancellation ---------------------------------------------

    def abandon(self, pid: str) -> None:
        with self._lock:
            if str(pid) in self._abandoned:
                return
            self._abandoned.add(str(pid))
        trace_mod.GLOBAL_COUNTERS.bump("jobs_abandoned")

    def clear_abandoned(self, pid: str) -> None:
        """Consume a stale flag for a job that settled in the race
        between the disconnect handler's liveness check and its
        abandon() — finish() already ran, so nothing else would."""
        with self._lock:
            self._abandoned.discard(str(pid))

    def is_abandoned(self, pid: str) -> bool:
        with self._lock:
            return str(pid) in self._abandoned

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "enabled": previews_enabled(),
                "clients": sum(len(v) for v in self._subs.values()),
                "watched_prompts": len(self._subs),
                "abandoned_pending": len(self._abandoned),
                "max_clients": self.max_clients,
            }


PREVIEWS = PreviewBus()


def preview_every() -> int:
    """Publish a preview every N steps (DTPU_PREVIEW_EVERY, min 1)."""
    return max(_env_int(C.PREVIEW_EVERY_ENV, C.PREVIEW_EVERY_DEFAULT), 1)


# --- tile-tier helpers -------------------------------------------------------

def conditioning_fingerprint(positive: Any, negative: Any) -> str:
    """Content identity of a (positive, negative) conditioning pair for
    tile keys — the refined tile depends on the prompt embeddings, not
    just the widget params.  Small arrays; the fetch happens here."""
    parts = []
    for cond in (positive, negative):
        parts.append(hash_array(cond.context))
        pooled = getattr(cond, "pooled", None)
        parts.append(hash_array(pooled) if pooled is not None else "-")
        sc = getattr(cond, "size_cond", None)
        parts.append(str(tuple(sc)) if sc is not None else "-")
    return _sha("|".join(parts))


def tile_keys(model_salt: str, cond_fp: str, params: Dict[str, Any],
              tiles: np.ndarray,
              tile_indices: List[int]) -> List[str]:
    """Per-tile content keys: model identity + conditioning fingerprint
    + refine params + the tile INDEX (its seed is ``seed + idx``) + the
    extracted window's bytes.  A 10%-changed source re-keys only the
    windows whose pixels moved."""
    base = _sha(model_salt + "|" + cond_fp + "|"
                + json.dumps(params, sort_keys=True, default=str))
    out = []
    arr = np.ascontiguousarray(np.asarray(tiles, np.float32))
    for k, idx in enumerate(tile_indices):
        h = hashlib.sha1(arr[k].tobytes())
        h.update(f"|{base}|{int(idx)}".encode())
        out.append(h.hexdigest())
    return out


def tile_nbytes(window: np.ndarray) -> int:
    return int(np.asarray(window).nbytes)


# --- result-tier helpers -----------------------------------------------------

def nbytes_of(x: Any) -> int:
    """Byte size WITHOUT forcing a host fetch (device arrays carry
    .nbytes; everything else goes through numpy)."""
    nb = getattr(x, "nbytes", None)
    return int(nb) if nb is not None else int(np.asarray(x).nbytes)


def images_nbytes(images: List[Any]) -> int:
    return int(sum(nbytes_of(im) for im in images))


def store_result(key: str, images: List[Any],
                 duration_s: float) -> bool:
    """Finalize-path store: per-prompt images + replay metadata."""
    plane = get_reuse()
    entry = {"images": [np.asarray(im) for im in images],
             "duration_s": float(duration_s),
             "stored_at": time.time()}
    return plane.result.put(key, entry, images_nbytes(images))


def conditioning_nbytes(cond: Any) -> int:
    n = nbytes_of(cond.context)
    pooled = getattr(cond, "pooled", None)
    if pooled is not None:
        n += nbytes_of(pooled)
    return n
