"""Iteration-level continuous batching: a step-granular denoise executor.

The Orca lesson (PAPERS.md) mapped onto diffusion serving: the unit of
scheduling drops from "a whole prompt" to "ONE denoise step of a padded
batch".  The PR 2 coalescer could only merge a contiguous same-signature
run at the queue head, once, at dispatch time — under mixed production
traffic it degenerates to batch=1 and the mesh idles between dispatches.
Here the denoise loop itself becomes the scheduler's inner loop:

- **Persistent shape-bucketed batches.**  Each PR 2 structural signature
  (seed-masked graph hash — identical model, resolution, steps, sampler)
  gets a *bucket*: a padded device batch whose row count comes from a
  fixed pad set (``DTPU_CB_PAD_BUCKETS``), with a per-pad jitted STEP
  callable from the pipeline's existing compile cache
  (``registry.denoise_step_fn``).  Shapes never leave the declared set,
  so steady state runs with **zero retraces**.
- **Per-slot iteration state.**  A slot carries one prompt's
  remaining-steps counter, sigma index and its exact ``(seed, fold-idx)``
  PRNG key rows — the same keys, init noise and per-step expressions its
  serial run would use (the step callable IS the scan sampler's extracted
  step, ``samplers.SAMPLER_STEPS``), so a continuously-batched image is
  **bit-identical** to its serial run.
- **Join at the step boundary.**  A new prompt is admitted into the
  RUNNING batch between steps (``scheduler.pop_cb_admit`` — the same
  stride-fair class scheduling as ``pop_fair_group``, so paid/free/batch
  ratios survive the new dispatch model).  Non-contiguous same-signature
  prompts merge too: anything behind the scheduled head with the same
  class+signature joins, killing the head-run-only limitation.
- **Exit without draining.**  A finished prompt's rows are sliced out at
  the boundary, the batch compacts (dense slots, pad shrinks along the
  pad set) and the latents proceed to VAE decode + save on the *tail*
  thread while the batch keeps stepping.  This slot-exit point is also
  the natural future cancellation hook (ROADMAP item 3: client-gone).
- **Fallback, not refusal.**  Prompts the step model cannot serve
  (multi-sampler graphs, control/masks, non-extracted samplers,
  orchestrated shares — ``orchestrate.is_dispatched_share``) run through
  the classic one-dispatch executor on the fallback thread, preserving
  every PR 2/9 behavior for them.

Threading: the *driver* thread owns all bucket/device state (admit,
step, retire, compact run strictly between steps — no device-state
locks needed); the *tail* thread decodes retired slots; the *fallback*
thread runs ineligible groups.  Only the telemetry counters the metrics
routes read cross threads, and those sit under ``self._lock``.
Everything here runs on plain threads — never on the aiohttp event loop
(dtpu-lint async-blocking stays clean by construction).

Off by default; ``DTPU_CB=1`` (or ``ServerState(cb=True)``) opts in.
"""

from __future__ import annotations

import functools
import queue as queue_mod
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

from comfyui_distributed_tpu.ops.base import DeviceLatent, OpContext
from comfyui_distributed_tpu.runtime import reuse as reuse_mod
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils import trace as trace_mod
from comfyui_distributed_tpu.utils.logging import debug_log, log
from comfyui_distributed_tpu.workflow import scheduler as sched_mod
from comfyui_distributed_tpu.workflow.executor import WorkflowExecutor
from comfyui_distributed_tpu.workflow.graph import parse_workflow
from comfyui_distributed_tpu.workflow.orchestrate import is_dispatched_share


class CBIneligible(Exception):
    """The prompt looked batchable but the deep (capture-time) checks
    failed — model patches, regional conds, unclip ADM, ...  The driver
    blacklists the signature and routes the group to the fallback."""


def _class_rank(cls: str) -> int:
    """Preemption rank (ISSUE 17): position in ``CB_PREEMPT_ORDER`` is
    the rank — batch (0) parks before free (1) — and any class OUTSIDE
    the order (paid, custom tenants) ranks above every preemptible
    class, so a paid row is never parked."""
    try:
        return C.CB_PREEMPT_ORDER.index(str(cls))
    except ValueError:
        return len(C.CB_PREEMPT_ORDER)


def validate_cb_env(env: Dict[str, str]) -> None:
    """Fail-fast validation of the continuous-batching knobs at worker
    launch (the PR 16 ``DTPU_TP``/``DTPU_MESH_SHAPE`` pattern in
    runtime/manager.py): a malformed value dies HERE with a clear
    error naming the knob, instead of deep inside the driver thread's
    first admission where it would surface as a poisoned bucket."""

    def _int_knob(name: str, lo: int, what: str) -> None:
        raw = env.get(name)
        if raw in (None, ""):
            return
        try:
            v = int(str(raw).strip())
        except ValueError:
            raise ValueError(
                f"{name}={raw!r}: not an integer ({what})") from None
        if v < lo:
            raise ValueError(f"{name}={raw!r}: must be >= {lo} ({what})")

    _int_knob(C.CB_SLOTS_ENV, 1, "slots per bucket")
    _int_knob(C.CB_PARK_MAX_ENV, 0, "max parked rows; 0 disables "
              "preemption while leaving DTPU_CB_PARK armed")
    raw = env.get(C.CB_PARK_ENV)
    if raw not in (None, "") and str(raw).strip().lower() not in (
            "0", "1", "true", "false", "yes", "no", "on", "off"):
        raise ValueError(f"{C.CB_PARK_ENV}={raw!r}: expected a boolean "
                         "('1'/'0')")
    raw = env.get(C.CB_PARK_HBM_FRACTION_ENV)
    if raw not in (None, ""):
        try:
            f = float(str(raw).strip())
        except ValueError:
            raise ValueError(
                f"{C.CB_PARK_HBM_FRACTION_ENV}={raw!r}: not a float "
                "(HBM residency gate)") from None
        if not 0.0 < f <= 1.0:
            raise ValueError(
                f"{C.CB_PARK_HBM_FRACTION_ENV}={raw!r}: must be in "
                "(0, 1] (fraction of the device memory limit)")


class _ParkedRow:
    """One PARKED slot's complete truth, pulled to host (produced by
    the driver thread, held by ``runtime.jobs.ParkedStore``): the
    latent rows mid-schedule, the sigma index to resume at, and the
    ORIGINAL admit timestamp so latency accounting spans the parked
    gap.  PRNG keys are NOT stored — they are a pure function of
    ``(seed, row-index)`` (``samplers.sample_keys``) and are recomputed
    bit-identically at resume, so parking round-trips one f32 buffer,
    not two."""

    __slots__ = ("pid", "item", "sig", "rank", "step", "t_admit",
                 "t_park", "x_rows")

    def __init__(self, item: Dict[str, Any], sig: str, rank: int,
                 step: int, t_admit: float, x_rows: np.ndarray,
                 t_park: float):
        self.pid = str(item["id"])
        self.item = item
        self.sig = sig
        self.rank = int(rank)
        self.step = int(step)
        self.t_admit = float(t_admit)
        self.t_park = float(t_park)
        self.x_rows = x_rows


def quick_eligible(prompt: Dict[str, Any]) -> bool:
    """Cheap enqueue-time screen for step-batchability, layered ON TOP
    of a non-None coalescing signature (which already guarantees the
    safe node set, an EmptyLatentImage source and no hidden state):
    exactly one KSampler + one EmptyLatentImage, a sampler with an
    extracted step callable, integer widgets, and not an orchestrated
    share.  Deep checks (model patches, conditioning shape) happen once
    per signature at bucket build."""
    ks = None
    n_ks = n_el = 0
    for node in prompt.values():
        if not isinstance(node, dict):
            continue
        ct = node.get("class_type")
        if ct == "KSampler":
            n_ks += 1
            ks = node
        elif ct == "EmptyLatentImage":
            n_el += 1
    if n_ks != 1 or n_el != 1 or ks is None:
        return False
    ins = ks.get("inputs", {})
    if str(ins.get("sampler_name")) not in C.CB_SAFE_SAMPLERS:
        return False
    try:
        if int(ins.get("steps", 0)) < 1:
            return False
        if float(ins.get("denoise", 1.0)) <= 0.0:
            return False
        int(ins.get("seed", 0))
    except (TypeError, ValueError):
        return False
    return not is_dispatched_share(prompt)


_KS_LINK_INPUTS = ("model", "positive", "negative", "latent_image")


def tail_nodes(graph, ks_node: str) -> set:
    """The node set a finished slot's decode run actually needs: the
    KSampler plus everything downstream of it, plus those nodes' OTHER
    ancestors (the VAE via CheckpointLoader) — but NOT the sampler's own
    upstream (encode subtree, latent source): ``cb_latent``
    short-circuits the sampler, so re-running CLIP encode per retired
    slot would pay the whole per-prompt encode cost the bucket already
    amortized away."""
    down = {ks_node}
    changed = True
    while changed:
        changed = False
        for nid, node in graph.nodes.items():
            if nid in down:
                continue
            for val in node.inputs.values():
                if isinstance(val, (list, tuple)) and len(val) == 2 \
                        and str(val[0]) in down:
                    down.add(nid)
                    changed = True
                    break
    need = set(down)
    stack = []
    for nid in down:
        if nid == ks_node:
            continue
        for val in graph.nodes[nid].inputs.values():
            if isinstance(val, (list, tuple)) and len(val) == 2 \
                    and str(val[0]) in graph.nodes \
                    and str(val[0]) not in need:
                stack.append(str(val[0]))
    while stack:
        nid = stack.pop()
        if nid in need:
            continue
        need.add(nid)
        for val in graph.nodes[nid].inputs.values():
            if isinstance(val, (list, tuple)) and len(val) == 2 \
                    and str(val[0]) in graph.nodes:
                stack.append(str(val[0]))
    return need


def build_tail_prompt(prompt: Dict[str, Any], keep: set,
                      ks_node: str) -> Dict[str, Any]:
    """API-format tail graph for one retired slot: only ``keep`` nodes,
    with the KSampler's upstream links stripped (cb_latent replaces
    them).  Widget values — including THIS prompt's seed — ride along
    untouched; the PNG still embeds the full original prompt via the
    executor's prompt_json override."""
    out: Dict[str, Any] = {}
    for nid, node in prompt.items():
        if not isinstance(node, dict) or nid not in keep:
            continue
        node = dict(node)
        if nid == ks_node:
            node["inputs"] = {k: v for k, v
                              in dict(node.get("inputs", {})).items()
                              if k not in _KS_LINK_INPUTS}
        out[nid] = node
    return out


# --- shared slot-plumbing executables ----------------------------------------
#
# ONE jitted write/gather/init for the whole process, not one per
# bucket: jax.jit caches per argument shape, so two buckets with the
# same latent geometry share every executable (the per-bucket STEP
# callable already shares through the pipeline's jit cache the same
# way).  Start indices and gather indices ride as traced operands —
# admits at any slot offset and retire cohorts of any composition reuse
# one program per shape pair.

def _lazy_jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


@functools.lru_cache(maxsize=1)
def _write_fn():
    jax, _ = _lazy_jax()

    def write(x, rows, start):
        return jax.lax.dynamic_update_slice(
            x, rows, (start,) + (0,) * (x.ndim - 1))
    return jax.jit(write, donate_argnums=(0,))


@functools.lru_cache(maxsize=1)
def _gather_fn():
    jax, jnp = _lazy_jax()

    def gather(x, idx):
        return jnp.take(x, idx, axis=0)
    # no donation: pad transitions change the output shape, so the
    # input buffer is not reusable (XLA would warn every repad)
    return jax.jit(gather)


@functools.lru_cache(maxsize=64)
def _init_fn(lat_shape: tuple):
    jax, jnp = _lazy_jax()
    from comfyui_distributed_tpu.models import samplers as smp

    def init(keys, sigma0):
        noise = smp.make_noise_fn(keys)(
            jnp.asarray(0x7FFFFFFF, jnp.uint32), lat_shape)
        # mirrors the serial core exactly: zeros latent + noise scaled
        # by the schedule head
        return jnp.zeros((keys.shape[0],) + lat_shape, jnp.float32) \
            + noise * sigma0
    return jax.jit(init)


def _pad_set(max_slots: int) -> List[int]:
    """The declared padded slot-count set, clamped to [1, max_slots]
    and always covering max_slots — every step executes at a size from
    this list, which is what makes "zero steady-state retraces" a shape
    argument instead of a hope."""
    import os
    raw = os.environ.get(C.CB_PAD_BUCKETS_ENV, C.CB_PAD_BUCKETS_DEFAULT)
    pads = set()
    for part in str(raw).split(","):
        part = part.strip()
        if not part:
            continue
        try:
            p = int(part)
        except ValueError:
            continue
        if 1 <= p <= max_slots:
            pads.add(p)
    pads.add(max_slots)
    return sorted(pads)


class _Slot:
    """One admitted prompt's iteration state (plain record; driver-
    thread-only)."""

    __slots__ = ("item", "step", "t_admit")

    def __init__(self, item: Dict[str, Any], t_admit: float):
        self.item = item
        self.step = 0            # next sigma-pair index to execute
        self.t_admit = t_admit


class _Bucket:
    """Persistent padded batch for ONE structural signature.  All state
    is owned by the driver thread; the executor mirrors the few numbers
    the metrics routes need into its lock-guarded stats."""

    def __init__(self, sig: str, item: Dict[str, Any], ctx: OpContext,
                 max_slots: int):
        import jax.numpy as jnp

        from comfyui_distributed_tpu.models import samplers as smp
        from comfyui_distributed_tpu.models import schedules as sch
        from comfyui_distributed_tpu.ops.basic import _prepare_sample_inputs

        self.sig = sig
        prompt = item["prompt"]
        graph = parse_workflow(prompt)
        capture: Dict[str, Any] = {}
        # prefix run: encode nodes execute for real, the KSampler
        # records its resolved inputs and stops the walk
        WorkflowExecutor(ctx).execute(graph, cb_capture=capture)
        if not capture:
            raise CBIneligible("graph never reached a KSampler")
        self.ks_node = next(nid for nid, n in graph.nodes.items()
                            if n.class_type == "KSampler")
        self.tail_keep = tail_nodes(graph, self.ks_node)
        pipe = capture["model"]
        seed = capture["seed"]
        if not isinstance(seed, (int, np.integer)):
            raise CBIneligible("non-plain seed (SeedValue/distributed)")
        lat = capture["latent_image"]
        if lat.get("noise_mask") is not None \
                or lat.get("seed_fixed_batch"):
            raise CBIneligible("masked or fixed-seed-batch latent")
        lat_arr = np.asarray(lat["samples"])
        self.b = int(lat_arr.shape[0])
        self.lat_shape = tuple(int(d) for d in lat_arr.shape[1:])
        self.sampler_name = str(capture["sampler_name"])
        self.cfg = float(capture["cfg"])
        smp.get_sampler_step(self.sampler_name)   # raises on non-step
        for attr in ("sag_params", "hypernets", "deep_shrink_spec",
                     "perp_neg_cond"):
            if getattr(pipe, attr, None):
                raise CBIneligible(f"model patch present: {attr}")
        if float(getattr(pipe, "cfg_rescale", 0.0) or 0.0):
            raise CBIneligible("cfg_rescale patch present")
        self.sigmas_np = np.asarray(sch.compute_sigmas(
            pipe.schedule, str(capture["scheduler"]),
            int(capture["steps"]), float(capture["denoise"])), np.float32)
        if self.sigmas_np.shape[0] < 2:
            raise CBIneligible("degenerate sigma schedule")
        self.n_steps = int(self.sigmas_np.shape[0]) - 1
        self.pipe = pipe
        self.capacity = int(max_slots)
        self.pads = _pad_set(self.capacity)
        rows_max = self.capacity * self.b
        # bucket-shared conditioning at max padded rows, built by the
        # SAME preamble the serial sampler uses — a slot's context rows
        # are value-identical to its serial run's (repeat of one row)
        prep = _prepare_sample_inputs(
            ctx, pipe, 0,
            {"samples": jnp.zeros((rows_max,) + self.lat_shape,
                                  jnp.float32),
             "local_batch": rows_max, "fanout": 1},
            capture["positive"], capture["negative"])
        if prep.control is not None or prep.noise_mask is not None \
                or prep.mid_context is not None \
                or prep.c_concat is not None \
                or prep.gligen_objs is not None \
                or isinstance(prep.y, (list, tuple)) \
                or isinstance(prep.context, list) \
                or isinstance(prep.uncond, list):
            raise CBIneligible("conditioning shape outside the plain "
                               "single-entry CFG case")
        # 2-D tensor-parallel composition (ISSUE 16): when the live mesh
        # has an engaged tensor axis, the persistent padded batch lives
        # 2-D-sharded — rows over "data", UNet internals over "tensor"
        # (the step fn's params/constraints handle the latter).  _pin()
        # normalizes every rows-leading buffer onto ONE canonical layout
        # per pad (rows on data when divisible, else replicated), so the
        # step executable sees a single input sharding per pad and the
        # zero-steady-state-retrace argument survives sharding.  Without
        # a tensor axis _pin is identity and nothing here changes.
        from comfyui_distributed_tpu.parallel import sharding as shd
        self._shd = shd
        self._tp_mesh = shd.serving_mesh()
        self._ctx_full = self._pin(prep.context)
        self._unc_full = self._pin(prep.uncond)
        self._y_full = self._pin(prep.y)
        self.has_y = prep.y is not None
        self._per_pad: Dict[int, tuple] = {}
        # process-shared slot-plumbing executables (module docstring):
        # same-geometry buckets reuse one compile
        self._write = _write_fn()
        self._permute = _gather_fn()
        self._init_rows = _init_fn(self.lat_shape)
        self._jnp = jnp
        self.slots: List[_Slot] = []      # dense: slot i owns rows [i*b, (i+1)*b)
        self.pad = self.pads[0]
        self.x = self._pin(jnp.zeros((self.pad * self.b,) + self.lat_shape,
                                     jnp.float32))
        self.keys = self._pin(jnp.zeros((self.pad * self.b, 2), jnp.uint32))
        self.admits = 0
        self.retires = 0
        self.steps_done = 0
        self.retraces = 0
        self.pad_transitions = 0
        self.last_active = time.monotonic()

    # -- geometry -------------------------------------------------------------

    def _pin(self, x):
        """Canonical 2-D bucket layout for a rows-leading array (identity
        when no tensor axis is engaged, or for None leaves)."""
        if x is None or self._tp_mesh is None:
            return x
        return self._shd.put_rows(x, self._tp_mesh)

    @property
    def n_active(self) -> int:
        return len(self.slots)

    def _pad_for(self, n: int) -> int:
        for p in self.pads:
            if p >= max(n, 1):
                return p
        return self.pads[-1]

    def _repad(self, keep: List[int],
               target: Optional[int] = None) -> None:
        """Rebuild the padded batch keeping ``keep``'s slots (old slot
        indices, in order) densely at the front, padded for ``target``
        slots (defaults to ``len(keep)``; an admit passes the count
        INCLUDING the incoming slot, or the write would land past the
        buffer and lax would clamp it onto slot 0).  ONE gather per
        array — the executable depends only on the (rows_in, rows_out)
        shape pair, never on which slots moved."""
        jnp = self._jnp
        new_pad = self._pad_for(target if target is not None
                                else len(keep))
        perm = np.zeros(new_pad * self.b, np.int32)
        for new_i, old_i in enumerate(keep):
            perm[new_i * self.b:(new_i + 1) * self.b] = np.arange(
                old_i * self.b, (old_i + 1) * self.b, dtype=np.int32)
        idx = jnp.asarray(perm)
        self.x = self._pin(self._permute(self.x, idx))
        self.keys = self._pin(self._permute(self.keys, idx))
        if new_pad != self.pad:
            self.pad_transitions += 1
        self.pad = new_pad

    # -- admit / step / retire (driver thread only) ---------------------------

    def admit(self, item: Dict[str, Any]) -> int:
        """Join ONE prompt at the current step boundary; returns its
        slot index."""
        return self.admit_many([item])

    def admit_many(self, items: List[Dict[str, Any]]) -> int:
        """Join a same-signature group at the current step boundary
        with ONE device round trip (one key build, one init-noise call,
        one write) — admission's analog of the cohort-batched retire.
        Returns the first slot index.  Every slot's keys/init noise are
        EXACTLY its serial run's: ``sample_keys(full(b, seed),
        arange(b))`` per slot (the stacked build vmaps the identical
        per-row fold-ins) and ``zeros + noise * sigmas[0]``."""
        from comfyui_distributed_tpu.models import samplers as smp
        jnp = self._jnp
        k = len(items)
        n = self.n_active
        if n + k > self.capacity:
            raise RuntimeError("bucket full (driver admitted past room)")
        if n + k > self.pad:
            # grow along the pad set, sized for the incoming slots
            self._repad(list(range(n)), target=n + k)
        seeds = np.repeat(np.asarray(
            [int(it["prompt"][self.ks_node]["inputs"].get("seed", 0))
             for it in items], np.uint64), self.b)
        idx = np.tile(np.arange(self.b, dtype=np.uint32), k)
        keys_rows = smp.sample_keys(seeds, idx)
        x_rows = self._init_rows(keys_rows,
                                 jnp.asarray(self.sigmas_np[0]))
        start = jnp.asarray(n * self.b, jnp.int32)
        self.x = self._pin(self._write(self.x, x_rows, start))
        self.keys = self._pin(
            self._write(self.keys, jnp.asarray(keys_rows), start))
        # perf_counter, matching every other finalize t0 producer
        # (monotonic shares its epoch only on Linux)
        now = time.perf_counter()
        for it in items:
            self.slots.append(_Slot(it, now))
        self.admits += k
        self.last_active = time.monotonic()
        return n

    def step_once(self) -> None:
        """Advance every active slot ONE step of ITS OWN schedule: one
        jitted call over the padded batch with per-row sigma/step
        vectors; padding rows are masked through unchanged."""
        jnp = self._jnp
        rows = self.pad * self.b
        sigma = np.ones((rows,), np.float32)
        sigma_next = np.ones((rows,), np.float32)
        step_v = np.zeros((rows,), np.int32)
        active = np.zeros((rows,), bool)
        for i, slot in enumerate(self.slots):
            lo, hi = i * self.b, (i + 1) * self.b
            sigma[lo:hi] = self.sigmas_np[slot.step]
            sigma_next[lo:hi] = self.sigmas_np[slot.step + 1]
            step_v[lo:hi] = slot.step
            active[lo:hi] = True
        key = (rows, self.has_y)
        cached = self._per_pad.get(key)
        if cached is None:
            # per-pad conditioning slices are cached AND pinned once:
            # their sharding is part of the step executable's signature
            cached = (self._pin(self._ctx_full[:rows]),
                      self._pin(self._unc_full[:rows]),
                      self._pin(self._y_full[:rows]) if self.has_y
                      else None,
                      self.pipe.denoise_step_fn(
                          self.sampler_name, self.cfg, rows,
                          self.lat_shape, has_y=self.has_y))
            self._per_pad[key] = cached
        ctx_r, unc_r, y_r, fn = cached
        self.x = fn(self.pipe.unet_params, self.x, ctx_r, unc_r, y_r,
                    self.keys, jnp.asarray(sigma),
                    jnp.asarray(sigma_next), jnp.asarray(step_v),
                    jnp.asarray(active))
        for slot in self.slots:
            slot.step += 1
        self.steps_done += 1
        self.last_active = time.monotonic()

    def take_finished(self) -> List[tuple]:
        """Slice out finished slots' latent rows and compact the batch
        (pad shrinks along the pad set).  Returns retirement COHORTS —
        ``[(items, rows, t_admit_first), ...]`` with ``rows`` the
        cohort's stacked latents in item order: slots that exit the
        same boundary share one batched decode tail (split_images +
        per-prompt PNG metadata, the PR 2 machinery), amortizing the
        per-prompt tail cost exactly like admission amortized the
        per-prompt encode.  The batch keeps stepping — nothing
        drains."""
        jnp = self._jnp
        done = [i for i, s in enumerate(self.slots)
                if s.step >= self.n_steps]
        if not done:
            return []
        perm = np.concatenate(
            [np.arange(i * self.b, (i + 1) * self.b,
                       dtype=np.int32) for i in done])
        rows = self._permute(self.x, jnp.asarray(perm))
        items = [self.slots[i].item for i in done]
        t0 = min(self.slots[i].t_admit for i in done)
        out = [(items, rows, t0)]
        keep = [i for i, s in enumerate(self.slots)
                if s.step < self.n_steps]
        self.slots = [self.slots[i] for i in keep]
        self._repad(keep)
        self.retires += len(done)
        return out

    def drop_slots(self, drop: List[int]) -> List[Dict[str, Any]]:
        """Slice out specific slots at a step boundary (client-gone
        cancellation): their rows leave the batch, the pad compacts
        along the pad set, the rest keep stepping.  Returns the dropped
        items."""
        doomed = set(drop)
        items = [self.slots[i].item for i in sorted(doomed)]
        keep = [i for i in range(len(self.slots)) if i not in doomed]
        self.slots = [self.slots[i] for i in keep]
        self._repad(keep)
        return items

    def park_slots(self, park: List[int]) -> List[tuple]:
        """PARK: slice out ``park``'s slots at a step boundary with
        their latent rows pulled to HOST — the latent-paging exit
        (ISSUE 17).  Returns ``[(item, step, t_admit, x_rows), ...]``
        with ``x_rows`` a host f32 copy of the slot's ``b`` rows (a
        sharded 2-D mesh buffer gathers cleanly; ``resume_parked``'s
        ``_pin`` restores the canonical layout).  Duplicate or
        out-of-range indices raise — a double-park would fork one
        slot's truth into two records.  Device work is ONE gather (the
        same ``(pad*b -> k*b)`` shape pair a retire cohort uses) plus
        the compaction repad — no executables outside the warmed set."""
        jnp = self._jnp
        if len(set(park)) != len(park):
            raise ValueError(f"double-park of slot(s) {sorted(park)}")
        for i in park:
            if not 0 <= i < len(self.slots):
                raise ValueError(f"park of unknown slot {i} "
                                 f"({len(self.slots)} active)")
        order = sorted(park)
        perm = np.concatenate(
            [np.arange(i * self.b, (i + 1) * self.b, dtype=np.int32)
             for i in order])
        rows = np.asarray(self._permute(self.x, jnp.asarray(perm)))
        out = []
        for n, i in enumerate(order):
            s = self.slots[i]
            out.append((s.item, s.step, s.t_admit,
                        rows[n * self.b:(n + 1) * self.b]))
        doomed = set(order)
        keep = [i for i in range(len(self.slots)) if i not in doomed]
        self.slots = [self.slots[i] for i in keep]
        self._repad(keep)
        return out

    def resume_parked(self, recs: List[Any]) -> int:
        """RESUME: the exact inverse of :meth:`park_slots`, at a later
        step boundary.  Latent rows are written back from the host
        copies and the per-row PRNG keys are REBUILT from each prompt's
        seed — the same ``sample_keys(repeat(seed), arange(b))``
        expression admission used, so the resumed slot's remaining
        steps consume exactly the key stream its serial run would.
        Bit-exactness is an identity argument (f32 host round trip +
        deterministic key derivation), not a tolerance.  Device work is
        the admit path's ``(k*b)`` write pair — no new executables —
        and ``_pin`` restores the canonical 2-D mesh layout.  Returns
        the first slot index."""
        from comfyui_distributed_tpu.models import samplers as smp
        jnp = self._jnp
        k = len(recs)
        n = self.n_active
        if n + k > self.capacity:
            raise RuntimeError("bucket full (driver resumed past room)")
        if n + k > self.pad:
            self._repad(list(range(n)), target=n + k)
        x_rows = jnp.asarray(np.concatenate(
            [np.asarray(r.x_rows, np.float32) for r in recs]))
        seeds = np.repeat(np.asarray(
            [int(r.item["prompt"][self.ks_node]["inputs"].get("seed", 0))
             for r in recs], np.uint64), self.b)
        idx = np.tile(np.arange(self.b, dtype=np.uint32), k)
        keys_rows = smp.sample_keys(seeds, idx)
        start = jnp.asarray(n * self.b, jnp.int32)
        self.x = self._pin(self._write(self.x, x_rows, start))
        self.keys = self._pin(
            self._write(self.keys, jnp.asarray(keys_rows), start))
        for r in recs:
            slot = _Slot(r.item, r.t_admit)
            slot.step = int(r.step)
            self.slots.append(slot)
        self.last_active = time.monotonic()
        return n

    def abort_all(self) -> List[Dict[str, Any]]:
        items = [s.item for s in self.slots]
        self.slots = []
        self._repad([])
        return items


class ContinuousBatchExecutor:
    """The DTPU_CB=1 queue consumer: driver + tail + fallback threads
    over one ServerState.  See the module docstring for the model."""

    def __init__(self, state: Any):
        import os
        self.state = state
        self.max_slots = max(1, int(os.environ.get(
            C.CB_SLOTS_ENV, C.CB_SLOTS_DEFAULT)))
        self.max_buckets = max(1, int(os.environ.get(
            C.CB_MAX_BUCKETS_ENV, C.CB_MAX_BUCKETS_DEFAULT)))
        try:
            self.admit_window = max(0.0, float(os.environ.get(
                C.CB_ADMIT_WINDOW_ENV, C.CB_ADMIT_WINDOW_DEFAULT)))
        except ValueError:
            self.admit_window = C.CB_ADMIT_WINDOW_DEFAULT
        # latent paging + SLO-aware preemption (ISSUE 17): DTPU_CB_PARK=1
        # arms the park/resume plane; the ParkedStore is the beyond-HBM
        # working set (capacity 0 when disarmed keeps every park path
        # structurally unreachable — ParkedStore.room() == 0)
        self.park_enabled = str(os.environ.get(
            C.CB_PARK_ENV, "0")).strip().lower() in ("1", "true",
                                                     "yes", "on")
        try:
            park_max = max(0, int(os.environ.get(
                C.CB_PARK_MAX_ENV, C.CB_PARK_MAX_DEFAULT)))
        except ValueError:
            park_max = C.CB_PARK_MAX_DEFAULT
        try:
            self.park_hbm_fraction = float(os.environ.get(
                C.CB_PARK_HBM_FRACTION_ENV,
                C.CB_PARK_HBM_FRACTION_DEFAULT))
        except ValueError:
            self.park_hbm_fraction = C.CB_PARK_HBM_FRACTION_DEFAULT
        from comfyui_distributed_tpu.runtime.jobs import ParkedStore
        self.parked = ParkedStore(park_max if self.park_enabled else 0)
        self._mem_probe = None    # test seam; None -> PR 5 telemetry
        self._buckets: "Dict[str, _Bucket]" = {}   # driver thread only
        self._bad_sigs: set = set()                # driver thread only
        self._rr: int = 0                          # round-robin cursor
        self._tail_q: "queue_mod.Queue" = queue_mod.Queue()
        self._fallback_q: "queue_mod.Queue" = queue_mod.Queue()
        self._fallback_busy = False                # driver + fallback
        self._stop = False
        self._lock = threading.Lock()
        self._stats = {"admits": 0, "retires": 0, "steps": 0,
                       "fallbacks": 0, "retraces": 0,
                       "pad_transitions": 0,
                       "abandoned": 0,
                       "parks": 0, "resumes": 0,
                       "preemptions": 0}           # guarded-by: self._lock
        self._bucket_stats: Dict[str, Dict[str, Any]] = {}  # guarded-by: self._lock
        self._active = 0                           # guarded-by: self._lock
        self._tailing = 0                          # guarded-by: self._lock
        # flight deck (ISSUE 18): step-boundary occupancy timeline ring
        # + admit-to-first-step latency — the observability face of the
        # continuous-batching plane, rendered by `cli flightdeck`
        try:
            deck_ring = max(1, int(os.environ.get(
                C.CB_DECK_RING_ENV, C.CB_DECK_RING_DEFAULT)))
        except ValueError:
            deck_ring = C.CB_DECK_RING_DEFAULT
        self._deck: deque = deque(maxlen=deck_ring)  # guarded-by: self._lock
        self._deck_seq = 0                         # guarded-by: self._lock
        self._deck_prev = {"admits": 0, "retires": 0,
                           "preemptions": 0}       # driver thread only
        self.admit_to_first_step = trace_mod.LatencyHistogram()

    # -- lifecycle ------------------------------------------------------------

    def start(self) -> None:
        for name, target in (("dtpu-cb-drive", self._drive),
                             ("dtpu-cb-tail", self._tail_loop),
                             ("dtpu-cb-fallback", self._fallback_loop)):
            threading.Thread(target=target, daemon=True, name=name).start()

    def stop(self) -> None:
        self._stop = True

    # -- cross-thread views ---------------------------------------------------

    def active_prompts(self) -> int:
        # parked rows are deliberately NOT counted here: queue_remaining
        # feeds the autoscaler's queue_depth_fn, and the parked backlog
        # folds into that signal ONCE through parked_backlog_fn (its own
        # attributed term) — counting it here too would double it.  The
        # parked store has its own admission cap (DTPU_CB_PARK_MAX), and
        # drain correctness rides on idle(), which does count parked.
        with self._lock:
            return self._active + self._tailing

    def parked_count(self) -> int:
        """Parked-backlog level for the autoscaler and metrics (any
        thread; ParkedStore is self-locked)."""
        return self.parked.count()

    def idle(self) -> bool:
        with self._lock:
            busy = self._active or self._tailing or self._fallback_busy
        return not busy and self._fallback_q.empty() \
            and self._tail_q.empty() and self.parked.count() == 0

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            stats = dict(self._stats)
            buckets = [dict(v) for v in self._bucket_stats.values()]
            active = self._active
            deck = [dict(r) for r in self._deck]
            deck_ring = self._deck.maxlen
        slots_total = self.max_buckets * self.max_slots
        return {
            "enabled": True,
            "max_slots": self.max_slots,
            "max_buckets": self.max_buckets,
            "pad_buckets": _pad_set(self.max_slots),
            "slots_active": active,
            "slots_free": max(slots_total - active, 0),
            "buckets": buckets,
            "park_enabled": self.park_enabled,
            "parked": self.parked.count(),
            "park_room": self.parked.room(),
            "deck": deck,
            "deck_ring": deck_ring,
            "admit_to_first_step": self.admit_to_first_step.snapshot(),
            **stats,
        }

    def _deck_record(self, bkt: _Bucket) -> None:
        """One step-boundary occupancy row into the flight-deck ring:
        busy/parked/free slots plus the admits/retires/preemptions that
        landed since the previous boundary (driver thread writes; the
        scrape routes read the ring under the lock)."""
        parked = self.parked.count()
        with self._lock:
            cur = {k: self._stats[k] for k in self._deck_prev}
            self._deck.append({
                "seq": self._deck_seq, "t": round(time.time(), 3),
                "bucket": bkt.sig[:8],
                "busy": bkt.n_active,
                "free": max(bkt.capacity - bkt.n_active, 0),
                "parked": parked,
                "admits": cur["admits"] - self._deck_prev["admits"],
                "retires": cur["retires"] - self._deck_prev["retires"],
                "preemptions": cur["preemptions"]
                - self._deck_prev["preemptions"],
            })
            self._deck_seq += 1
        self._deck_prev = cur

    def _mirror_stats(self) -> None:
        """Driver -> metrics handoff: copy the driver-owned bucket
        numbers into the lock-guarded view the scrape routes read."""
        per = {
            b.sig: {"sig": b.sig[:8], "slots_active": b.n_active,
                    "slots_max": b.capacity, "pad": b.pad,
                    "batch_rows": b.pad * b.b, "admits": b.admits,
                    "retires": b.retires, "steps": b.steps_done,
                    "retraces": b.retraces,
                    "pad_transitions": b.pad_transitions}
            for b in self._buckets.values()}
        active = sum(b.n_active for b in self._buckets.values())
        with self._lock:
            self._bucket_stats = per
            self._active = active
            self._stats["pad_transitions"] = sum(
                b.pad_transitions for b in self._buckets.values())

    # -- admission ------------------------------------------------------------

    def _class_of(self, item: Dict[str, Any]) -> str:
        return str(item.get("tenant")
                   or self.state.admission.default_class)

    def _preemptible(self, bkt: _Bucket, item: Dict[str, Any]) -> int:
        """How many of ``bkt``'s slots a would-be admit of ``item`` may
        PARK: slots whose tenant class ranks strictly below the
        incoming class in the preempt order (batch < free < paid; a
        paid-class row is never parked)."""
        new_rank = _class_rank(self._class_of(item))
        return sum(1 for s in bkt.slots
                   if _class_rank(self._class_of(s.item)) < new_rank)

    def room_for(self, item: Dict[str, Any]) -> int:
        """scheduler.pop_cb_admit capacity oracle: >0 = admit that many
        now, -1 = batchable but full (defer; a slot exit will free
        room), 0 = not batchable (legacy fallback).  With latent paging
        armed (DTPU_CB_PARK=1) a full bucket is no longer a hard -1: a
        higher-class item may claim as many slots as the bucket holds
        lower-class rows (bounded by parked-store room) — the actual
        park happens in _admit_cb at the same boundary."""
        sig = item.get("sig")
        if not item.get("cb") or sig is None or sig in self._bad_sigs:
            return 0
        bkt = self._buckets.get(sig)
        if bkt is not None:
            free = bkt.capacity - bkt.n_active
            if free > 0:
                return free
            if self.park_enabled:
                k = min(self._preemptible(bkt, item), self.parked.room())
                if k > 0:
                    return k
            return -1
        if len(self._buckets) < self.max_buckets:
            return self.max_slots
        # all bucket tables taken: an idle one can be evicted
        if any(b.n_active == 0 for b in self._buckets.values()):
            return self.max_slots
        return -1

    def _evict_idle_bucket(self) -> None:
        # a bucket whose every row is PARKED is idle-by-count but not
        # evictable: its captured conditioning is the only thing the
        # parked rows can resume into
        parked_sigs = set(self.parked.sigs())
        idle = [(b.last_active, sig) for sig, b in self._buckets.items()
                if b.n_active == 0 and sig not in parked_sigs]
        if idle:
            _, sig = min(idle)
            self._buckets.pop(sig, None)
            debug_log(f"cb: evicted idle bucket {sig[:8]}")

    def _fresh_ctx(self) -> OpContext:
        from comfyui_distributed_tpu.parallel.mesh import get_runtime
        st = self.state
        return OpContext(
            runtime=get_runtime(), models_dir=st.models_dir,
            input_dir=st.input_dir, output_dir=st.output_dir,
            is_worker=st.is_worker, job_store=st.jobs,
            server_loop=st.loop, interrupt_event=st.interrupt_event,
            host_pool=st.host_pool, cluster=st.cluster,
            ledger=st.ledger, fault_inject=st.fault_inject)

    @staticmethod
    def _record_queue_wait(items: List[Dict[str, Any]]) -> None:
        now = time.perf_counter()
        now_wall = time.time()
        for item in items:
            wait = now - item.get("t_enq", now)
            trace_mod.record_stage("queue_wait", now_wall - wait, now_wall,
                                   parent=item.get("span"))

    def _admit_boundary(self) -> bool:
        """Pop-and-admit at a step boundary until the queue, capacity or
        fairness says stop.  Returns True when anything was dispatched
        (admitted or handed to the fallback)."""
        st = self.state
        st._purge_abandoned()
        got = False
        while not self._stop:
            if not st._exec_gate.is_set():
                break
            with st._queue_lock:
                if not st._queue:
                    st._queue_event.clear()
                    break
                kind, items = sched_mod.pop_cb_admit(
                    st._queue, st.admission, self.room_for,
                    fallback_ok=not self._fallback_busy,
                    legacy_max=st.coalesce_max
                    if st.coalesce_enabled else 1)
                if kind == "fallback":
                    st._running = True
                    self._fallback_busy = True
            if kind == "defer" or not items:
                break
            self._record_queue_wait(items)
            if kind == "fallback":
                with self._lock:
                    self._stats["fallbacks"] += len(items)
                self._fallback_q.put(items)
                got = True
                continue
            got = True
            self._admit_cb(items)
        if got:
            self._mirror_stats()
        return got

    def _admit_cb(self, items: List[Dict[str, Any]]) -> None:
        sig = items[0]["sig"]
        bkt = self._buckets.get(sig)
        if bkt is None:
            if len(self._buckets) >= self.max_buckets:
                self._evict_idle_bucket()
            try:
                bkt = _Bucket(sig, items[0], self._fresh_ctx(),
                              self.max_slots)
            except Exception as e:  # noqa: BLE001 - route to fallback
                self._bad_sigs.add(sig)
                if not isinstance(e, CBIneligible):
                    log(f"cb: bucket build failed for {sig[:8]}: "
                        f"{type(e).__name__}: {e}")
                else:
                    debug_log(f"cb: {sig[:8]} ineligible: {e}")
                with self._lock:
                    self._stats["fallbacks"] += len(items)
                    self._fallback_busy = True
                with self.state._queue_lock:
                    self.state._running = True
                self._fallback_q.put(items)
                return
            self._buckets[sig] = bkt
        # SLO preemption (ISSUE 17): when the group was admitted INTO a
        # full bucket (room_for counted preemptible lower-class rows),
        # park the victims first so admit_many sees real free slots
        need = bkt.n_active + len(items) - bkt.capacity
        if need > 0 and self.park_enabled:
            self._park_victims(bkt, need, items[0])
        now_wall = time.time()
        try:
            # whole group in one device round trip (one key build, one
            # init-noise call, one write)
            first_slot = bkt.admit_many(items)
        except Exception as e:  # noqa: BLE001 - items are already popped
            # the prompts must not vanish: a failed admission (device
            # OOM growing the pad, a poisoned compile) routes the group
            # to the fallback executor, which runs or error-finalizes
            # them with history entries either way
            log(f"cb: admit failed for {sig[:8]}: "
                f"{type(e).__name__}: {e}")
            self._bad_sigs.add(sig)
            self._buckets.pop(sig, None)
            self._fail_parked(sig, e)
            for slot in bkt.abort_all():
                self.state._finalize_hand([slot], None, e,
                                          time.perf_counter())
            with self._lock:
                self._stats["fallbacks"] += len(items)
                self._fallback_busy = True
            with self.state._queue_lock:
                self.state._running = True
            self._fallback_q.put(items)
            return
        trace_mod.GLOBAL_COUNTERS.bump("cb_admits", len(items))
        with self._lock:
            self._stats["admits"] += len(items)
        for off, item in enumerate(items):
            if item.get("span") is not None:
                trace_mod.event_span(
                    "cb_admit", now_wall, now_wall,
                    parent=item["span"],
                    attrs={"bucket": sig[:8],
                           "slot": first_slot + off})
            debug_log(f"cb: {item['id']} joined bucket {sig[:8]} "
                      f"slot {first_slot + off} "
                      f"({bkt.n_active}/{bkt.capacity})")

    # -- latent paging: park / resume (driver thread only) --------------------

    def _park_victims(self, bkt: _Bucket, need: int,
                      incoming: Dict[str, Any]) -> None:
        """SLO preemption: park up to ``need`` lowest-class slots to
        free room for ``incoming``.  Victim order is lowest rank first,
        then YOUNGEST admit first within a rank — the oldest started
        work keeps its slot and finishes, bounding batch-tier
        completion delay instead of starving one unlucky prompt."""
        new_rank = _class_rank(self._class_of(incoming))
        cands = [(i, s) for i, s in enumerate(bkt.slots)
                 if _class_rank(self._class_of(s.item)) < new_rank]
        cands.sort(key=lambda t: (
            _class_rank(self._class_of(t[1].item)), -t[1].t_admit))
        victims = [i for i, _ in
                   cands[:min(need, len(cands), self.parked.room())]]
        if victims:
            self._park_out(bkt, victims, preempted_by=incoming)

    def _park_out(self, bkt: _Bucket, indices: List[int],
                  preempted_by: Optional[Dict[str, Any]] = None) -> None:
        """Pull ``indices``'s slots to host and register them with the
        ParkedStore; emits cb_park spans and the parked gauge.  The
        ONLY writer of parked records (with _resume_boundary as the
        only reader) — slot-state mutation never leaves this file
        (dtpu-lint cb-slot-state-discipline)."""
        t_park = time.perf_counter()
        now_wall = time.time()
        recs = [
            _ParkedRow(item, bkt.sig,
                       _class_rank(self._class_of(item)),
                       step, t_admit, x_rows, t_park)
            for item, step, t_admit, x_rows in bkt.park_slots(indices)]
        self.parked.park(recs)
        trace_mod.GLOBAL_COUNTERS.bump("cb_parks", len(recs))
        if preempted_by is not None:
            trace_mod.GLOBAL_COUNTERS.bump("cb_preemptions", len(recs))
        trace_mod.GLOBAL_GAUGES.set("cb_parked", self.parked.count())
        with self._lock:
            self._stats["parks"] += len(recs)
            if preempted_by is not None:
                self._stats["preemptions"] += len(recs)
        for rec in recs:
            if rec.item.get("span") is not None:
                attrs = {"bucket": bkt.sig[:8], "step": rec.step,
                         "tenant": self._class_of(rec.item)}
                if preempted_by is not None:
                    attrs["preempted_by"] = self._class_of(preempted_by)
                trace_mod.event_span("cb_park", now_wall, now_wall,
                                     parent=rec.item["span"],
                                     attrs=attrs)
            debug_log(f"cb: {rec.pid} parked from bucket {bkt.sig[:8]} "
                      f"at step {rec.step} "
                      f"({self.parked.count()} parked)")

    def _mem_fraction(self) -> Optional[float]:
        """PR 5 telemetry residency gate: fraction of the accelerator
        memory limit in use, or None when the backend exposes no limit
        (CPU) — in which case only slot pressure drives paging."""
        probe = self._mem_probe
        if probe is None:
            from comfyui_distributed_tpu.utils import resource as res_mod
            probe = res_mod.device_memory_snapshot
        try:
            snap = probe() or {}
        except Exception:  # noqa: BLE001 - telemetry must not kill the driver
            return None
        limit = snap.get("bytes_limit")
        if not limit:
            return None
        return float(snap.get("bytes_in_use", 0) or 0) / float(limit)

    def _pressure_park(self) -> None:
        """Residency under memory pressure: above the HBM fraction,
        shed ONE lowest-class slot per boundary to host (the compaction
        repad shrinks the live buffers along the pad set) — gradual on
        purpose, so a transient allocation spike doesn't evict the
        whole batch tier in a burst."""
        if self.parked.room() <= 0:
            return
        frac = self._mem_fraction()
        if frac is None or frac < self.park_hbm_fraction:
            return
        best = None   # ((rank, -t_admit), bucket, slot index)
        for bkt in self._buckets.values():
            for i, s in enumerate(bkt.slots):
                r = _class_rank(self._class_of(s.item))
                if r >= len(C.CB_PREEMPT_ORDER):
                    continue
                key = (r, -s.t_admit)
                if best is None or key < best[0]:
                    best = (key, bkt, i)
        if best is not None:
            self._park_out(best[1], [best[2]])
            self._mirror_stats()

    def _drop_abandoned_parked(self) -> None:
        """PR 13 client-gone composed with paging: a parked row whose
        client disconnected is FREED — finalized as abandoned — instead
        of resumed (resuming it would spend denoise steps on an image
        nobody can receive)."""
        gone = self.parked.pop_abandoned(
            reuse_mod.PREVIEWS.is_abandoned)
        if not gone:
            return
        err = reuse_mod.AbandonedError(
            "client disconnected while parked")
        now_wall = time.time()
        trace_mod.GLOBAL_COUNTERS.bump("cb_abandoned", len(gone))
        trace_mod.GLOBAL_GAUGES.set("cb_parked", self.parked.count())
        with self._lock:
            self._stats["abandoned"] += len(gone)
        for rec in gone:
            if rec.item.get("span") is not None:
                trace_mod.event_span("cb_exit", now_wall, now_wall,
                                     parent=rec.item["span"],
                                     attrs={"bucket": rec.sig[:8]})
            debug_log(f"cb: parked {rec.pid} abandoned (client gone); "
                      "row freed without resume")
            self.state._finalize_hand([rec.item], None, err,
                                      time.perf_counter())

    def _fail_parked(self, sig: str, err: BaseException) -> None:
        """A bucket died (poisoned step / failed admit) while rows of
        its signature were parked: their captured conditioning died
        with it, so the rows error-finalize instead of waiting on a
        resume that can never come."""
        recs = self.parked.pop_for(sig, self.parked.count())
        if not recs:
            return
        trace_mod.GLOBAL_GAUGES.set("cb_parked", self.parked.count())
        for rec in recs:
            self.state._finalize_hand([rec.item], None, err,
                                      time.perf_counter())

    def _resume_boundary(self) -> bool:
        """The residency scheduler's resume half, run every boundary:
        refill free slots from the parked store — highest class first,
        FIFO within a class — gated on PR 5 memory telemetry (no
        resume while HBM use sits above DTPU_CB_PARK_HBM_FRACTION:
        re-admitting rows under pressure would undo the shed).  Runs
        AFTER queue admission, so stride-fair dequeue keeps first claim
        on free slots and a resumed row is never immediately re-parked
        by the same boundary's admit (no park/resume thrash).  Returns
        True when anything resumed."""
        if self.parked.count() == 0:
            return False
        self._drop_abandoned_parked()
        frac = self._mem_fraction()
        if frac is not None and frac >= self.park_hbm_fraction:
            return False
        moved = False
        for sig in self.parked.sigs():
            bkt = self._buckets.get(sig)
            if bkt is None:
                # evicted-while-parked is prevented (_evict_idle_bucket
                # skips parked sigs); reaching here means the bucket
                # died on an error path that already blacklisted it
                self._fail_parked(sig, RuntimeError(
                    f"bucket {sig[:8]} lost while rows were parked"))
                continue
            free = bkt.capacity - bkt.n_active
            if free <= 0:
                continue
            recs = self.parked.pop_for(sig, free)
            if not recs:
                continue
            now_wall = time.time()
            try:
                first_slot = bkt.resume_parked(recs)
            except Exception as e:  # noqa: BLE001 - rows must not vanish
                log(f"cb: resume failed in bucket {sig[:8]}: "
                    f"{type(e).__name__}: {e}")
                for rec in recs:
                    self.state._finalize_hand([rec.item], None, e,
                                              time.perf_counter())
                continue
            moved = True
            trace_mod.GLOBAL_COUNTERS.bump("cb_resumes", len(recs))
            with self._lock:
                self._stats["resumes"] += len(recs)
            for off, rec in enumerate(recs):
                if rec.item.get("span") is not None:
                    trace_mod.event_span(
                        "cb_resume", now_wall, now_wall,
                        parent=rec.item["span"],
                        attrs={"bucket": sig[:8],
                               "slot": first_slot + off,
                               "step": rec.step})
                debug_log(f"cb: {rec.pid} resumed into bucket "
                          f"{sig[:8]} slot {first_slot + off} "
                          f"at step {rec.step}")
            # no-op resume: a row parked AT its final boundary has no
            # steps left — retire it straight to the decode tail
            self._retire_cohorts(bkt)
        if moved:
            trace_mod.GLOBAL_GAUGES.set("cb_parked",
                                        self.parked.count())
            self._mirror_stats()
        return moved

    # -- the step loop --------------------------------------------------------

    def _next_bucket(self) -> Optional[_Bucket]:
        live = [b for b in self._buckets.values() if b.n_active]
        if not live:
            return None
        self._rr = (self._rr + 1) % len(live)
        return live[self._rr]

    def _drop_abandoned(self, bkt: _Bucket) -> None:
        """Client-gone cancellation (runtime/reuse.PreviewBus): slots
        whose last preview subscriber disconnected exit HERE, at the
        step boundary — their rows leave the batch immediately (freeing
        the slot for the next admit), and the job finalizes as
        ``abandoned`` (history/WAL/span all record it)."""
        bus = reuse_mod.PREVIEWS
        doomed = [i for i, s in enumerate(bkt.slots)
                  if bus.is_abandoned(s.item["id"])]
        if not doomed:
            return
        items = bkt.drop_slots(doomed)
        err = reuse_mod.AbandonedError(
            "client disconnected mid-denoise")
        now_wall = time.time()
        trace_mod.GLOBAL_COUNTERS.bump("cb_abandoned", len(items))
        with self._lock:
            self._stats["abandoned"] += len(items)
        for item in items:
            if item.get("span") is not None:
                trace_mod.event_span("cb_exit", now_wall, now_wall,
                                     parent=item["span"],
                                     attrs={"bucket": bkt.sig[:8]})
            debug_log(f"cb: {item['id']} abandoned (client gone); "
                      f"slot freed at step boundary")
            self.state._finalize_hand([item], None, err,
                                      time.perf_counter())
        self._mirror_stats()

    def _publish_previews(self, bkt: _Bucket) -> None:
        """Step-wise progressive previews: one cheap latent->RGB frame
        per WATCHED slot every DTPU_PREVIEW_EVERY boundaries.  The
        wants() screen keeps the unwatched steady state at one dict
        lookup per active slot."""
        bus = reuse_mod.PREVIEWS
        every = reuse_mod.preview_every()
        for i, slot in enumerate(bkt.slots):
            pid = slot.item["id"]
            if slot.step % every == 0 and bus.wants(pid):
                bus.publish_latent(pid, slot.step, bkt.n_steps,
                                   bkt.x[i * bkt.b])

    def _step_and_retire(self, bkt: _Bucket) -> None:
        self._drop_abandoned(bkt)
        if not bkt.slots:
            return
        mark = trace_mod.GLOBAL_RETRACES.mark()
        first_timers = [s for s in bkt.slots if s.step == 0]
        t0 = time.perf_counter()
        try:
            # the CB executor's share of ``dispatch``: one step's enqueue
            with trace_mod.stage("dispatch", own=True):
                bkt.step_once()
        except Exception as e:  # noqa: BLE001 - poison bucket, not loop
            log(f"cb: step failed in bucket {bkt.sig[:8]}: "
                f"{type(e).__name__}: {e}")
            self._bad_sigs.add(bkt.sig)
            for item in bkt.abort_all():
                self.state._finalize_hand([item], None, e,
                                          time.perf_counter())
            self._buckets.pop(bkt.sig, None)
            self._fail_parked(bkt.sig, e)
            self._mirror_stats()
            return
        t1 = time.perf_counter()
        trace_mod.GLOBAL_STAGES.record("cb_step", t1 - t0)
        # flight deck: admit-to-first-step — the CB admission tail the
        # queue_wait stage can't see (time parked at the boundary
        # waiting for a step, not time in the queue)
        for s in first_timers:
            wait = max(t1 - s.t_admit, 0.0)
            sp = s.item.get("span")
            tid = sp.trace_id if sp is not None else None
            self.admit_to_first_step.record(wait, trace_id=tid)
            trace_mod.GLOBAL_STAGES.record("cb_admit_to_first_step",
                                           wait, trace_id=tid)
        traced = trace_mod.GLOBAL_RETRACES.since(mark).get("traces", 0)
        with self._lock:
            concurrent = self._fallback_busy or self._tailing > 0
        if traced and not concurrent:
            # the retrace counter is process-global; only attribute the
            # delta to this bucket when no other thread (fallback group,
            # decode tail) could have been compiling during the step —
            # a false steady-state alert is worse than a missed warmup
            # count
            bkt.retraces += traced
            trace_mod.GLOBAL_COUNTERS.bump("cb_retraces", traced)
        else:
            traced = 0
        trace_mod.GLOBAL_COUNTERS.bump("cb_steps")
        with self._lock:
            self._stats["steps"] += 1
            self._stats["retraces"] += traced
        if reuse_mod.previews_enabled():
            self._publish_previews(bkt)
        if self._retire_cohorts(bkt):
            self._mirror_stats()
        self._deck_record(bkt)

    def _retire_cohorts(self, bkt: _Bucket) -> bool:
        """Hand every finished slot to the decode tail (shared by the
        step loop and the no-op-resume path — a row resumed at its
        final boundary retires without stepping, because step_once on
        a finished row would index past the sigma schedule)."""
        finished = bkt.take_finished()
        now_wall = time.time()
        for items, rows, t_admit in finished:
            trace_mod.GLOBAL_COUNTERS.bump("cb_retires", len(items))
            with self._lock:
                self._stats["retires"] += len(items)
                self._tailing += len(items)
            for item in items:
                if item.get("span") is not None:
                    trace_mod.event_span(
                        "cb_exit", now_wall, now_wall,
                        parent=item["span"],
                        attrs={"bucket": bkt.sig[:8]})
            self._tail_q.put((bkt, items, rows, t_admit))
        return bool(finished)

    def _abort_active(self, err: BaseException) -> None:
        for bkt in list(self._buckets.values()):
            for item in bkt.abort_all():
                self.state._finalize_hand([item], None, err,
                                          time.perf_counter())
        for rec in self.parked.drain_all():
            self.state._finalize_hand([rec.item], None, err,
                                      time.perf_counter())
        trace_mod.GLOBAL_GAUGES.set("cb_parked", 0)
        self._mirror_stats()

    def _drive(self) -> None:
        trace_mod.thread_role(trace_mod.EXECUTOR)  # the step thread
        st = self.state
        batch_started = None
        while not self._stop:
            try:
                if not st._exec_gate.is_set():
                    st._exec_gate.wait(0.05)
                    continue
                if st.interrupt_event.is_set():
                    active = any(b.n_active
                                 for b in self._buckets.values())
                    if active or self._fallback_busy:
                        # abort active slots; only CONSUME the flag when
                        # the fallback executor is idle — a mid-group
                        # fallback job must still see its interrupt (its
                        # per-step poll / op-boundary checks read the
                        # same event)
                        if not self._fallback_busy:
                            st.interrupt_event.clear()
                        self._abort_active(
                            InterruptedError("execution interrupted"))
                        time.sleep(0.005)
                        continue
                    if st._queue_event.is_set():
                        # stale flag with fresh work queued: consume it
                        # at the dispatch boundary exactly like the
                        # legacy exec loop's group start
                        st.interrupt_event.clear()
                    else:
                        # nothing here to interrupt: the process-global
                        # flag is NOT ours to consume — another
                        # ServerState in this process (or a directly
                        # driven sampler) may be its target, and an
                        # idle driver eating it would make /interrupt
                        # a no-op for them (the leaked-driver bug the
                        # per-step-interrupt tests caught)
                        st._queue_event.wait(timeout=0.05)
                        continue
                admitted = self._admit_boundary()
                resumed = False
                if self.park_enabled:
                    # residency scheduling at the boundary: shed under
                    # memory pressure, then refill free slots from the
                    # parked backlog (admission above already took its
                    # stride-fair share of the room)
                    self._pressure_park()
                    resumed = self._resume_boundary()
                bkt = self._next_bucket()
                if bkt is None:
                    batch_started = None
                    if not admitted and not resumed:
                        if st._queue_event.is_set():
                            # queued work that can't dispatch right now
                            # (non-batchable head behind a busy
                            # fallback, or a full bucket): sleep flat —
                            # the event stays set, so waiting on it
                            # would spin the core against the queue
                            # lock
                            time.sleep(0.005)
                        else:
                            st._queue_event.wait(timeout=0.02)
                    continue
                if batch_started is None:
                    batch_started = time.monotonic()
                    if self.admit_window > 0:
                        # linger at the first boundary so a burst's
                        # later arrivals join step 0's batch
                        deadline = batch_started + self.admit_window
                        while time.monotonic() < deadline \
                                and not self._stop:
                            st._queue_event.wait(timeout=min(
                                0.005, self.admit_window))
                            self._admit_boundary()
                self._step_and_retire(bkt)
            except Exception as e:  # noqa: BLE001 - the loop must survive
                log(f"cb driver error: {type(e).__name__}: {e}")
                time.sleep(0.1)

    # -- tail (decode/save) and fallback threads ------------------------------

    def _tail_loop(self) -> None:
        while True:
            bkt, items, rows, t_admit = self._tail_q.get()
            k = len(items)
            first = items[0]
            res, err = None, None
            try:
                ctx = self._fresh_ctx()
                # cohort decode: ONE pruned tail run over the stacked
                # rows; split_images + the coalesced per-prompt PNG
                # metadata path (ctx.coalesce + coalesced_seeds) give
                # every prompt its own images, seed and history entry
                ctx.coalesce = k
                hidden = {bkt.ks_node: {"cb_latent":
                                        DeviceLatent(rows)}}
                if k > 1:
                    hidden[bkt.ks_node]["coalesced_seeds"] = [
                        int(it["prompt"][bkt.ks_node]["inputs"]
                            .get("seed", 0)) for it in items]
                with trace_mod.use_span(first.get("span")), \
                        trace_mod.span("cb_decode",
                                       bucket=bkt.sig[:8],
                                       coalesced=k):
                    res = WorkflowExecutor(ctx).execute(
                        build_tail_prompt(first["prompt"],
                                          bkt.tail_keep, bkt.ks_node),
                        hidden=hidden,
                        extra_pnginfo=first.get("extra_data", {}).get(
                            "extra_pnginfo"),
                        # provenance: the PNG embeds the FULL prompt
                        # (its own seed), not the pruned decode graph
                        prompt_json=first["prompt"])
            except Exception as e:  # noqa: BLE001 - surfaces in history
                err = e
            with self._lock:
                self._tailing -= k
            self.state._finalize_hand(items, res, err, t_admit)

    def _fallback_loop(self) -> None:
        while True:
            group = self._fallback_q.get()
            try:
                self.state._execute_group(group)
            finally:
                self._fallback_busy = False
