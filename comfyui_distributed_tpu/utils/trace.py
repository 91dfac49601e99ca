"""Tracing / profiling / request-scoped telemetry subsystem.

The reference has NONE (SURVEY.md §5: "Tracing / profiling: ABSENT" — its
only timing is a preflight elapsed-ms debug line, ``gpupanel.js:1502``).
Here profiling is a first-class subsystem:

- phase wall-clock aggregation (:class:`PhaseStats`) fed by
  ``utils.logging.Timer`` and the executor's per-node timings, surfaced on
  ``GET /distributed/metrics`` — now with fixed-bucket latency histograms
  and p50/p95/p99 per phase (:class:`LatencyHistogram`), also rendered as
  Prometheus text by :func:`prometheus_text` for ``/distributed/metrics.prom``;
- **request-scoped distributed tracing** (Dapper-style: low-overhead,
  always-on, propagated via RPC metadata): a :class:`Span` model
  (``trace_id``/``span_id``/``parent_id``) with a contextvar-carried
  current span (async-task- and thread-correct), snapshot/reattach
  (:func:`capture_span_context`) mirroring the transfer context so spans
  survive the HostIOPool handoff, W3C-``traceparent`` helpers for the
  distributed HTTP edges, and a bounded per-job flight recorder
  (:class:`FlightRecorder`) behind ``GET /distributed/trace/<prompt_id>``;
- XLA/device traces via ``jax.profiler`` (viewable in TensorBoard /
  Perfetto), driven by ``POST /distributed/profile/start`` + ``/stop``;
  while one runs, every stage/span is also a ``dtpu/<name>`` annotation
  on the profiler's clock, and ``stop`` ends by reducing the trace to a
  summary (``trace_summary.py``): device seconds per jitted program by
  kernel class (:data:`KERNEL_CLASSES`, read from the module paths the
  operations carry), idle seconds by the host span over them; beside
  the annotations a **host timeline** the profiler cannot cut (every
  timed interval by thread role on ``perf_counter_ns``, the hand-overs
  between threads as ``wake_*``, the collector's pauses as ``gc_pause``),
  written by ``stop`` as ``host_timeline.json`` and laid on the trace's
  clock by two ``dtpu/clock_sync`` markers, so that every idle second
  BETWEEN programs has one owner: the executor's interval at that instant;
- host<->device transfer accounting (:class:`TransferStats`): every device
  edge in the ops layer reports bytes through :func:`record_transfer`,
  attributed to the executing workflow node (:func:`node_scope`) — the
  software-measurable proxy for "tensors never leave HBM";
- attention and GEGLU call sites by the path each took
  (:data:`ATTENTION_PATHS`, :data:`GEGLU_PATHS`) and a language model's
  weight products by lowering (:data:`DENSE_PATHS`), counted while a
  program is traced;
- retrace/compile counters (:class:`RetraceStats`) fed by
  ``jax.monitoring`` events, telling a compile from a cache load, with
  their seconds: a steady-state serving process must report ZERO new
  traces on a repeated workflow (``install_jax_monitoring``).

Telemetry never touches traced code paths: spans and histograms are pure
host-side Python around (never inside) the jitted programs, so tracing-on
vs tracing-off must show zero retrace delta (the no-retrace guard of
``tests/conftest.py``; what a device trace costs while it is on is the
chip benchmark's to measure: ``PERF.md`` §6).
"""

from __future__ import annotations

import contextvars
import gc
import json
import os
import re
import threading
import time
import weakref
from collections import OrderedDict, deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils.logging import log


class LatencyHistogram:
    """Fixed-bucket latency histogram with percentile estimation.

    Prometheus-shaped: per-bucket counts over
    :data:`constants.HISTOGRAM_BUCKETS_S` plus an overflow (+Inf) bucket,
    with sum/count/max — enough for ``_bucket``/``_sum``/``_count`` series
    AND interpolated p50/p95/p99 without storing samples (thread-safe).

    Buckets optionally carry OpenMetrics exemplars: ``record(...,
    trace_id=...)`` remembers the latest (trace_id, value, wall-clock)
    that landed in each bucket, so the ``.prom`` exposition can link a
    slow bucket straight to a flight-recorder / capture-file trace."""

    __slots__ = ("bounds", "counts", "overflow", "count", "sum_s", "max_s",
                 "exemplars", "_lock")

    def __init__(self, bounds: Tuple[float, ...] = C.HISTOGRAM_BUCKETS_S):
        self.bounds = tuple(bounds)
        self.counts = [0] * len(self.bounds)  # guarded-by: self._lock
        self.overflow = 0                     # guarded-by: self._lock
        self.count = 0                        # guarded-by: self._lock
        self.sum_s = 0.0                      # guarded-by: self._lock
        self.max_s = 0.0                      # guarded-by: self._lock
        # bucket index (len(bounds) = overflow) -> (trace_id, value, ts)
        self.exemplars: Dict[int, Tuple[str, float, float]] = {}  # guarded-by: self._lock
        self._lock = threading.Lock()

    def record(self, seconds: float,
               trace_id: Optional[str] = None) -> None:
        s = max(float(seconds), 0.0)
        with self._lock:
            self.count += 1
            self.sum_s += s
            self.max_s = max(self.max_s, s)
            idx = len(self.bounds)
            for i, le in enumerate(self.bounds):
                if s <= le:
                    self.counts[i] += 1
                    idx = i
                    break
            else:
                self.overflow += 1
            if trace_id:
                self.exemplars[idx] = (str(trace_id), s, time.time())

    def cumulative(self) -> List[Tuple[float, int]]:
        """``[(le, cumulative_count), ..., (inf, total)]`` — the
        Prometheus ``_bucket`` series."""
        return self.prom_series()[0]

    def prom_series(self) -> Tuple[List[Tuple[float, int]], float, int]:
        """``(buckets, sum, count)`` read under ONE lock acquisition —
        the Prometheus invariant (+Inf bucket == _count) must hold even
        against a concurrent record() mid-scrape."""
        with self._lock:
            out, cum = [], 0
            for le, n in zip(self.bounds, self.counts):
                cum += n
                out.append((le, cum))
            out.append((float("inf"), cum + self.overflow))
            return out, self.sum_s, self.count

    def exemplars_snapshot(self) -> Dict[int, Tuple[str, float, float]]:
        """Bucket-index -> (trace_id, value, unix_ts) under the lock."""
        with self._lock:
            return dict(self.exemplars)

    # dtpu-lint: holds[self._lock]
    def _percentile(self, q: float) -> float:
        """Caller holds the lock.  Linear interpolation inside the bucket
        holding the target rank; the overflow bucket interpolates toward
        the observed max."""
        if self.count == 0:
            return 0.0
        target = max(min(q, 1.0), 0.0) * self.count
        cum, lo = 0, 0.0
        for le, n in zip(self.bounds, self.counts):
            if n and cum + n >= target:
                frac = (target - cum) / n
                hi = min(le, self.max_s) if self.max_s > 0 else le
                return min(lo + (max(hi, lo) - lo) * frac, self.max_s)
            cum += n
            lo = le
        if self.overflow:
            frac = (target - cum) / self.overflow
            hi = max(self.max_s, lo)
            return lo + (hi - lo) * frac
        return self.max_s

    def percentile(self, q: float) -> float:
        """Estimated q-quantile (q in [0, 1])."""
        with self._lock:
            return self._percentile(q)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            count, sum_s, max_s = self.count, self.sum_s, self.max_s
            return {"count": count, "total_s": sum_s, "max_s": max_s,
                    "mean_s": sum_s / count if count else 0.0,
                    "p50_s": self._percentile(0.50),
                    "p95_s": self._percentile(0.95),
                    "p99_s": self._percentile(0.99)}


class PhaseStats:
    """Aggregated per-phase wall-clock (thread-safe).

    Historically count/total/max only; each phase now carries a
    :class:`LatencyHistogram`, so ``snapshot()`` additionally reports
    mean and p50/p95/p99 and :meth:`histograms` feeds the Prometheus
    ``_bucket`` series.  The legacy keys (``count``/``total_s``/``max_s``)
    are preserved — existing readers (bench, tests) keep working."""

    def __init__(self) -> None:
        self._stats: Dict[str, LatencyHistogram] = {}  # guarded-by: self._lock
        self._lock = threading.Lock()

    def _hist(self, phase: str) -> LatencyHistogram:
        with self._lock:
            h = self._stats.get(phase)
            if h is None:
                h = self._stats[phase] = LatencyHistogram()
            return h

    def record(self, phase: str, seconds: float,
               trace_id: Optional[str] = None) -> None:
        self._hist(phase).record(seconds, trace_id=trace_id)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            items = list(self._stats.items())
        return {k: h.snapshot() for k, h in items}

    def histograms(self) -> Dict[str, LatencyHistogram]:
        with self._lock:
            return dict(self._stats)

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()


# process-wide sink the Timer class reports into
GLOBAL_PHASES = PhaseStats()


# --- names on the profiler's clock -------------------------------------------
#
# Two kinds of name reach a device trace.  The operations carry theirs
# already: the models are Flax modules, Flax wraps every module call in
# ``jax.named_scope(<module name>)``, and a TPU trace keeps the resulting
# path of every operation (the HLO ``op_name``, e.g.
# ``jit(core)/while/body/closed_call/UNet/down_2_attn_0/blocks_0/attn1/
# to_q/dot_general``) in the statistic ``tf_op`` of the operation's event
# metadata.  KERNEL_CLASSES reads those paths; nothing in ``models/`` is
# written for it, so renaming a module there is what changes a class
# (tests/test_trace_names.py breaks first).  On the host, stage()/span()/
# event_span() open a ``dtpu/<name>`` TraceAnnotation while a device
# trace runs, so host spans and device operations share a clock.

HOST_PREFIX = "dtpu/"
OTHER = "other"

# class -> (model the path must lie in or None for any, pattern over one
# ``/``-separated segment of the path).  Ordered: the INNERMOST segment
# that matches any row of its model decides, and within a segment the
# first row.  So ``.../attn1/to_q/dot_general`` is attn_proj, the einsum
# written in ``attn1`` itself (``.../attn1/bnhd,bmhd->bhnm/dot_general``)
# is attn_self, and a GroupNorm inside a ResBlock is norm.
_UNET, _VAE, _CLIP, _LM = "UNet", "VAE", "CLIPTextModel", "LoopLM"
_MOE, _SWA, _SSM = "PanguUltraMoE", "ExaoneMoe", "GraniteMoeHybrid"
_DSA, _SBY, _SCM = "KeyeVL2", "Phi4Flash", "LongcatFlash"
_BLOCK = r"(?:down_\d+|up_\d+|mid)"
KERNEL_CLASSES = (
    # the Mamba mixer's gated RMSNorm is published as ``mamba/norm``: ahead
    # of the row below, which would take the name for the image models'
    ("lm_ssm", _SSM, r"norm"),
    ("norm", None, r"GroupNorm_\d+|LayerNorm_\d+|(?:in_|out_)?norm\d*"
                   r"|ln\d+|ln_final"),
    ("attn_proj", _UNET, r"to_q|to_k|to_v|to_out|proj_in|proj_out"),
    ("attn_self", _UNET, r"attn1"),
    ("attn_cross", _UNET, r"attn2"),
    ("ff", _UNET, r"ff|geglu"),
    ("resblock", _UNET, _BLOCK + r"_res_\d+"),
    ("resample", _UNET, r"down_\d+_ds|up_\d+_us|conv_in|conv_out"),
    ("embed", _UNET, r"time_fc\d+|label_fc\d+"),
    # a transformer block's and the SpatialTransformer's own operations
    # (residual adds, token reshapes): with the projections
    ("attn_proj", _UNET, r"blocks_\d+|" + _BLOCK + r"_attn(?:_\d+)?"),
    # UNet.__call__'s own: skip concatenations, the final activation,
    # the timestep sinusoid
    ("resample", _UNET, r"UNet"),
    ("vae_attn", _VAE, r"mid_attn"),
    ("vae_res", _VAE, _BLOCK + r"_res_\d+"),
    # the VAE's convolutions, resamplers and its own glue
    ("vae_conv", _VAE, r"conv_in|conv_out|(?:post_)?quant_conv"
                       r"|down_\d+_ds|up_\d+_us|encoder|decoder|VAE"),
    ("clip_mlp", _CLIP, r"fc\d+"),
    ("embed", _CLIP, r"token_embedding|position_embedding|text_projection"
                     r"|CLIPTextModel"),
    ("clip_attn", _CLIP, r"layers_\d+"),
    # the looped language model (models/looplm.py), whose scopes carry the
    # published modules' names.  Its RMSNorms are ``lm_norm``, not
    # ``norm``: none of their names matches the first row
    ("lm_norm", _LM, r"(?:input|post_attention)_layernorm(?:_2)?"
                     r"|final_norm"),
    ("lm_proj", _LM, r"[qkvo]_proj"),
    ("lm_cache", _LM, r"kv_cache"),
    # QK^T, the mask, the softmax, PV; the rotary embedding
    ("lm_attn", _LM, r"self_attn|rotary"),
    ("lm_mlp", _LM, r"mlp|gate_proj|up_proj|down_proj"),
    ("lm_head", _LM, r"lm_head|early_exit_gate|sample"),
    ("embed", _LM, r"embed_tokens"),
    # the layer scan's and the program's own glue (residual adds, the
    # slice that picks the prompt's last row); the two phase scopes
    # (PHASES) are glue in every family: `phase_of` reads them
    ("lm_proj", _LM, r"layers|prefill|decode|LoopLM"),
    # the latent-attention decoder with routed experts (models/mla_moe.py),
    # under the same classes, and one more: ``lm_experts`` is everything
    # routing adds (the router, the dispatch, the routed experts held
    # here, the combine); the dense blocks' MLP and the shared expert are
    # ``lm_mlp``.  Inside ``experts`` no scope carries a projection's
    # name: the innermost segment decides
    ("lm_norm", _MOE, r"(?:input|post_attention|pre_mlp|post_mlp)_layernorm"
                      r"|(?:q_a|kv_a)_layernorm|final_norm"),
    # the two low-rank pairs, the output projection, and absorption
    ("lm_proj", _MOE, r"q_[ab]_proj|kv_a_proj_with_mqa|kv_b_proj|o_proj"
                      r"|absorb_[qv]"),
    ("lm_cache", _MOE, r"kv_cache"),                # the LATENT cache
    ("lm_attn", _MOE, r"self_attn|rotary"),
    ("lm_experts", _MOE, r"gate|dispatch|experts|combine"),
    ("lm_mlp", _MOE, r"mlp|shared_experts|gate_proj|up_proj|down_proj"),
    ("lm_head", _MOE, r"lm_head|sample"),
    ("embed", _MOE, r"embed_tokens"),
    ("lm_proj", _MOE, r"dense_layers|moe_layers|prefill|decode"
                      r"|PanguUltraMoE"),
    # the decoder with window and full attention layers and routed experts
    # (models/swa_moe.py), under the same classes; its expert layer IS the
    # one above, so the same four names are ``lm_experts``
    ("lm_norm", _SWA, r"post_(?:attention|feedforward)_layernorm"
                      r"|[qk]_norm|final_norm"),
    ("lm_proj", _SWA, r"[qkvo]_proj"),
    ("lm_cache", _SWA, r"kv_cache"),        # the ring and the full cache
    ("lm_attn", _SWA, r"self_attn|rotary"),
    ("lm_experts", _SWA, r"gate|dispatch|experts|combine"),
    ("lm_mlp", _SWA, r"mlp|shared_experts|gate_proj|up_proj|down_proj"),
    ("lm_head", _SWA, r"lm_head|sample"),
    ("embed", _SWA, r"embed_tokens"),
    ("lm_proj", _SWA, r"dense_layers|moe_layers|prefill|decode|ExaoneMoe"),
    # the decoder of state-space (Mamba-2) and attention layers
    # (models/ssm_hybrid.py), under the same classes, and two more.
    # ``lm_ssm`` is everything in a Mamba mixer that is no product with a
    # weight: the convolution, the discretisation, the chunked scan or the
    # state's step, the ``D`` skip, the gated norm (the first row of
    # all).  ``lm_state`` is the recurrent state's and the convolution
    # tail's read and write, as ``lm_cache`` is the positional cache's.
    # The mixer's two projections are ``lm_proj``, as q / k / v / o are
    ("lm_norm", _SSM, r"(?:input|post_attention)_layernorm|final_norm"),
    ("lm_proj", _SSM, r"[qkvo]_proj|in_proj|out_proj"),
    ("lm_cache", _SSM, r"kv_cache"),
    ("lm_state", _SSM, r"ssm_state|conv_state"),
    ("lm_ssm", _SSM, r"mamba|conv1d|ssm"),
    ("lm_attn", _SSM, r"self_attn"),
    ("lm_mlp", _SSM, r"shared_mlp|input_linear|output_linear"),
    ("lm_head", _SSM, r"lm_head|sample"),
    ("embed", _SSM, r"embed_tokens"),
    ("lm_proj", _SSM, r"mamba_layers|attention_layers|prefill|decode"
                      r"|GraniteMoeHybrid"),
    # the decoder with a learned key selection and routed experts
    # (models/dsa_moe.py), under the same classes, and one more:
    # ``lm_index`` is everything the selection adds to an attention (the
    # indexer's three projections, its key's norm and rotation, the index
    # scores, the search for the ``topk`` best, the gather of the keys
    # chosen, the record of the choice); the index keys' WRITE lies in
    # ``kv_cache`` with the keys' and values'.  Its expert layer IS
    # ``mla_moe``'s, so the same four names are ``lm_experts``
    ("lm_norm", _DSA, r"(?:input|post_attention)_layernorm|[qk]_norm"
                      r"|final_norm"),
    ("lm_index", _DSA, r"indexer|wq|wk|k_layernorm|index_rotary"
                       r"|weights_proj|index_scores|topk|gather"
                       r"|selection_record"),
    ("lm_proj", _DSA, r"[qkvo]_proj"),
    ("lm_cache", _DSA, r"kv_cache"),    # keys, values AND index keys
    ("lm_attn", _DSA, r"self_attn|rotary"),
    ("lm_experts", _DSA, r"gate|dispatch|experts|combine"),
    ("lm_mlp", _DSA, r"mlp|gate_proj|up_proj|down_proj"),
    ("lm_head", _DSA, r"lm_head|sample"),
    ("embed", _DSA, r"embed_tokens"),
    ("lm_proj", _DSA, r"layers|prefill|decode|KeyeVL2"),
    # the decoder-hybrid-decoder (models/sambay.py), under the same
    # classes (``lm_ssm`` the convolution and the selective scan with its
    # ``D`` skip and gate, ``lm_state`` the states' and tails' read and
    # write, ``lm_attn`` a window or full layer's two maps a head pair),
    # and two more.  ``lm_gmu`` is a ``gmu`` layer's gate with the memory
    # (its two products are ``lm_proj``'s); ``lm_cross`` a ``cross``
    # layer's attention over the cache another layer wrote, its read of
    # that cache with it
    ("lm_norm", _SBY, r"(?:input|post_attention|final)_layernorm|subln"),
    ("lm_proj", _SBY, r"in_proj|x_proj|dt_proj|out_proj|Wqkv"),
    ("lm_cache", _SBY, r"kv_cache"),    # the rings and the one cache
    ("lm_state", _SBY, r"ssm_state|conv_state"),
    ("lm_ssm", _SBY, r"conv1d|selective_scan"),
    ("lm_gmu", _SBY, r"gate"),
    ("lm_cross", _SBY, r"inner_cross_attn"),
    ("lm_attn", _SBY, r"inner_attn"),
    ("lm_mlp", _SBY, r"mlp|fc1|fc2"),
    ("lm_head", _SBY, r"lm_head|sample"),
    ("embed", _SBY, r"embed_tokens"),
    # a mixer's and a layer's own glue (splits, residual adds), the six
    # kinds of layer
    ("lm_proj", _SBY, r"attn|layers|mamba|swa|memory|full|gmu|cross"
                      r"|prefill|decode|Phi4Flash"),
    # the latent-attention decoder whose layer is two attentions and two
    # dense MLPs around a shortcut-connected expert layer
    # (models/mla_scmoe.py), under ``mla_moe``'s classes (its attention IS
    # that family's; ``lm_mlp`` the two dense MLPs, ``mlps_0`` and
    # ``mlps_1``; ``lm_experts`` the router, the dispatch, the experts held
    # and the expert layer's own glue under ``mlp``), and one more:
    # ``lm_zero`` is the identity experts' scaled add of the layer's input
    # (which choices are zero experts, the sum of their weights, the add)
    ("lm_norm", _SCM, r"(?:input|post_attention)_layernorm_[01]"
                      r"|(?:q_a|kv_a)_layernorm|final_norm"),
    ("lm_proj", _SCM, r"q_[ab]_proj|kv_a_proj_with_mqa|kv_b_proj|o_proj"
                      r"|absorb_[qv]"),
    ("lm_cache", _SCM, r"kv_cache"),        # two latent slots a layer
    ("lm_attn", _SCM, r"self_attn_[01]|rotary"),
    ("lm_zero", _SCM, r"zero_experts"),
    ("lm_experts", _SCM, r"mlp|router|dispatch|experts"),
    ("lm_mlp", _SCM, r"mlps_[01]|gate_proj|up_proj|down_proj"),
    ("lm_head", _SCM, r"lm_head|sample"),
    ("embed", _SCM, r"embed_tokens"),
    ("lm_proj", _SCM, r"layers|prefill|decode|LongcatFlash"),
)
# the outer scopes a program may put directly under its model's: where it
# does, a trace summary gives its seconds by PHASE beside its seconds by
# class (the seven language models' ``generate`` do; the denoise, VAE and
# text programs do not and have no phases).  A program that the
# persistent compile cache LOADS carries the names of the tree that
# compiled it (JAX keys a program without a Pallas kernel with its debug
# info stripped): a scope named later shows once this tree has compiled
PHASES = ("prefill", "decode")
# the denoise programs' own operations under no module (CFG combine,
# solver update, noise): ``core`` / ``step`` are the functions
# models/registry.py jits
SAMPLER = "sampler"
_SAMPLER_PROGRAM = re.compile(r"(?:^|/)jit\((?:core|step)\)(?:/|$)")
_MODEL_OF = re.compile(
    r"^(UNet|VAE|CLIPTextModel|LoopLM|PanguUltraMoE|ExaoneMoe"
    r"|GraniteMoeHybrid|KeyeVL2|Phi4Flash|LongcatFlash)(?:\.\w+)?$")
_ROWS = tuple((cls, model, re.compile(f"(?:{pat})$"))
              for cls, model, pat in KERNEL_CLASSES)


def classify(op_name: str) -> str:
    """The class of KERNEL_CLASSES an operation's path (HLO ``op_name``)
    falls in; ``sampler`` for an operation of a denoise program under no
    model; ``other`` for the rest (no path, or a program that is none of
    ours).  Pure string work: needs no JAX."""
    segments = op_name.split("/")
    model = None
    for i, seg in enumerate(segments):
        m = _MODEL_OF.match(seg)
        if m:
            model, segments = m.group(1), segments[i:]
            segments[0] = model
            break
    if model is None:
        return SAMPLER if _SAMPLER_PROGRAM.search(op_name) else OTHER
    for seg in reversed(segments[:-1] if len(segments) > 1 else segments):
        for cls, of, pat in _ROWS:
            if (of is None or of == model) and pat.match(seg):
                return cls
    return OTHER


def phase_of(op_name: str) -> Optional[str]:
    """The phase of PHASES an operation's path lies in: the scope right
    under its model's; None where there is none."""
    segments = op_name.split("/")
    for i, seg in enumerate(segments[:-1]):
        if _MODEL_OF.match(seg):
            return segments[i + 1] if segments[i + 1] in PHASES else None
    return None


def _annotate(name: str, **args: Any):
    """An entered ``TraceAnnotation("dtpu/<name>")`` carrying the
    request's ids while a device trace runs; None (one module-level
    read) otherwise.  The caller exits it on the thread that opened it."""
    if _trace_dir is None:
        return None
    import jax
    ids = current_trace_ids()
    if ids:
        args = {"trace_id": ids["trace_id"],
                "prompt_id": ids.get("prompt_id", ""), **args}
    ann = jax.profiler.TraceAnnotation(HOST_PREFIX + name, **args)
    ann.__enter__()
    return ann


def _end_annotation(ann) -> None:
    if ann is not None:
        ann.__exit__(None, None, None)


# --- the host timeline ---------------------------------------------------------
#
# What the device's idle time BETWEEN programs is held against
# (``trace_summary.py``: ``idle_by_executor``).  Every interval the program
# times (stage / span / device_wait, the caller-measured record_stage /
# event_span, the hand-overs of :func:`woke`, the collector's pauses) is
# also an entry of its thread's LANE: ``(name, start_ns, depth)`` on
# ``time.perf_counter_ns`` while it is open, a row of the ring once it has
# closed.  With the profiler off that is an append and a pop; the ring is
# armed by :func:`start_device_trace` alone.  The lanes' open entries are
# reachable from there and from :func:`stop_device_trace`, so an interval
# that began before the slice, or is still open at its end, is written
# clipped to it where the profiler drops the annotation whole.

TIMELINE_RING = 1 << 16         # closed intervals kept a slice, oldest out
TIMELINE_FILE = "host_timeline.json"
CLOCK_SYNC = "clock_sync"       # the annotation that carries perf_counter_ns
# what a thread says it is (:func:`thread_role`); a thread that never said
# is ``other``.  The executor is the thread whose next enqueue the device
# waits for
EXECUTOR, FINALIZER, HOST_POOL, HTTP = (
    "executor", "finalizer", "host_pool", "http")
NO_ROLE = "other"
GC_PAUSE = "gc_pause"
WAKE_PREFIX = "wake_"
MEASURED = -1                   # the depth of an interval its caller measured:
                                # not of the thread's stack, owns no idle time

_now_ns = time.perf_counter_ns


def now_ns() -> int:
    """The timeline's clock: what a notifier stamps for :func:`woke`."""
    return _now_ns()


class _Lane:
    """One thread's intervals: its role, and the ones open now."""

    __slots__ = ("role", "thread", "open", "__weakref__")

    def __init__(self, thread: str):
        self.role, self.thread = NO_ROLE, thread
        self.open: List[tuple] = []     # (name, start_ns, depth), outermost first


_lane_state = threading.local()
# a lane lives as long as its thread (the thread's locals hold it)
_lanes: "weakref.WeakSet[_Lane]" = weakref.WeakSet()  # guarded-by: _lanes_lock
_lanes_lock = threading.Lock()
_ring: "deque[tuple]" = deque(maxlen=TIMELINE_RING)   # (lane, entry, end_ns)
_ring_armed = False
# rows since the ring was armed (what it lost: this less its length).
# Re-entrant: the collector's callback keeps its pause from whatever thread
# it interrupts, `_keep` itself among them
_ring_added = 0                 # guarded-by: _ring_lock
_ring_lock = threading.RLock()


def _lane() -> _Lane:
    lane = getattr(_lane_state, "lane", None)
    if lane is None:
        lane = _lane_state.lane = _Lane(threading.current_thread().name)
        with _lanes_lock:
            _lanes.add(lane)
    return lane


def thread_role(role: str) -> None:
    """Said once by a thread that is the executor, the finaliser, of the
    host pool or the HTTP loop."""
    _lane().role = role


def _open(name: str, start_ns: int):
    lane = _lane()
    entry = (name, start_ns, len(lane.open))
    lane.open.append(entry)
    return lane, entry


def _close(lane: _Lane, entry: tuple, end_ns: int) -> None:
    opened = lane.open
    if opened and opened[-1] is entry:
        opened.pop()
    elif entry in opened:       # two handlers of one event loop interleave
        opened.remove(entry)
    if _ring_armed:
        _keep(lane, entry, end_ns)


def _keep(lane: Optional[_Lane], entry: tuple, end_ns: int) -> None:
    global _ring_added
    with _ring_lock:
        _ring.append((lane, entry, end_ns))
        _ring_added += 1


def _keep_wall(name: str, start_s: float, end_s: float) -> None:
    """An interval with wall-clock bounds, onto the timeline's clock."""
    if _ring_armed:
        now_ns, now_s = _now_ns(), time.time()
        _keep(_lane(), (name, now_ns - int((now_s - start_s) * 1e9), MEASURED),
              now_ns - int((now_s - end_s) * 1e9))


def woke(what: str, stamp_ns: int, since_ns: int = 0) -> None:
    """The waiter's half of a hand-over between threads, called when its
    wait has returned: ``wake_<what>`` from the notifier's stamp
    (``perf_counter_ns``, read under the lock the notifier already holds)
    to now, a stage and an interval of THIS thread.  A stamp from before
    ``since_ns`` (when the waiter began to wait) woke nobody: what it
    announced was there already."""
    if stamp_ns > since_ns:
        name, end_ns = WAKE_PREFIX + what, _now_ns()
        GLOBAL_STAGES.record(name, (end_ns - stamp_ns) / 1e9)
        if _ring_armed:
            lane = _lane()
            _keep(lane, (name, stamp_ns, len(lane.open)), end_ns)


# The collector stops every thread.  Its callback runs on whichever thread
# allocated last, possibly inside one of this module's locks (a histogram's
# snapshot builds a dict under its own), so it takes none: a pause goes to
# a deque, and `_fold_gc` makes stages and counters of them from a thread
# that holds nothing (the end of a stage; every read of the aggregates).
_gc_pauses: "deque[tuple]" = deque(maxlen=4096)     # (start_ns, end_ns, gen)
_gc_start_ns = 0
_gc_installed = False


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    global _gc_start_ns
    if phase == "start":
        _gc_start_ns = _now_ns()
        return
    end_ns = _now_ns()
    _gc_pauses.append((_gc_start_ns, end_ns, info.get("generation", 0)))
    if _ring_armed:
        lane = getattr(_lane_state, "lane", None)
        _keep(lane, (GC_PAUSE, _gc_start_ns,
                     len(lane.open) if lane is not None else 0), end_ns)


def install_gc_monitoring() -> None:
    """Time every collection (``gc.callbacks``; idempotent): stage
    ``gc_pause``, counters ``gc.collections`` and ``gc.collections_gen2``,
    and an interval of the thread it ran on."""
    global _gc_installed
    if not _gc_installed:
        _gc_installed = True
        gc.callbacks.append(_on_gc)


def _fold_gc() -> None:
    while _gc_pauses:
        try:
            start_ns, end_ns, generation = _gc_pauses.popleft()
        except IndexError:      # another thread folded it
            return
        GLOBAL_STAGES.record(GC_PAUSE, (end_ns - start_ns) / 1e9)
        GLOBAL_COUNTERS.bump("gc.collections")
        if generation == 2:
            GLOBAL_COUNTERS.bump("gc.collections_gen2")


def _clock_sync() -> int:
    """A ``dtpu/clock_sync`` annotation that carries ``perf_counter_ns``
    as a statistic: where the timeline's clock stands on the profiler's.
    The first annotation after a pause pays the wrapper's cold path (5-25
    us seen), so one without a name of ours goes in front; and a thread
    taken off its core between the clock's read and the annotation would
    put the two clocks that far apart, so a marker that took long is
    made again (the reader takes the one whose value the timeline
    names)."""
    import jax
    for _ in range(3):
        with jax.profiler.TraceAnnotation(CLOCK_SYNC + "_warm"):
            pass
        ns = _now_ns()
        with jax.profiler.TraceAnnotation(HOST_PREFIX + CLOCK_SYNC,
                                          perf_counter_ns=ns):
            pass
        if _now_ns() - ns < 50_000:
            break
    return ns


def _timeline_slice(start_ns: int, stop_ns: int) -> Dict[str, Any]:
    """The slice ``[start_ns, stop_ns]`` of the timeline: the ring's rows
    and what is open on any lane now, clipped to it, times from its
    start."""
    with _lanes_lock:
        lanes = list(_lanes)
    still_open = [(lane, entry) for lane in lanes for entry in list(lane.open)]
    rows = list(_ring)
    closed = {id(entry) for _, entry, _ in rows}
    rows += [(lane, entry, stop_ns) for lane, entry in still_open
             if id(entry) not in closed]
    lane_no: Dict[int, int] = {}
    lane_rows, names, intervals = [], {}, []
    for lane, (name, t0, depth), t1 in rows:
        t0, t1 = max(t0, start_ns), min(t1, stop_ns)
        if t1 <= t0:
            continue
        key = id(lane)
        if key not in lane_no:
            lane_no[key] = len(lane_rows)
            lane_rows.append({"role": lane.role if lane else NO_ROLE,
                              "thread": lane.thread if lane else ""})
        intervals.append([lane_no[key], names.setdefault(name, len(names)),
                          t0 - start_ns, t1 - start_ns, depth])
    intervals.sort(key=lambda r: (r[2], r[4]))
    return {"clock": "perf_counter_ns", "start_ns": start_ns,
            "stop_ns": stop_ns, "ring": TIMELINE_RING,
            "dropped": max(_ring_added - len(_ring), 0),
            "lanes": lane_rows, "names": list(names),
            "columns": ["lane", "name", "start_ns", "end_ns", "depth"],
            "intervals": intervals}


# --- pipeline stage timeline -------------------------------------------------

# Per-job stage wall-clock for the overlapped serving pipeline
# (queue_wait / coalesced_batch / compute / d2h / encode / upload).
# Separate from GLOBAL_PHASES so /distributed/metrics can expose the
# pipeline timeline as its own coherent block: stage totals here overlap
# in wall-clock (that is the point): what the device's idle time lay
# under is read from a device trace (``trace_summary.py``), not from them.
GLOBAL_STAGES = PhaseStats()


# Per-node-type op wall-clock (the executor records every node execution
# here by class_type): the latency histogram behind the
# dtpu_node_seconds Prometheus family and the "nodes" metrics block.
GLOBAL_NODES = PhaseStats()


_wait_state = threading.local()


def _waited_s() -> float:
    """Seconds this thread has spent inside :func:`device_wait`."""
    return getattr(_wait_state, "s", 0.0)


@contextmanager
def stage(name: str, own: bool = False):
    """Time one pipeline stage into :data:`GLOBAL_STAGES`.

    When a request trace is active (``current_span()``), the stage is ALSO
    recorded as a child span of the same name, so the flight recorder's
    per-job tree shows exactly where the wall-clock went — the aggregate
    histogram and the per-job trace are fed by one instrumentation
    point.  While a device trace runs it is also a ``dtpu/<name>``
    annotation on the profiler's clock.

    ``own=True`` records the host's own seconds of a stage that holds
    other spans: what this thread spent in :func:`device_wait` inside the
    block is taken out of the aggregate and recorded apart as
    ``<name>_wait``.  Its span keeps the whole interval, carries the
    difference as ``device_wait_s``, and is added beside the spans opened
    inside the block (they stay children of the current span) when the
    block ends.

    It is an entry of this thread's lane of the host timeline too (and so
    is a :func:`span`), from the same two clock reads."""
    t0_ns = _now_ns()
    wall0 = time.time()
    w0 = _waited_s()
    sp = None if own else _begin_span(name)
    ann = _annotate(name)
    lane, entry = _open(name, t0_ns)
    try:
        yield
    except BaseException:
        if sp is not None:
            sp.set_status("error")
        raise
    finally:
        _end_annotation(ann)
        t1_ns = _now_ns()
        _close(lane, entry, t1_ns)
        dur = (t1_ns - t0_ns) / 1e9
        if _gc_pauses:
            _fold_gc()
        if own:
            # ``device_wait`` sums every thread's waits; this is the
            # share that lay inside this stage
            waited = _waited_s() - w0
            dur -= waited
            GLOBAL_STAGES.record(name + "_wait", waited)
            _add_event_span(name, wall0, time.time(),
                            parent=_SPAN_VAR.get(),
                            attrs={"device_wait_s": round(waited, 6)})
        GLOBAL_STAGES.record(name, dur)
        _end_span(sp)


@contextmanager
def device_wait():
    """Around every place a host thread blocks for the device: the
    ``device_wait`` stage, and the seconds an enclosing ``stage(...,
    own=True)`` on this thread leaves out."""
    t0 = time.perf_counter()
    try:
        with stage("device_wait"):
            yield
    finally:
        _wait_state.s = _waited_s() + time.perf_counter() - t0


def mark_instant(name: str, sp: Optional["Span"] = None,
                 at: Optional[float] = None) -> None:
    """Stamp a lifecycle instant (wall clock) on the request's root span
    — ``sp`` or the root of the current span; the first stamp of a name
    stands.  No-op outside a request."""
    node = sp if sp is not None else _SPAN_VAR.get()
    if node is None:
        return
    while node.parent is not None:
        node = node.parent
    node.attrs.setdefault("instants", {}).setdefault(
        name, round(time.time() if at is None else at, 6))


def record_stage(name: str, start_s: float, end_s: float,
                 parent: Optional["Span"] = None) -> None:
    """An interval measured by its caller (wall-clock ``time.time()``
    bounds): into :data:`GLOBAL_STAGES` and, under ``parent``, as a child
    span of the request — what :func:`stage` does for a ``with`` block."""
    GLOBAL_STAGES.record(name, end_s - start_s)
    if parent is not None:
        event_span(name, start_s, end_s, parent=parent)
    else:
        _keep_wall(name, start_s, end_s)


class CounterStats:
    """Named monotonic counters (thread-safe) — scheduler/wire events."""

    def __init__(self) -> None:
        self._counts: Dict[str, int] = {}  # guarded-by: self._lock
        self._lock = threading.Lock()

    def bump(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + int(n)

    def get(self, name: str) -> int:
        with self._lock:
            return int(self._counts.get(name, 0))

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()


# coalesced_batches / coalesced_prompts / exec_runs / wire_tensor_msgs /
# wire_png_msgs / wire_bytes ... — the scheduler and wire layers bump,
# /distributed/metrics reads (``pipeline.counters``)
GLOBAL_COUNTERS = CounterStats()

# Attention call sites by the path each took (``fused``, ``xla_whole``,
# ``xla_chunked``, ``ring``; ``xla_causal`` and ``xla_decode`` for a
# language model's masked calls): bumped by models/layers.py once per site
# while a program is TRACED, never around a jitted call, so a served
# request adds nothing.  A process's whole life: metrics/reset leaves it,
# like ``retraces``.
ATTENTION_PATHS = CounterStats()

# The UNet's GEGLU call sites by the path each took (``fused``: the Pallas
# kernel, ``xla``: the module as written), counted the same way
# (models/layers.py:geglu_path).
GEGLU_PATHS = CounterStats()

# A language model's products with a resident weight by the lowering each
# took and the rows that met it (``fewrow_few``: the weight-streaming
# Pallas kernel; ``xla_one`` / ``xla_few`` / ``xla_many``: ``jnp.dot``;
# ``*_tied_*``: a tied embedding read as the head), counted the same way
# (models/looplm.py:dense_path).
DENSE_PATHS = CounterStats()


class GaugeStats:
    """Named level gauges (thread-safe) — current-state values the
    counters can't express (a monotonic bump has no "now there are N"):
    parked continuous-batching rows, residency occupancy, ...  Setters
    publish, the metrics surfaces read."""

    def __init__(self) -> None:
        self._values: Dict[str, float] = {}  # guarded-by: self._lock
        self._lock = threading.Lock()

    def set(self, name: str, value: float) -> None:
        with self._lock:
            self._values[name] = float(value)

    def get(self, name: str, default: float = 0.0) -> float:
        with self._lock:
            return float(self._values.get(name, default))

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._values)

    def reset(self) -> None:
        with self._lock:
            self._values.clear()


# cb_parked (latent paging, ISSUE 17) ... — level views next to the
# monotonic counters on the same metrics surfaces
GLOBAL_GAUGES = GaugeStats()


def pipeline_snapshot() -> Dict[str, Any]:
    """The serving-pipeline block of /distributed/metrics."""
    _fold_gc()
    return {"stages": GLOBAL_STAGES.snapshot(),
            "counters": GLOBAL_COUNTERS.snapshot(),
            "gauges": GLOBAL_GAUGES.snapshot()}


# --- device/XLA tracing ------------------------------------------------------

_trace_lock = threading.Lock()
_trace_dir: Optional[str] = None
_trace_t0 = 0.0
_trace_sync_ns = 0      # perf_counter_ns of the slice's first clock marker
# the program's own reduction of its last device trace (trace_summary.py);
# kept until the next one, on /distributed/metrics as "profile"
_profile: Optional[Dict[str, Any]] = None
SUMMARY_TIMEOUT_S = 900.0


def start_device_trace(out_dir: Optional[str] = None) -> str:
    """Begin a ``jax.profiler`` trace (TensorBoard/Perfetto format), arm
    the host timeline's ring and mark where its clock stands on the
    profiler's."""
    global _trace_dir, _trace_t0, _trace_sync_ns, _ring_armed, _ring_added
    import jax
    with _trace_lock:
        if _trace_dir is not None:
            raise RuntimeError(f"trace already running -> {_trace_dir}")
        out_dir = out_dir or os.path.join(
            os.getcwd(), "traces", time.strftime("%Y%m%d-%H%M%S"))
        os.makedirs(out_dir, exist_ok=True)
        jax.profiler.start_trace(out_dir)
        _ring.clear()
        _ring_added, _ring_armed = 0, True
        _trace_dir, _trace_t0 = out_dir, time.time()
        _trace_sync_ns = _clock_sync()
        log(f"device trace started -> {out_dir}")
        return out_dir


def stop_device_trace() -> str:
    """Mark the clocks again, stop the trace, write the timeline's part
    of the slice beside the ``.xplane.pb`` (``host_timeline.json``), then
    reduce both (:func:`_summarize`): the summary is kept for
    :func:`profile_summary` and written beside the trace as
    ``summary.json``."""
    global _trace_dir, _profile, _ring_armed
    import jax
    with _trace_lock:
        if _trace_dir is None:
            raise RuntimeError("no trace running")
        out = _trace_dir
        t_stop = time.time()
        try:
            timeline = _timeline_slice(_trace_sync_ns, _clock_sync())
            # the profiler takes many seconds to write a slice out: what
            # the threads time meanwhile is not the slice's
            _ring_armed = False
            jax.profiler.stop_trace()
        finally:
            # a raising stop_trace must still clear the state: leaving
            # _trace_dir set would wedge every later start_device_trace
            # with "trace already running" for the life of the process
            _trace_dir, _ring_armed = None, False
            _ring.clear()
        written_s = time.time() - t_stop
        log(f"device trace stopped -> {out} (written in {written_s:.1f}s)")
    found = _find_xplane(out)
    if found is not None:   # a profiler that wrote nothing leaves no file
        with open(os.path.join(os.path.dirname(found), TIMELINE_FILE), "w",
                  encoding="utf-8") as f:
            json.dump(timeline, f, separators=(",", ":"))
    summary = _summarize(out, t_stop - _trace_t0)
    if summary is not None:
        summary["stop_trace_s"] = round(written_s, 3)
        with _trace_lock:
            _profile = summary
    return out


def _find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    found = [os.path.join(base, f) for base, _, files in os.walk(trace_dir)
             for f in files if f.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime) if found else None


def _summarize(trace_dir: str, traced_s: float) -> Optional[Dict[str, Any]]:
    """Reduce the trace under ``trace_dir`` in a child process
    (``JAX_PLATFORMS=cpu``: reading the protobuf imports JAX, and this
    process's GIL and chip are busy serving).  None where the profiler
    left no ``.xplane.pb`` or the child failed (logged, never raised:
    the trace itself is on disk either way)."""
    import subprocess
    import sys
    path = _find_xplane(trace_dir)
    if path is None:
        return None
    out_path = os.path.join(trace_dir, "summary.json")
    t0 = time.time()
    try:
        subprocess.run(
            [sys.executable, "-m",
             "comfyui_distributed_tpu.utils.trace_summary", path, out_path,
             str(traced_s)],
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, check=True,
            capture_output=True, text=True, timeout=SUMMARY_TIMEOUT_S)
        with open(out_path, encoding="utf-8") as f:
            summary = json.load(f)
    except (subprocess.SubprocessError, OSError, ValueError) as e:
        detail = getattr(e, "stderr", "") or ""
        log(f"trace summary failed for {path}: {e} {detail[-500:]}")
        return None
    summary["dir"] = trace_dir
    summary["summary_s"] = round(time.time() - t0, 3)
    log(f"trace summary -> {out_path} in {summary['summary_s']:.1f}s "
        f"(names found: {summary.get('names_found')})")
    return summary


def profile_summary() -> Optional[Dict[str, Any]]:
    """The summary of the last device trace this process stopped."""
    with _trace_lock:
        return _profile


def trace_status() -> Dict[str, Any]:
    with _trace_lock:
        return {"running": _trace_dir is not None, "dir": _trace_dir}


# --- host<->device transfer accounting ---------------------------------------

class TransferStats:
    """Per-label host<->device transfer byte/call counters (thread-safe).

    Labels are workflow node ids when a :func:`node_scope` is active,
    ``"_unattributed"`` otherwise.  Directions: ``d2h`` (device fetch —
    the expensive edge the tensor plane exists to eliminate) and ``h2d``
    (host put)."""

    def __init__(self) -> None:
        self._stats: Dict[str, Dict[str, float]] = {}  # guarded-by: self._lock
        self._lock = threading.Lock()

    def record(self, direction: str, nbytes: int,
               label: Optional[str] = None) -> None:
        key = label or "_unattributed"
        with self._lock:
            s = self._stats.setdefault(
                key, {"d2h_bytes": 0, "d2h_calls": 0,
                      "h2d_bytes": 0, "h2d_calls": 0})
            s[f"{direction}_bytes"] += int(nbytes)
            s[f"{direction}_calls"] += 1

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {k: dict(v) for k, v in self._stats.items()}

    def total(self, direction: str) -> int:
        with self._lock:
            return sum(int(v[f"{direction}_bytes"])
                       for v in self._stats.values())

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()


# process-wide sink (feeds /distributed/metrics); executors push a per-run
# sink on top so ExecutionResult can report per-node transfers for just
# that run
GLOBAL_TRANSFERS = TransferStats()

_transfer_state = threading.local()


def _sinks() -> List[TransferStats]:
    return getattr(_transfer_state, "sinks", None) or []


@contextmanager
def transfer_sink(sink: TransferStats):
    """Additionally record this thread's transfers into ``sink`` (the
    executor's per-run accounting)."""
    stack = getattr(_transfer_state, "sinks", None)
    if stack is None:
        stack = _transfer_state.sinks = []
    stack.append(sink)
    try:
        yield sink
    finally:
        stack.remove(sink)


@contextmanager
def node_scope(node_id: str):
    """Attribute transfers recorded inside the block to a workflow node."""
    prev = getattr(_transfer_state, "node", None)
    _transfer_state.node = str(node_id)
    try:
        yield
    finally:
        _transfer_state.node = prev


def current_node() -> Optional[str]:
    return getattr(_transfer_state, "node", None)


def capture_transfer_context() -> tuple:
    """Snapshot this thread's transfer attribution (node label + per-run
    sinks) so deferred host work keeps reporting into the run that
    spawned it.  The sinks/node state is thread-local; without this, a
    d2h fetch moved onto the encoder pool would vanish from the
    run-local ``ExecutionResult.transfers`` ledger."""
    return (current_node(), list(_sinks()))


@contextmanager
def transfer_context(captured: tuple):
    """Re-enter a :func:`capture_transfer_context` snapshot on another
    thread (the host-IO pool's worker)."""
    node, sinks = captured
    prev_node = getattr(_transfer_state, "node", None)
    stack = getattr(_transfer_state, "sinks", None)
    if stack is None:
        stack = _transfer_state.sinks = []
    added = [s for s in sinks if s not in stack]
    stack.extend(added)
    _transfer_state.node = node
    try:
        yield
    finally:
        _transfer_state.node = prev_node
        for s in added:
            stack.remove(s)


def record_transfer(direction: str, nbytes: int) -> None:
    """Report one host<->device edge (``direction`` in {"d2h", "h2d"}) from
    the ops layer; attribution and per-run fan-out happen here."""
    label = current_node()
    GLOBAL_TRANSFERS.record(direction, nbytes, label)
    for sink in _sinks():
        sink.record(direction, nbytes, label)


# --- retrace / compile counters ----------------------------------------------

class RetraceStats:
    """Monotonic counters over ``jax.monitoring`` events.

    ``traces`` counts jaxpr traces (every cache-missed jit call),
    ``compiles`` counts the backend-compile events: JAX emits one whether
    XLA compiled the program or the persistent cache held it, so
    ``cache_loads`` counts the ones that were loaded and
    ``compiles_uncached`` (in :meth:`mark`) the ones XLA really compiled.
    Seconds: ``trace_s`` (a jit traced inside another's trace counts
    once), ``lower_s`` (jaxpr to MLIR), ``cache_load_s`` (reading and
    deserialising cached executables) and ``compile_s`` (the
    backend-compile events less ``cache_load_s``: XLA's own time, plus
    hashing the cache key).

    The listener runs for every eager primitive and every inner jit of a
    set-up (~26,000 times for SDXL), so a thread adds to a cell of its
    own: no lock on that path, and :meth:`mark` sums the cells."""

    _FIELDS = ("traces", "compiles", "cache_loads", "trace_s", "lower_s",
               "compile_s", "cache_load_s")
    _COUNTS = 3                 # the first _COUNTS fields are whole numbers
    _MAX_OPEN = 4096            # top-level trace intervals kept per thread

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._cells: List[List[float]] = []   # guarded-by: self._lock
        self._local = threading.local()

    def _mine(self):
        local = self._local
        if not hasattr(local, "cell"):
            local.cell = [0.0] * len(self._FIELDS)
            # trace intervals counted so far that no later one has
            # enclosed yet: disjoint, ordered by start
            local.starts, local.durs = [], []
            with self._lock:
                self._cells.append(local.cell)
        return local

    def add(self, count: int, seconds: int, duration: float) -> None:
        """One duration event: ``count`` / ``seconds`` index _FIELDS
        (``count`` < 0: none)."""
        local = self._mine()
        if count >= 0:
            local.cell[count] += 1
        if seconds == _TRACE_S:
            duration = self._own_trace_seconds(local, duration)
        local.cell[seconds] += duration

    def _own_trace_seconds(self, local, duration: float) -> float:
        """``duration`` less what was already counted inside it.  JAX
        reports a jit traced inside another's trace once on its own (the
        inner event ends first) and again within the outer one; summed
        as they come, the seconds would count that time twice."""
        # (the listener runs a few microseconds after the event's end, so
        # an inner interval may seem to start that much before its outer)
        start = time.perf_counter() - duration
        starts, durs = local.starts, local.durs
        inside = 0.0
        while starts and starts[-1] >= start - 2e-5:
            starts.pop()
            inside += durs.pop()
        starts.append(start)
        durs.append(duration)
        if len(starts) > self._MAX_OPEN:
            del starts[:self._MAX_OPEN // 2], durs[:self._MAX_OPEN // 2]
        return max(duration - inside, 0.0)

    def mark(self) -> Dict[str, float]:
        with self._lock:
            cells = list(self._cells)
        out: Dict[str, float] = {}
        for i, k in enumerate(self._FIELDS):
            total = sum(c[i] for c in cells)
            out[k] = int(total) if i < self._COUNTS else total
        out["compiles_uncached"] = out["compiles"] - out["cache_loads"]
        return out

    def since(self, mark: Dict[str, float]) -> Dict[str, float]:
        now = self.mark()
        return {k: now[k] - mark.get(k, 0) for k in now}


GLOBAL_RETRACES = RetraceStats()

_TRACE_S = RetraceStats._FIELDS.index("trace_s")
_COMPILE_S = RetraceStats._FIELDS.index("compile_s")
_CACHE_LOAD_S = RetraceStats._FIELDS.index("cache_load_s")
# jax.monitoring duration events -> indices of (count, seconds) in _FIELDS
_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": (0, _TRACE_S),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        (-1, RetraceStats._FIELDS.index("lower_s")),
    "/jax/core/compile/backend_compile_duration": (1, _COMPILE_S),
    "/jax/compilation_cache/cache_retrieval_time_sec": (2, _CACHE_LOAD_S),
}
# persistent compile cache: pipeline counter per event.  JAX records
# "cache_misses" where it WRITES an entry (a compile that was too short,
# or whose program holds a host callback, is neither a hit nor a write)
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile_cache_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache_writes",
}

_monitoring_installed = False
_monitoring_lock = threading.Lock()


def install_jax_monitoring() -> None:
    """Register the (process-global, idempotent) ``jax.monitoring``
    listeners feeding :data:`GLOBAL_RETRACES` and the persistent
    compile cache's hit/write pipeline counters.  Cheap to call per run."""
    global _monitoring_installed
    with _monitoring_lock:
        if _monitoring_installed:
            return
        import jax.monitoring as monitoring

        def on_duration(name: str, duration: float, **kw) -> None:
            fields = _DURATION_EVENTS.get(name)
            if fields is None:
                return
            GLOBAL_RETRACES.add(fields[0], fields[1], duration)
            if fields[1] == _CACHE_LOAD_S:
                # the load happened inside a backend-compile event
                GLOBAL_RETRACES.add(-1, _COMPILE_S, -duration)

        def on_event(name: str, **kw) -> None:
            counter = _CACHE_EVENTS.get(name)
            if counter is not None:
                GLOBAL_COUNTERS.bump(counter)

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        _monitoring_installed = True


def counters_snapshot() -> Dict[str, Any]:
    """One payload for /distributed/metrics and bench artifacts."""
    return {"transfers": GLOBAL_TRANSFERS.snapshot(),
            "retraces": GLOBAL_RETRACES.mark(),
            "attention_paths": ATTENTION_PATHS.snapshot(),
            "geglu_paths": GEGLU_PATHS.snapshot(),
            "dense_paths": DENSE_PATHS.snapshot()}


# --- request-scoped distributed tracing (spans) ------------------------------
#
# Dapper-lite: always-on, low-overhead, propagated through RPC metadata.
# A span is a named timed interval with a trace_id shared by every span of
# one job (across processes) and a parent_id forming the tree.  The
# current span rides a contextvar — correct across asyncio task
# boundaries (each task gets a context copy at creation) and explicit
# across thread handoffs via capture_span_context()/use_span(), the span
# analog of capture_transfer_context.

_tracing_enabled = os.environ.get(C.TRACE_ENV, "1").lower() \
    not in ("0", "false", "off")


def set_tracing(enabled: bool) -> None:
    """Process-wide span-creation switch (env ``DTPU_TRACE`` start value).
    Aggregate metrics (phases/stages/counters) are unaffected — this
    gates only the per-request span machinery."""
    global _tracing_enabled
    _tracing_enabled = bool(enabled)


def tracing_enabled() -> bool:
    return _tracing_enabled


def new_trace_id() -> str:
    return os.urandom(16).hex()


def new_span_id() -> str:
    return os.urandom(8).hex()


class Span:
    """One timed interval of a request trace.

    ``parent`` is the in-process parent Span (None for a local root);
    ``parent_id`` may be set without a parent object when the parent
    lives in another process (the inbound traceparent case)."""

    __slots__ = ("trace_id", "span_id", "parent", "parent_id", "name",
                 "attrs", "start_s", "end_s", "status", "error", "_token")

    def __init__(self, name: str, trace_id: Optional[str] = None,
                 parent: Optional["Span"] = None,
                 parent_id: Optional[str] = None,
                 attrs: Optional[Dict[str, Any]] = None):
        self.name = str(name)
        self.parent = parent
        if parent is not None:
            self.trace_id = parent.trace_id
            self.parent_id = parent.span_id
        else:
            self.trace_id = trace_id or new_trace_id()
            self.parent_id = parent_id
        self.span_id = new_span_id()
        self.attrs: Dict[str, Any] = dict(attrs or {})
        self.start_s = time.time()
        self.end_s: Optional[float] = None
        self.status = "ok"
        self.error: Optional[str] = None
        self._token: Any = None  # contextvar token while current

    def set_status(self, status: str, error: Optional[str] = None) -> None:
        self.status = status
        if error is not None:
            self.error = str(error)[:500]

    def end(self, status: Optional[str] = None) -> None:
        if self.end_s is not None:
            return  # idempotent: double-end keeps the first timing
        if status is not None:
            self.status = status
        self.end_s = time.time()
        GLOBAL_TRACES.on_end(self)

    def to_dict(self, provisional: bool = False) -> Dict[str, Any]:
        end = self.end_s if self.end_s is not None else time.time()
        d = {"trace_id": self.trace_id, "span_id": self.span_id,
             "parent_id": self.parent_id, "name": self.name,
             "start_s": round(self.start_s, 6), "end_s": round(end, 6),
             "duration_s": round(end - self.start_s, 6),
             "status": self.status}
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.error:
            d["error"] = self.error
        if provisional and self.end_s is None:
            d["provisional"] = True
        return d


_SPAN_VAR: "contextvars.ContextVar[Optional[Span]]" = \
    contextvars.ContextVar("dtpu_current_span", default=None)


def current_span() -> Optional[Span]:
    return _SPAN_VAR.get()


def current_trace_ids() -> Optional[Dict[str, str]]:
    """``{"trace_id", "span_id", "prompt_id"?}`` for the active span — the
    correlation fields the JSON log mode stamps on every line."""
    sp = _SPAN_VAR.get()
    if sp is None:
        return None
    out = {"trace_id": sp.trace_id, "span_id": sp.span_id}
    node: Optional[Span] = sp
    while node is not None:
        pid = node.attrs.get("prompt_id")
        if pid:
            out["prompt_id"] = str(pid)
            break
        node = node.parent
    return out


def start_span(name: str, trace_id: Optional[str] = None,
               parent: Optional[Span] = None,
               parent_id: Optional[str] = None,
               attrs: Optional[Dict[str, Any]] = None) -> Optional[Span]:
    """Open a span (a root when no parent is given).  Returns None with
    tracing disabled — every consumer treats the span as optional."""
    if not _tracing_enabled:
        return None
    sp = Span(name, trace_id=trace_id, parent=parent, parent_id=parent_id,
              attrs=attrs)
    GLOBAL_TRACES.on_start(sp)
    return sp


def _begin_span(name: str, **attrs: Any) -> Optional[Span]:
    """Child of the current span, set as current; None when no trace is
    active (stray stages outside a job never create orphan spans)."""
    parent = _SPAN_VAR.get()
    if parent is None or not _tracing_enabled:
        return None
    sp = Span(name, parent=parent, attrs=attrs or None)
    GLOBAL_TRACES.on_start(sp)
    sp._token = _SPAN_VAR.set(sp)
    return sp


def _end_span(sp: Optional[Span]) -> None:
    if sp is None:
        return
    token, sp._token = sp._token, None
    if token is not None:
        try:
            _SPAN_VAR.reset(token)
        except ValueError:
            # reset from a different context (thread/task migrated the
            # span) — clearing by value keeps the var consistent
            if _SPAN_VAR.get() is sp:
                _SPAN_VAR.set(sp.parent)
    sp.end()


@contextmanager
def span(name: str, **attrs: Any):
    """Child span of the current span, current within the block; yields
    None (and records nothing) when no trace is active."""
    sp = _begin_span(name, **attrs)
    ann = _annotate(name)
    lane, entry = _open(name, _now_ns())
    try:
        yield sp
    except BaseException as e:
        if sp is not None:
            sp.set_status("error", repr(e))
        raise
    finally:
        _end_annotation(ann)
        _close(lane, entry, _now_ns())
        _end_span(sp)


@contextmanager
def use_span(sp: Optional[Span]):
    """Make ``sp`` the current span for the block WITHOUT ending it on
    exit (the span's owner ends it) — the reattach half of the
    cross-thread handoff, and how the exec loop parents a run under the
    job span created at enqueue time."""
    if sp is None:
        yield None
        return
    token = _SPAN_VAR.set(sp)
    try:
        yield sp
    finally:
        _SPAN_VAR.reset(token)


def capture_span_context() -> Optional[Span]:
    """Snapshot this thread's/task's span context for reattachment on
    another thread (``with use_span(captured): ...``) — mirrors
    :func:`capture_transfer_context` for the HostIOPool handoff."""
    return _SPAN_VAR.get()


def event_span(name: str, start_s: float, end_s: float,
               parent: Optional[Span] = None,
               trace_id: Optional[str] = None,
               parent_id: Optional[str] = None,
               attrs: Optional[Dict[str, Any]] = None,
               status: str = "ok") -> Optional[Dict[str, Any]]:
    """Record an already-finished interval as a span (queue_wait measured
    at pop time, an inbound upload measured by the handler).  Accepts a
    parent Span or raw (trace_id, parent_id) for remote parents.  The
    interval is over, so a running device trace gets an instant that
    carries its bounds."""
    if _trace_dir is not None:
        _end_annotation(_annotate(name, start_s=round(start_s, 6),
                                  end_s=round(end_s, 6)))
    _keep_wall(name, start_s, end_s)
    return _add_event_span(name, start_s, end_s, parent, trace_id,
                           parent_id, attrs, status)


def _add_event_span(name: str, start_s: float, end_s: float,
                    parent: Optional[Span] = None,
                    trace_id: Optional[str] = None,
                    parent_id: Optional[str] = None,
                    attrs: Optional[Dict[str, Any]] = None,
                    status: str = "ok") -> Optional[Dict[str, Any]]:
    if not _tracing_enabled:
        return None
    if parent is not None:
        trace_id, parent_id = parent.trace_id, parent.span_id
    if not trace_id:
        return None
    d = {"trace_id": trace_id, "span_id": new_span_id(),
         "parent_id": parent_id, "name": str(name),
         "start_s": round(start_s, 6), "end_s": round(end_s, 6),
         "duration_s": round(max(end_s - start_s, 0.0), 6),
         "status": status}
    if attrs:
        d["attrs"] = dict(attrs)
    GLOBAL_TRACES.add(trace_id, d)
    return d


# --- W3C traceparent (the propagation header) --------------------------------

def format_traceparent(sp: Span) -> str:
    """``00-<trace_id>-<span_id>-01`` (W3C trace-context, sampled)."""
    return f"00-{sp.trace_id}-{sp.span_id}-01"


def traceparent_headers(sp: Optional[Span] = None) -> Dict[str, str]:
    """Headers dict carrying the current (or given) span's traceparent;
    empty when no trace is active — callers merge unconditionally."""
    sp = sp if sp is not None else _SPAN_VAR.get()
    if sp is None or not _tracing_enabled:
        return {}
    return {C.TRACEPARENT_HEADER: format_traceparent(sp)}


def parse_traceparent(header: Optional[str]
                      ) -> Optional[Tuple[str, str]]:
    """``(trace_id, parent_span_id)`` from a traceparent header, or None
    on anything malformed (propagation must never fail a request)."""
    if not header:
        return None
    parts = str(header).strip().split("-")
    if len(parts) < 4:
        return None
    _, trace_id, span_id = parts[0], parts[1], parts[2]
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16), int(span_id, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


# --- flight recorder ---------------------------------------------------------

class FlightRecorder:
    """Bounded ring of recent completed job traces + the accumulation
    buffer for in-flight ones.

    Spans land here as they finish (``on_end``) or arrive from a peer
    (``ingest`` — the worker ships its spans on the final data-plane
    POST); ``commit(prompt_id, trace_id)`` moves a trace into the ring
    when its job finalizes.  Late arrivals for a committed trace are
    appended to the ring entry, so a straggler tile's spans still reach
    the postmortem.  Everything is bounded: spans per trace
    (``TRACE_MAX_SPANS``), in-flight traces, and the ring itself
    (``DTPU_TRACE_RING``)."""

    def __init__(self, max_traces: Optional[int] = None,
                 max_spans: int = C.TRACE_MAX_SPANS):
        self._lock = threading.Lock()
        self.max_traces = max_traces if max_traces is not None else \
            max(1, int(os.environ.get(C.TRACE_RING_ENV,
                                      C.TRACE_RING_DEFAULT)))
        self.max_spans = max_spans
        # trace_id -> {span_id: span dict} for in-flight traces
        self._active: "OrderedDict[str, Dict[str, Dict]]" = \
            OrderedDict()                       # guarded-by: self._lock
        # trace_id -> [open Span] (exported provisionally mid-flight)
        self._open: Dict[str, List[Span]] = {}  # guarded-by: self._lock
        # prompt_id -> committed record (the ring)
        self._jobs: "OrderedDict[str, Dict[str, Any]]" = \
            OrderedDict()                       # guarded-by: self._lock
        # committed trace -> prompt
        self._by_trace: Dict[str, str] = {}     # guarded-by: self._lock
        self.dropped_spans = 0                  # guarded-by: self._lock
        self.evictions = 0                      # guarded-by: self._lock

    # -- span sinks ---------------------------------------------------------

    def on_start(self, sp: Span) -> None:
        with self._lock:
            self._open.setdefault(sp.trace_id, []).append(sp)

    def on_end(self, sp: Span) -> None:
        with self._lock:
            opens = self._open.get(sp.trace_id)
            if opens is not None:
                try:
                    opens.remove(sp)
                except ValueError:
                    pass
                if not opens:
                    del self._open[sp.trace_id]
        self.add(sp.trace_id, sp.to_dict())

    def add(self, trace_id: str, span_dict: Dict[str, Any]) -> None:
        """Insert/replace one span dict (keyed by span_id: a provisional
        remote span is superseded by its final version)."""
        with self._lock:
            pid = self._by_trace.get(trace_id)
            if pid is not None:
                rec = self._jobs.get(pid)
                if rec is not None and (
                        span_dict["span_id"] in rec["_ids"]
                        or len(rec["spans"]) < self.max_spans):
                    if span_dict["span_id"] in rec["_ids"]:
                        rec["spans"] = [span_dict
                                        if s["span_id"] ==
                                        span_dict["span_id"] else s
                                        for s in rec["spans"]]
                    else:
                        rec["spans"].append(span_dict)
                        rec["_ids"].add(span_dict["span_id"])
                else:
                    self.dropped_spans += 1
                return
            spans = self._active.get(trace_id)
            if spans is None:
                # bound the in-flight buffer too: a flood of orphan
                # traces (e.g. remote spans for jobs this process never
                # commits) must not grow without limit
                while len(self._active) >= 4 * self.max_traces:
                    self._active.popitem(last=False)
                spans = self._active[trace_id] = {}
            if span_dict["span_id"] in spans \
                    or len(spans) < self.max_spans:
                spans[span_dict["span_id"]] = span_dict
            else:
                self.dropped_spans += 1

    def ingest(self, span_dicts: List[Dict[str, Any]]) -> int:
        """Merge spans shipped from a peer process (dicts with their own
        trace_id); malformed entries are skipped, count kept is
        returned."""
        kept = 0
        for d in span_dicts or []:
            if not isinstance(d, dict):
                continue
            tid, sid = d.get("trace_id"), d.get("span_id")
            if not tid or not sid:
                continue
            self.add(str(tid), d)
            kept += 1
        return kept

    def export(self, trace_id: str,
               include_open: bool = True) -> List[Dict[str, Any]]:
        """The trace's spans as dicts — finished ones plus (optionally)
        still-open ones with a provisional end, for shipping to the
        master before the local job span closes."""
        with self._lock:
            pid = self._by_trace.get(trace_id)
            if pid is not None and pid in self._jobs:
                out = list(self._jobs[pid]["spans"])
            else:
                out = list(self._active.get(trace_id, {}).values())
            opens = list(self._open.get(trace_id, ())) if include_open \
                else []
        out.extend(sp.to_dict(provisional=True) for sp in opens)
        return out

    # -- job lifecycle ------------------------------------------------------

    def commit(self, prompt_id: str, trace_id: str, status: str = "ok",
               root_span_id: Optional[str] = None,
               duration_s: Optional[float] = None) -> None:
        """Seal a job's trace into the ring under its prompt id.

        A trace_id may legitimately commit under more than one prompt id
        in ONE process (single-process loopback: the worker-role job and
        the master's fan-out job share the trace and the recorder) — the
        later commit absorbs the earlier record's spans so whichever
        prompt id the client holds resolves to the full tree."""
        evicted_total = 0
        with self._lock:
            by_id = dict(self._active.pop(trace_id, {}))
            prev_pid = self._by_trace.get(trace_id)
            if prev_pid is not None and prev_pid != str(prompt_id):
                prev = self._jobs.get(prev_pid)
                if prev is not None:
                    for s in prev["spans"]:
                        by_id.setdefault(s["span_id"], s)
            spans = list(by_id.values())
            rec = {"prompt_id": str(prompt_id), "trace_id": trace_id,
                   "status": status, "root_span_id": root_span_id,
                   "duration_s": duration_s, "finished_at": time.time(),
                   "spans": spans,
                   "_ids": set(by_id)}
            self._jobs[str(prompt_id)] = rec
            self._jobs.move_to_end(str(prompt_id))
            self._by_trace[trace_id] = str(prompt_id)
            # snapshot for the exporter inside the lock: a late-arrival
            # add() may mutate rec["spans"] the moment we release
            export_rec = {k: v for k, v in rec.items() if k != "_ids"}
            export_rec["spans"] = list(spans)
            while len(self._jobs) > self.max_traces:
                _, old = self._jobs.popitem(last=False)
                # only unmap the trace if the mapping still points at the
                # evicted record: after a dual-commit (loopback), the
                # newer prompt's record owns the mapping and must keep
                # receiving late arrivals
                if self._by_trace.get(old["trace_id"]) \
                        == old["prompt_id"]:
                    self._by_trace.pop(old["trace_id"], None)
                self.evictions += 1
                evicted_total = self.evictions
        if evicted_total:
            GLOBAL_COUNTERS.bump("trace_evictions")
            # no-silent-caps: the ring forgetting history is normal but
            # must be visible — one line per N, not one per trace
            if evicted_total % C.TRACE_EVICT_LOG_EVERY == 0:
                log(f"flight recorder: {evicted_total} committed traces "
                    f"evicted from the {self.max_traces}-entry ring "
                    f"(raise {C.TRACE_RING_ENV} or set "
                    f"{C.TRACE_EXPORT_DIR_ENV} for durable capture)")
        # durable capture plane (ISSUE 18): committed traces stream to
        # the capture files; a no-op unless DTPU_TRACE_EXPORT_DIR is set.
        # This runs on the finalizer/executor threads (never the event
        # loop) and outside the recorder lock — the exporter has its own.
        from comfyui_distributed_tpu.utils import trace_export
        trace_export.on_commit(export_rec)
        # critical-path analytics plane (ISSUE 20): armed only while a
        # baseline profile is configured; disarmed it costs one env read
        from comfyui_distributed_tpu.utils import trace_analysis
        trace_analysis.on_commit(export_rec)

    def get(self, prompt_id: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            rec = self._jobs.get(str(prompt_id))
            if rec is None:
                return None
            out = {k: v for k, v in rec.items() if k != "_ids"}
            out["spans"] = sorted(rec["spans"],
                                  key=lambda s: s.get("start_s", 0.0))
            out["n_spans"] = len(out["spans"])
            return out

    def index(self) -> List[Dict[str, Any]]:
        """Newest-first job summaries for ``GET /distributed/traces``."""
        with self._lock:
            return [{"prompt_id": rec["prompt_id"],
                     "trace_id": rec["trace_id"],
                     "status": rec["status"],
                     "duration_s": rec["duration_s"],
                     "finished_at": rec["finished_at"],
                     "n_spans": len(rec["spans"])}
                    for rec in reversed(self._jobs.values())]

    def records(self) -> List[Dict[str, Any]]:
        """All committed job records, oldest first, shaped like
        :meth:`get` (sorted span-dict lists) — the cross-trace
        analytics plane's bulk read (ISSUE 20)."""
        with self._lock:
            out = []
            for rec in self._jobs.values():
                r = {k: v for k, v in rec.items() if k != "_ids"}
                r["spans"] = sorted(rec["spans"],
                                    key=lambda s: s.get("start_s", 0.0))
                out.append(r)
            return out

    def breakdown(self, trace_id: str) -> Dict[str, float]:
        """Per-span-name total seconds for one trace — the slow-job log's
        one-line stage summary."""
        out: Dict[str, float] = {}
        for s in self.export(trace_id, include_open=False):
            out[s["name"]] = round(
                out.get(s["name"], 0.0) + float(s.get("duration_s", 0.0)),
                6)
        return out

    def size(self) -> int:
        with self._lock:
            return len(self._jobs)

    def eviction_count(self) -> int:
        with self._lock:
            return self.evictions

    def reset(self) -> None:
        with self._lock:
            self._active.clear()
            self._open.clear()
            self._jobs.clear()
            self._by_trace.clear()
            self.dropped_spans = 0
            self.evictions = 0


GLOBAL_TRACES = FlightRecorder()


def build_span_tree(spans: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Nest span dicts by parent_id: returns the root list, each node a
    copy with a ``children`` list (start-time ordered).  Spans whose
    parent is unknown (a remote hop that never shipped) surface as
    additional roots rather than vanishing."""
    nodes = {s["span_id"]: {**s, "children": []}
             for s in sorted(spans, key=lambda s: s.get("start_s", 0.0))}
    roots: List[Dict[str, Any]] = []
    for node in nodes.values():
        parent = nodes.get(node.get("parent_id") or "")
        if parent is not None and parent is not node:
            parent["children"].append(node)
        else:
            roots.append(node)
    return roots


# --- Prometheus text exposition ----------------------------------------------

def _prom_escape(value: Any) -> str:
    return (str(value).replace("\\", r"\\").replace("\n", r"\n")
            .replace('"', r'\"'))


def _prom_labels(labels: Dict[str, Any]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_prom_escape(v)}"'
                     for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def _prom_num(v: float) -> str:
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _render_histogram_family(lines: List[str], family: str, help_text: str,
                             stats: PhaseStats, label_key: str) -> None:
    hists = stats.histograms()
    lines.append(f"# HELP {family} {help_text}")
    lines.append(f"# TYPE {family} histogram")
    for name in sorted(hists):
        base = {label_key: name}
        h = hists[name]
        buckets, sum_s, count = h.prom_series()
        exemplars = h.exemplars_snapshot()
        for i, (le, cum) in enumerate(buckets):
            le_s = "+Inf" if le == float("inf") else _prom_num(le)
            line = (f"{family}_bucket"
                    f"{_prom_labels({**base, 'le': le_s})} {cum}")
            ex = exemplars.get(i)
            if ex is not None:
                # OpenMetrics exemplar: the last sample that landed in
                # THIS (non-cumulative) bucket, linking it to a trace
                tid, val, ts = ex
                line += (f' # {{trace_id="{_prom_escape(tid)}"}} '
                         f"{_prom_num(val)} {round(ts, 3)}")
            lines.append(line)
        lines.append(f"{family}_sum{_prom_labels(base)} {repr(sum_s)}")
        lines.append(f"{family}_count{_prom_labels(base)} {count}")


def prometheus_text(extra: Optional[List[Tuple[str, str, str,
                                               List[Tuple[Dict, float]]]]]
                    = None) -> str:
    """Render the telemetry state as Prometheus text exposition format
    (v0.0.4): stage/phase/node latency histograms (``_bucket``/``_sum``/
    ``_count``), event counters, transfer byte counters, jit
    trace/compile counters and the flight-recorder gauge.  ``extra`` adds
    caller families as ``(name, type, help, [(labels, value), ...])`` —
    the server layer appends its prompt/image counters and queue gauge."""
    lines: List[str] = []
    _fold_gc()
    _render_histogram_family(
        lines, "dtpu_stage_seconds",
        "Serving-pipeline stage wall-clock (overlapping stages).",
        GLOBAL_STAGES, "stage")
    _render_histogram_family(
        lines, "dtpu_phase_seconds",
        "Internal phase wall-clock (Timer sink).",
        GLOBAL_PHASES, "phase")
    _render_histogram_family(
        lines, "dtpu_node_seconds",
        "Per-workflow-node-type op execution seconds.",
        GLOBAL_NODES, "node_type")

    lines.append("# HELP dtpu_events_total Scheduler/wire/pipeline event "
                 "counters.")
    lines.append("# TYPE dtpu_events_total counter")
    for name, value in sorted(GLOBAL_COUNTERS.snapshot().items()):
        lines.append(f"dtpu_events_total{_prom_labels({'event': name})} "
                     f"{int(value)}")

    lines.append("# HELP dtpu_transfer_bytes_total Host<->device transfer "
                 "bytes by direction.")
    lines.append("# TYPE dtpu_transfer_bytes_total counter")
    for direction in ("d2h", "h2d"):
        lines.append(
            f"dtpu_transfer_bytes_total"
            f"{_prom_labels({'direction': direction})} "
            f"{GLOBAL_TRANSFERS.total(direction)}")

    retr = GLOBAL_RETRACES.mark()
    lines.append("# HELP dtpu_jit_traces_total Jaxpr traces observed "
                 "(cache-missed jit calls).")
    lines.append("# TYPE dtpu_jit_traces_total counter")
    lines.append(f"dtpu_jit_traces_total {retr['traces']}")
    lines.append("# HELP dtpu_xla_compiles_total Backend (XLA) "
                 "compilations observed.")
    lines.append("# TYPE dtpu_xla_compiles_total counter")
    lines.append(f"dtpu_xla_compiles_total {retr['compiles']}")

    lines.append("# HELP dtpu_trace_ring_size Completed job traces held "
                 "by the flight recorder.")
    lines.append("# TYPE dtpu_trace_ring_size gauge")
    lines.append(f"dtpu_trace_ring_size {GLOBAL_TRACES.size()}")

    lines.append("# HELP dtpu_trace_evictions_total Committed traces "
                 "pushed out of the flight-recorder ring.")
    lines.append("# TYPE dtpu_trace_evictions_total counter")
    lines.append(f"dtpu_trace_evictions_total "
                 f"{GLOBAL_TRACES.eviction_count()}")

    _append_prom_families(lines, extra or [])
    return "\n".join(lines) + "\n"


def _append_prom_families(lines: List[str],
                          families: List[Tuple[str, str, str,
                                               List[Tuple[Dict, float]]]]
                          ) -> None:
    for name, typ, help_text, samples in families:
        lines.append(f"# HELP {name} {help_text}")
        lines.append(f"# TYPE {name} {typ}")
        for labels, value in samples:
            lines.append(f"{name}{_prom_labels(labels)} {_prom_num(value)}")


def render_prom_families(families: List[Tuple[str, str, str,
                                              List[Tuple[Dict, float]]]]
                         ) -> str:
    """Standalone Prometheus text for caller-supplied families only (the
    federated cluster exposition renders fleet gauges without duplicating
    this process's histograms)."""
    lines: List[str] = []
    _append_prom_families(lines, families)
    return "\n".join(lines) + "\n"


def reset_aggregate_metrics() -> Dict[str, Any]:
    """POST /distributed/metrics/reset core: clear the process-wide
    aggregate sinks (phases, stages, node timings, counters, transfers)
    so benches and multi-phase test runs stop inheriting cross-run
    telemetry.  Retrace counters are monotonic observations of
    jax.monitoring and are NOT reset (readers diff marks); the flight
    recorder keeps its per-job history unless asked."""
    _fold_gc()      # a pause that ended before the reset is not the next window's
    before = {"phases": len(GLOBAL_PHASES.snapshot()),
              "stages": len(GLOBAL_STAGES.snapshot()),
              "nodes": len(GLOBAL_NODES.snapshot()),
              "counters": len(GLOBAL_COUNTERS.snapshot()),
              "transfer_labels": len(GLOBAL_TRANSFERS.snapshot())}
    GLOBAL_PHASES.reset()
    GLOBAL_STAGES.reset()
    GLOBAL_NODES.reset()
    GLOBAL_COUNTERS.reset()
    GLOBAL_TRANSFERS.reset()
    return before
