"""aiohttp application: the reference's full route surface plus TPU-native
status/metrics.

Route inventory (capability parity with reference ``distributed.py:49-599,
1135-1218`` and ``distributed_upscale.py:711-760``; SURVEY.md §2 #5-#8,
#13, #15, #22-#24):

  control plane
    GET  /distributed/config                 full config
    POST /distributed/config/update_worker   upsert (None deletes field)
    POST /distributed/config/delete_worker
    POST /distributed/config/update_setting
    POST /distributed/config/update_master
    GET  /distributed/network_info           host IPs + recommended master IP
    POST /distributed/clear_memory           drop model/jit caches, gc
    POST /distributed/launch_worker          process manager
    POST /distributed/stop_worker
    GET  /distributed/managed_workers
    GET  /distributed/worker_log             backwards log tail
    POST /distributed/worker/clear_launching
    GET  /distributed/queue_status           does a tile job queue exist
    POST /distributed/prepare_job            create queue before dispatch
    POST /distributed/load_image             base64 input staging
    GET  /distributed/status                 mesh topology + runtime (new)
    GET  /distributed/metrics                counters/timings (new)
    GET  /distributed/metrics.prom           Prometheus text exposition (new)
    POST /distributed/metrics/reset          clear aggregate sinks (new)
    GET  /distributed/traces                 flight-recorder index (new)
    GET  /distributed/trace/<prompt_id>      one job's span tree (new)
    GET  /distributed/slo                    SLO burn-rate snapshot (new)
    GET  /distributed/cluster                lease states + work ledger (new)
    POST /distributed/register               elastic worker registration (new)
    POST /distributed/heartbeat              worker lease renewal (new)

  data plane
    POST /distributed/job_complete           multipart PNG -> image queue
    POST /distributed/tile_complete          multipart PNG -> tile queue

  ComfyUI-compatible worker surface (what the reference's workers expose)
    GET  /prompt        {"exec_info": {"queue_remaining": N}}
    POST /prompt        queue a workflow for execution
    POST /interrupt     stop the running job
    POST /upload/image  receive staged input images
"""

from __future__ import annotations

import asyncio
import base64
import collections
import functools
import itertools
import json
import math
import os
import queue
import threading
import time
from typing import Any, Dict, List, Optional

from aiohttp import web

from comfyui_distributed_tpu.ops.base import OpContext
from comfyui_distributed_tpu.runtime import autoscale as autoscale_mod
from comfyui_distributed_tpu.runtime import cluster as cluster_mod
from comfyui_distributed_tpu.runtime import reuse as reuse_mod
from comfyui_distributed_tpu.runtime import shard as shard_mod
from comfyui_distributed_tpu.runtime.jobs import JobStore
from comfyui_distributed_tpu.server.lm_handover import GenerateHandover
from comfyui_distributed_tpu.utils import chaos as chaos_mod
from comfyui_distributed_tpu.runtime.manager import (
    WorkerProcessManager,
    auto_launch_workers,
)
from comfyui_distributed_tpu.utils import config as cfg_mod
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils import net as net_mod
from comfyui_distributed_tpu.utils import resource as resource_mod
from comfyui_distributed_tpu.utils import slo as slo_mod
from comfyui_distributed_tpu.utils import trace as trace_mod
from comfyui_distributed_tpu.utils import trace_analysis as analysis_mod
from comfyui_distributed_tpu.utils import trace_export as trace_export_mod
from comfyui_distributed_tpu.utils.constants import LOG_TAIL_BYTES
from comfyui_distributed_tpu.utils.image import decode_png, decode_tensor
from comfyui_distributed_tpu.utils.logging import debug_log, log
from comfyui_distributed_tpu.workflow import scheduler as sched_mod
from comfyui_distributed_tpu.workflow.executor import WorkflowExecutor


class QueueFullError(RuntimeError):
    """enqueue_prompt hit the DTPU_MAX_QUEUE backpressure cap."""


class ShedError(QueueFullError):
    """Admission shed the prompt (class-aware overload shedding or a
    per-client token bucket); carries the rejection detail so the 429
    can tell the client WHY and HOW LONG to back off."""

    def __init__(self, rejection: Dict[str, Any]):
        self.rejection = dict(rejection)
        super().__init__(
            f"shed ({rejection.get('reason')}) for tenant class "
            f"{rejection.get('tenant')!r}")


class DrainingError(RuntimeError):
    """enqueue_prompt refused: the server is shutting down."""


def _env_flag(name: str, default: str = "1") -> bool:
    return os.environ.get(name, default).lower() not in ("0", "false", "off")


class ServerState:
    """Everything the handlers share: config path, job store, process
    manager, the execution queue and its pipelined worker thread.

    The execution pipeline (ISSUE 2): the exec thread pops a *group* of
    signature-identical prompts (workflow/scheduler.py) and runs them as
    one batched dispatch; OUTPUT-node host edges (d2h/PNG/disk) defer
    onto a bounded host-IO pool so job N's encode overlaps job N+1's
    denoise loop; a finalizer thread joins the deferred work and writes
    history/metrics.  ``overlap``/``coalesce`` default from
    DTPU_OVERLAP/DTPU_COALESCE ("0" restores the strictly serial seed
    behavior — same thread does everything)."""

    def __init__(self, config_path: Optional[str] = None,
                 is_worker: bool = False,
                 input_dir: Optional[str] = None,
                 output_dir: Optional[str] = None,
                 models_dir: Optional[str] = None,
                 start_exec_thread: bool = True,
                 overlap: Optional[bool] = None,
                 coalesce: Optional[bool] = None,
                 cb: Optional[bool] = None):
        self.config_path = config_path
        self.is_worker = is_worker
        self.port: Optional[int] = None  # set by serve()
        self.input_dir = input_dir or os.path.join(os.getcwd(), "input")
        self.output_dir = output_dir or os.path.join(os.getcwd(), "output")
        self.models_dir = models_dir
        self.jobs = JobStore()
        self.manager = WorkerProcessManager(config_path=config_path,
                                            models_dir=models_dir)
        # cluster control plane (ISSUE 4): worker registry with leases +
        # per-job work ledger.  Seeded from config; the health poller,
        # heartbeats and data-plane POSTs all renew leases; the
        # collectors consult both through OpContext.
        self.cluster = cluster_mod.ClusterRegistry()
        self.ledger = cluster_mod.WorkLedger()
        if not is_worker:
            try:
                self.cluster.seed_from_config(
                    cfg_mod.load_config(config_path).get("workers", []))
            except Exception as e:  # noqa: BLE001 - config is optional
                debug_log(f"cluster seed skipped: {e}")
        self.fault_inject = cluster_mod.fault_injection()
        # worker->master lease renewal (set by serve(); the rehome
        # endpoint retargets it when a standby master takes over)
        self.heartbeat: Optional[Any] = None
        from comfyui_distributed_tpu.runtime.health import HealthPoller
        self.health = HealthPoller(config_path=config_path,
                                   manager=self.manager,
                                   registry=self.cluster)
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        # the process-global flag: compiled samplers poll it per step
        # (runtime/interrupt.py), so /interrupt stops a sample in flight
        from comfyui_distributed_tpu.runtime.interrupt import interrupt_event
        self.interrupt_event = interrupt_event()
        self.metrics: Dict[str, Any] = {
            "prompts_executed": 0, "prompts_failed": 0,
            "images_received": 0, "tiles_received": 0,
            # cross-request reuse (ISSUE 13): exact-hit replays and
            # client-gone abandonments are neither executed nor failed
            "prompts_replayed": 0, "prompts_abandoned": 0,
            "last_execution_s": None,
        }
        self.max_queue = int(os.environ.get(C.MAX_QUEUE_ENV,
                                            C.MAX_QUEUE_DEFAULT))
        # SLO-aware multi-tenant admission (ISSUE 9): priority classes
        # with weighted fair dequeue, class-aware shedding and optional
        # per-client token buckets.  Untagged traffic rides the highest
        # class, so single-tenant deployments keep the plain
        # DTPU_MAX_QUEUE backpressure semantics unchanged.
        self.admission = sched_mod.AdmissionController()
        # SLO burn-rate engine (ISSUE 18): per-tenant-class objectives
        # from DTPU_SLO_SPEC over fast/slow rolling windows, fed by
        # _finalize_group; disarmed (record() is a no-op) without a spec
        self.slo = slo_mod.SLOEngine.from_env()
        # completion timestamps ring feeding the 429 Retry-After hint
        # (drain rate = prompts finalized per second, recent window)
        self._completions: collections.deque = collections.deque(
            maxlen=128)
        # elastic-fleet autoscaler: armed by serve() on a master when
        # DTPU_AUTOSCALE=1 (runtime/autoscale.install)
        self.autoscaler: Optional[Any] = None
        # resource telemetry plane (ISSUE 5): process-global sampler
        # feeding bounded ring timeseries; queue depth reads from THIS
        # state (the most recent ServerState in a multi-state process).
        # DTPU_RESOURCE=0 disables; None then.
        self.resources = resource_mod.install_monitor(
            queue_depth_fn=self.queue_remaining)
        self.overlap_enabled = _env_flag(C.OVERLAP_ENV) \
            if overlap is None else bool(overlap)
        self.coalesce_enabled = _env_flag(C.COALESCE_ENV) \
            if coalesce is None else bool(coalesce)
        self.coalesce_max = max(1, int(os.environ.get(
            C.COALESCE_MAX_ENV, C.COALESCE_MAX_DEFAULT)))
        # iteration-level continuous batching (ISSUE 12): DTPU_CB=1
        # replaces the pop-a-group exec loop with the step-granular
        # batch executor (workflow/batch_executor.py) — eligible prompts
        # join a RUNNING padded batch at step boundaries; everything
        # else rides its fallback thread through _execute_group.  Off
        # by default: the legacy dispatch model is untouched without
        # the flag.
        self.cb_enabled = _env_flag(C.CB_ENV, "0") \
            if cb is None else bool(cb)
        self.cb: Optional[Any] = None
        self.host_pool = net_mod.HostIOPool() if self.overlap_enabled \
            else None
        self._queue: List[Dict[str, Any]] = []
        # every admitted-but-not-finalized prompt id (queued, in a CB
        # slot, mid-decode, or in a fallback group): the preview
        # route's authoritative liveness check — the queue/CB-slot
        # views individually have handoff windows where a live prompt
        # is in neither
        self._inflight: set = set()        # guarded-by: self._queue_lock
        self._queue_lock = threading.Lock()
        self._queue_event = threading.Event()
        # bench/test hook: the exec loop waits here before popping, so a
        # caller can clear it, stage a burst that must coalesce into ONE
        # group, and set it again — no race against the pop
        self._exec_gate = threading.Event()
        self._exec_gate.set()
        # what a LanguageModelGenerate node sees of this queue, and the
        # rows it ran for requests still in it (server/lm_handover.py)
        self.lm_handover = GenerateHandover(self)
        # the head ids of the dispatched groups whose image the device
        # still owes: in when the group's last enqueue has returned, out
        # at its device_ready or however else it ends.  A generate node
        # with room in its row set waits here for the set to empty
        self._owed: set = set()            # guarded-by: self._queue_lock
        self._drained = threading.Condition(self._queue_lock)
        # when each hand-over to another thread was last announced
        # (perf_counter_ns; trace.woke on the waiter's side names the wait)
        self._drained_ns = 0               # guarded-by: self._queue_lock
        self._queued_ns = 0
        self._finalized_ns = 0
        trace_mod.install_gc_monitoring()
        self._running = False
        self._draining = False
        self._history: Dict[str, Any] = {}
        self._id_counter = itertools.count()
        # finalizer plumbing (overlap mode): (group, result, error, wall)
        # tuples, joined off the exec thread so compute never waits on
        # encode/disk.  FIFO -> history lands in execution order.
        self._finalize_q: "queue.Queue" = queue.Queue()
        self._finalize_pending = 0
        # the previous finalized request's device_ready instant (the
        # finaliser is FIFO, so "previous" is in execution order)
        self._last_device_ready = 0.0
        # multi-master shard plane (ISSUE 14): resolve the shard config
        # BEFORE the durability plane attaches — each shard keeps its
        # own WAL/epoch stream under DTPU_SHARD_WAL_ROOT/<id>, its
        # lease-owner identity is the shard id (crash-restart reclaims;
        # a PEER's absorb acquire is the fresh-owner epoch bump), and
        # the JobStore's idempotency keys are namespaced by shard so a
        # takeover can never alias another master's acked units
        self._shard_cfg = None if is_worker else shard_mod.shard_config()
        shard_wal_dir = None
        shard_owner = None
        if self._shard_cfg is not None:
            self.jobs.set_scope(self._shard_cfg["id"])
            shard_owner = self._shard_cfg["id"]
            if self._shard_cfg.get("wal_root"):
                shard_wal_dir = os.path.join(
                    self._shard_cfg["wal_root"], self._shard_cfg["id"])
        # durability plane (ISSUE 7): with DTPU_WAL_DIR set, a master
        # acquires (or, under DTPU_STANDBY=1, watches) the file lease,
        # replays the write-ahead job log, and preloads the recovered
        # ledger/idempotency state BEFORE the exec thread can pop
        # anything.  The interrupted prompts themselves are re-enqueued
        # by resume_recovered() once the server loop is up.
        from comfyui_distributed_tpu.runtime import durable as durable_mod
        try:
            self.durable = durable_mod.DurableMaster.attach(
                self, dirpath=shard_wal_dir, owner=shard_owner)
        except durable_mod.WalError as e:
            # a held lease (second active master) must fail LOUDLY, not
            # boot a split-brain — but a standby construction never hits
            # this (it only watches)
            raise RuntimeError(f"durable master startup refused: {e}")
        # the ShardManager itself (ring + gossip + peer-lease watch)
        # attaches after the durability plane so a takeover can merge
        # an absorbed shard's recovered state into live planes; the
        # per-client admission rate splits by the member count (one
        # client's traffic spreads over the shards by prompt-id hash)
        self.shard = shard_mod.ShardManager.attach(
            self, cfg=self._shard_cfg, start_threads=start_exec_thread)
        if self.shard is not None:
            self.admission.set_rate_scale(1.0 / self.shard.n_members())
        self._exec_started = bool(start_exec_thread)
        if start_exec_thread:
            if self.cb_enabled:
                from comfyui_distributed_tpu.workflow import \
                    batch_executor as cb_mod
                self.cb = cb_mod.ContinuousBatchExecutor(self)
                self.cb.start()
            else:
                t = threading.Thread(target=self._exec_loop, daemon=True,
                                     name="dtpu-exec")
                t.start()
            if self.overlap_enabled:
                f = threading.Thread(target=self._finalize_loop,
                                     daemon=True, name="dtpu-finalize")
                f.start()

    def _drop_tile_queues(self, prompt: Dict[str, Any]) -> None:
        """Remove master-mode tile queues for a finished prompt.  They're
        pre-created at /prompt time (before the exec thread runs), so a
        prompt that fails before its upscale node would otherwise leave an
        orphan queue accepting tiles forever — the leak put_tile's
        require_existing guard exists to prevent.  The upscale node's own
        drain also removes the queue; this is the failure-path backstop."""
        if self.loop is None:
            return
        for node in prompt.values():
            if not isinstance(node, dict) \
                    or node.get("class_type") != "UltimateSDUpscaleDistributed":
                continue
            h = {**node.get("inputs", {}), **node.get("hidden", {})}
            mj = h.get("multi_job_id")
            if mj and not h.get("is_worker"):
                try:
                    asyncio.run_coroutine_threadsafe(
                        self.jobs.remove_tile_queue(str(mj)),
                        self.loop).result(timeout=5)
                except Exception as e:  # noqa: BLE001 - cleanup best-effort
                    debug_log(f"tile queue cleanup {mj}: {e}")

    # --- execution queue (ComfyUI /prompt semantics) -----------------------

    def queue_remaining(self) -> int:
        with self._queue_lock:
            n = len(self._queue) + (1 if self._running else 0)
        if self.cb is not None:
            # continuous batching: in-flight slots + decoding tails are
            # queued-or-executing work exactly like the legacy in-flight
            # group (backpressure, autoscale signal, Retry-After hints)
            n += self.cb.active_prompts()
        return n

    def queued_by_class(self) -> Dict[str, int]:
        """Queued (not yet running) prompts per tenant class — the
        admission block's live gauge on both metrics surfaces."""
        out = {cls: 0 for cls in self.admission.classes}
        with self._queue_lock:
            for item in self._queue:
                cls = item.get("tenant") or self.admission.default_class
                out[cls] = out.get(cls, 0) + 1
        return out

    def enqueue_prompt(self, prompt: Dict[str, Any], client_id: str,
                       extra_data: Optional[Dict[str, Any]] = None,
                       trace_parent: Optional[tuple] = None,
                       trace_span: Any = None,
                       pid: Optional[str] = None,
                       tenant: Optional[str] = None,
                       span_attrs: Optional[Dict[str, Any]] = None,
                       _recovered: bool = False,
                       _preadmitted: bool = False,
                       _absorbed: bool = False) -> str:
        """Queue one prompt.  Every job gets a request-scoped trace: a
        ``job`` root span that lives from enqueue to finalize and lands
        in the flight recorder under the prompt id.  ``trace_parent`` is
        an inbound (trace_id, parent_span_id) extracted from a peer's
        traceparent header (this process becomes a child of the caller's
        trace — the dispatched-worker case); ``trace_span`` hands in an
        already-open span to adopt as the job span (the master's fan-out
        root, so its dispatch/collect children and the local execution
        share one tree)."""
        # `pid` override = crash recovery re-enqueueing an interrupted
        # prompt under its ORIGINAL id (clients polling /history find it
        # on the stand-in master), or a router/client-supplied hash hint.
        # A sharded master GENERATES ids its own shard owns, so a direct
        # (hint-less) submission never needs the forward hop.
        if pid is None:
            pid = self.shard.local_pid(self._id_counter) \
                if self.shard is not None \
                else f"p_{int(time.time() * 1000)}_{next(self._id_counter)}"
        # an extra_data-carried priority survives paths that don't pass
        # tenant explicitly (crash-recovery re-enqueues replay extra_data
        # from the WAL; direct embedded callers)
        tenant = self.admission.classify(
            tenant or (extra_data or {}).get("priority"))
        sp = trace_span
        if sp is None:
            tid, par = trace_parent if trace_parent else (None, None)
            sp = trace_mod.start_span(
                "job", trace_id=tid, parent_id=par,
                attrs={"prompt_id": pid, "client_id": str(client_id),
                       "tenant": tenant,
                       "role": "worker" if self.is_worker else "master"})
        else:
            sp.attrs.setdefault("prompt_id", pid)
            sp.attrs.setdefault("tenant", tenant)
        if sp is not None:
            if self.shard is not None:
                sp.attrs["shard"] = self.shard.id
                sp.attrs["ring_epoch"] = self.shard.ring_epoch()
            for k, v in (span_attrs or {}).items():
                sp.attrs[k] = v
        # signature hashed OUTSIDE the lock (it walks the whole graph):
        # _pop_group then only compares strings under the lock.  The
        # continuous-batching flag rides along the same way: a cheap
        # whole-graph screen now, so the step executor's pop decisions
        # are string/int compares under the lock.
        sig = sched_mod.coalesce_signature(prompt) \
            if (self.coalesce_enabled or self.cb_enabled) else None
        cb_ok = False
        if self.cb_enabled and sig is not None:
            from comfyui_distributed_tpu.workflow import \
                batch_executor as cb_mod
            cb_ok = cb_mod.quick_eligible(prompt)
        # exact-hit result cache (ISSUE 13 tier a): a byte-identical
        # re-submission (same signature AND same full widget values,
        # seed included) replays the stored outputs without ever
        # touching the queue — history/metrics/span stamped cache_hit.
        # DTPU_CACHE=0 skips the key computation entirely; recovery
        # re-enqueues always re-execute (their first run may not have
        # finished storing).
        rkey = None
        if not self.is_worker and not _recovered \
                and reuse_mod.reuse_enabled():
            rkey = reuse_mod.result_key(prompt, input_dir=self.input_dir,
                                        models_dir=self.models_dir,
                                        scope=self.shard_cache_scope())
            if rkey is not None:
                entry = reuse_mod.get_reuse().result.get(rkey)
                if entry is not None:
                    self._replay_cached(pid, sp, entry)
                    return pid
        # rejection decided under the lock, but the span seal/commit
        # (FlightRecorder lock) and the raise happen OUTSIDE it: the
        # queue lock is the hottest lock in the process and must never
        # be held across a foreign subsystem's lock — the dtpu-lint
        # deadlock-cycle rule tracks exactly these ordering edges
        reject: Optional[tuple] = None
        with self._queue_lock:
            if self._draining:
                reject = (DrainingError("server is draining; not "
                                        "accepting prompts"),
                          "rejected: draining")
            elif not _recovered and not _preadmitted:
                # class-aware admission (token bucket + shed
                # thresholds); recovery re-enqueues and pre-admitted
                # fan-out shares skip it — their admission already
                # happened (and was WAL'd).  The admission lock is a
                # leaf: AdmissionController never calls back out.
                rejection = self.admission.admit(
                    tenant, str(client_id), len(self._queue),
                    self.max_queue)
                if rejection is not None:
                    reject = (ShedError(rejection),
                              f"rejected: shed "
                              f"({rejection['reason']}, {tenant})")
            if reject is None \
                    and len(self._queue) >= self.max_queue:
                reject = (QueueFullError(
                    f"prompt queue full ({self.max_queue})"),
                    "rejected: queue full")
            if reject is None:
                self._queue.append({"id": pid, "prompt": prompt,
                                    "client_id": client_id,
                                    "extra_data": extra_data or {},
                                    "sig": sig,
                                    "cb": cb_ok,
                                    "rkey": rkey,
                                    "tenant": tenant,
                                    "span": sp,
                                    "t_enq": time.perf_counter()})
                self._inflight.add(pid)
                trace_mod.mark_instant("enqueued", sp)
        if reject is not None:
            self._abandon_span(sp, pid, reject[1])
            raise reject[0]
        # write-ahead: the admission record is durable BEFORE the
        # prompt_id reaches the client (a crash after the append but
        # before the response re-runs the prompt — at-least-once at the
        # prompt level, exactly-once per unit through the ledger).
        # Recovery re-enqueues suppress the append (their record — the
        # original admission — is already in the log) EXCEPT absorbed
        # shards' prompts: their record lives in the DEAD shard's now-
        # dormant log, so ownership transfers by re-logging them here.
        if self.durable is not None and (not _recovered or _absorbed):
            self.durable.log_enqueue(pid, prompt, client_id, extra_data)
        self._queued_ns = trace_mod.now_ns()
        self._queue_event.set()
        return pid

    def shard_cache_scope(self) -> Optional[str]:
        """The shard-owner-epoch token salting the exact-hit result
        cache (ISSUE 14 satellite): shard id + this shard's current WAL
        epoch, so cross-shard entries never alias and a deposed epoch's
        entries go cold after a takeover.  None (key unchanged) when
        sharding is off."""
        if self.shard is None:
            return None
        epoch = self.durable.epoch if self.durable is not None else 0
        return f"{self.shard.id}:e{epoch}"

    def _replay_cached(self, pid: str, sp,
                       entry: Dict[str, Any]) -> None:
        """Exact-hit replay: settle the prompt NOW from the stored
        outputs.  The history entry and the committed job span look
        like a normal success, distinguished by ``cache_hit`` — a
        client polling /history cannot tell the difference except by
        latency.  Counted ONLY as ``prompts_replayed``: nothing
        executed (prompts_executed stays honest), nothing was admitted
        (the per-class completed counter would break
        admitted >= completed), and no queue slot freed (the
        drain-rate ring feeds the Retry-After estimate)."""
        done_t = time.time()
        self.metrics["prompts_replayed"] += 1
        trace_mod.GLOBAL_COUNTERS.bump("cache_result_replays")
        self._history[pid] = {
            "status": "success",
            "images": len(entry.get("images", ())),
            "duration_s": 0.0,
            "cache_hit": True,
            "finished_at": done_t,
        }
        if sp is not None:
            sp.attrs["cache_hit"] = True
            sp.attrs["cache_tier"] = "result"
            sp.end()
            trace_mod.GLOBAL_TRACES.commit(
                pid, sp.trace_id, status="ok", root_span_id=sp.span_id,
                duration_s=round(done_t - sp.start_s, 6))

    def _purge_abandoned(self) -> int:
        """Client-gone cancellation for prompts still IN the queue: the
        exec/CB driver calls this before popping, so an abandoned job
        never starts executing.  Each purged prompt finalizes as
        ``abandoned`` through the normal finalize path (history, WAL
        record, sealed span)."""
        bus = reuse_mod.PREVIEWS
        with self._queue_lock:
            if not self._queue:
                return 0
            doomed = [it for it in self._queue
                      if bus.is_abandoned(it["id"])]
            if not doomed:
                return 0
            gone = {id(it) for it in doomed}
            self._queue = [it for it in self._queue
                           if id(it) not in gone]
        err = reuse_mod.AbandonedError(
            "client disconnected before execution")
        for item in doomed:
            self._finalize_hand([item], None, err, time.perf_counter())
        return len(doomed)

    @staticmethod
    def _abandon_span(sp, pid: str, reason: str) -> None:
        """End + commit a job span for a prompt that never executes
        (backpressure/drain rejections and purges still leave a
        postmortem trace)."""
        if sp is None:
            return
        sp.set_status("error", reason)
        sp.end()
        trace_mod.GLOBAL_TRACES.commit(
            pid, sp.trace_id, status="error", root_span_id=sp.span_id,
            duration_s=round(time.time() - sp.start_s, 6))

    def _pop_group(self) -> Optional[List[Dict[str, Any]]]:
        """Pop the next dispatch group under weighted fair scheduling
        (workflow/scheduler.pop_fair_group): the scheduled class's
        head prompt plus that class's next signature-identical prompts
        (capped at DTPU_MAX_COALESCE).  Per-class FIFO order is
        preserved by construction — no prompt ever executes before one
        of ITS OWN class queued ahead of it — and with a single class
        queued (the default: untagged traffic) this is exactly the
        legacy head-of-queue contiguous-run pop."""
        with self._queue_lock:
            if not self._queue:
                self._queue_event.clear()
                return None
            group = sched_mod.pop_fair_group(
                self._queue, self.admission,
                coalesce_max=self.coalesce_max
                if self.coalesce_enabled else 1)
            self._running = True
        now = time.perf_counter()
        now_wall = time.time()
        for item in group:
            wait = now - item.get("t_enq", now)
            trace_mod.record_stage("queue_wait", now_wall - wait, now_wall,
                                   parent=item.get("span"))
            trace_mod.mark_instant("popped", item.get("span"), now_wall)
        return group

    def _exec_loop(self) -> None:
        trace_mod.thread_role(trace_mod.EXECUTOR)
        while True:
            with trace_mod.stage("exec_idle"):
                began_ns = trace_mod.now_ns()
                self._queue_event.wait()
                self._exec_gate.wait()
                trace_mod.woke("queue", self._queued_ns, began_ns)
            self._purge_abandoned()
            group = self._pop_group()
            if group is None:
                continue
            self._execute_group(group)

    def _execute_group(self, group: List[Dict[str, Any]]) -> None:
        """Run one popped dispatch group end to end (the legacy
        whole-graph model): coalesced build, executor run, finalize
        hand-off.  Shared by the classic exec loop and the continuous-
        batching executor's fallback thread — non-step-batchable
        prompts keep every PR 2/9 behavior bit for bit."""
        from comfyui_distributed_tpu.parallel.mesh import get_runtime
        self.interrupt_event.clear()
        t0 = time.perf_counter()
        res, err = None, None
        try:
            ctx = OpContext(
                runtime=get_runtime(),
                models_dir=self.models_dir,
                input_dir=self.input_dir,
                output_dir=self.output_dir,
                is_worker=self.is_worker,
                job_store=self.jobs,
                server_loop=self.loop,
                interrupt_event=self.interrupt_event,
                host_pool=self.host_pool,
                cluster=self.cluster,
                ledger=self.ledger,
                fault_inject=self.fault_inject,
                lm_handover=self.lm_handover,
                device_ready=functools.partial(self._image_settled,
                                               group[0]),
            )
            first = group[0]
            trace_mod.GLOBAL_COUNTERS.bump("exec_runs")
            # the run executes under the HEAD prompt's job span
            # (coalesced followers' traces stay thin — job +
            # queue_wait — and name their leader); per-node and
            # stage spans created inside attach to this trace
            # ``dispatch``: pop to the return of the last enqueue, the
            # host's own seconds (what the thread spends in
            # trace.device_wait inside is taken out of the aggregate)
            with trace_mod.use_span(first.get("span")), \
                    trace_mod.stage("dispatch", own=True), \
                    trace_mod.span("execute",
                                   coalesced=len(group)):
                if len(group) > 1:
                    graph, hidden = sched_mod.build_coalesced(
                        [it["prompt"] for it in group])
                    ctx.coalesce = len(group)
                    trace_mod.GLOBAL_COUNTERS.bump("coalesced_batches")
                    trace_mod.GLOBAL_COUNTERS.bump("coalesced_prompts",
                                                   len(group))
                    debug_log(f"coalesced {len(group)} prompts into "
                              f"one dispatch ({first['id']}..)")
                    for item in group[1:]:
                        if item.get("span") is not None:
                            item["span"].attrs["coalesced_into"] = \
                                first["id"]
                    with trace_mod.stage("coalesced_batch"):
                        res = WorkflowExecutor(ctx).execute(
                            graph, hidden=hidden,
                            extra_pnginfo=first.get(
                                "extra_data", {}).get("extra_pnginfo"))
                else:
                    res = WorkflowExecutor(ctx).execute(
                        first["prompt"],
                        extra_pnginfo=first.get("extra_data", {}).get(
                            "extra_pnginfo"))
            trace_mod.GLOBAL_STAGES.record("compute", res.total_s)
            for item in group:
                trace_mod.mark_instant("dispatched", item.get("span"))
        except Exception as e:  # noqa: BLE001 - survive bad prompts
            err = e
        finally:
            with self._queue_lock:
                self._running = False
                self._finalize_pending += 1
                if res is not None and res.image_futures \
                        and not group[0].get("settled"):
                    self._owed.add(group[0]["id"])
        if self.overlap_enabled:
            # hand host-side joining to the finalizer so the next
            # group's compute starts NOW — this is the overlap
            self._finalized_ns = trace_mod.now_ns()
            self._finalize_q.put((group, res, err, t0))
        else:
            self._finalize_group(group, res, err, t0)

    def _finalize_hand(self, group, res, err, t0) -> None:
        """Finalize entry point for the continuous-batching executor
        (tail decodes, slot aborts): books the pending finalize and
        routes through the same overlap/inline split as
        _execute_group."""
        with self._queue_lock:
            self._finalize_pending += 1
        if self.overlap_enabled:
            self._finalized_ns = trace_mod.now_ns()
            self._finalize_q.put((group, res, err, t0))
        else:
            self._finalize_group(group, res, err, t0)

    def _finalize_loop(self) -> None:
        trace_mod.thread_role(trace_mod.FINALIZER)
        while True:
            began_ns = trace_mod.now_ns()
            group, res, err, t0 = self._finalize_q.get()
            trace_mod.woke("finalize", self._finalized_ns, began_ns)
            self._finalize_group(group, res, err, t0)

    def _image_settled(self, head: Dict[str, Any]) -> None:
        """The device owes the group of ``head`` no image any longer: its
        deferred host edge has met the device (``OpContext.device_ready``,
        on the pool's thread), or the group has ended some other way
        (`_finalize_group`).  The first call counts; it may come before
        the group is entered, when the image was out before the graph's
        last node returned."""
        with self._queue_lock:
            head["settled"] = True
            self._owed.discard(head["id"])
            self._drained_ns = trace_mod.now_ns()
            self._drained.notify_all()

    def _record_queue_to_device(self, group, ready_fallback: float) -> None:
        """``queue_to_device``: enqueue to the instant the request's own
        denoise could start — the later of its dispatch and the previous
        request's ``device_ready`` on this executor.  The queue delay
        measured where it happens: the executor pops a request as it
        arrives, so the wait lies behind the device, not in the queue."""
        head = group[0].get("span")
        if head is None:
            return
        ready = head.attrs.get("instants", {}).get("device_ready",
                                                   ready_fallback)
        prev, self._last_device_ready = self._last_device_ready, ready
        for item in group:
            sp = item.get("span")
            inst = sp.attrs.get("instants", {}) if sp is not None else {}
            if "enqueued" in inst and "dispatched" in inst:
                start = max(inst["dispatched"], prev)
                trace_mod.record_stage("queue_to_device", inst["enqueued"],
                                       start, parent=sp)

    def _write_history(self, group, res, err, done_t: float) -> None:
        """The history entries of a finalized group (and what rides with
        them: counters, the exact-hit result tier)."""
        k = len(group)
        abandoned = isinstance(err, reuse_mod.AbandonedError)
        if err is None:
            per_prompt = sched_mod.split_images(res.images, k)
            # metrics BEFORE history: clients poll history for
            # completion, then read metrics — the other order would give
            # them a window where the prompt is "done" but uncounted
            self.metrics["prompts_executed"] += k
            self.metrics["last_execution_s"] = res.total_s
            reuse_on = reuse_mod.reuse_enabled()
            for item, imgs in zip(group, per_prompt):
                entry = {"status": "success", "images": len(imgs),
                         # what a client without disk access can check
                         # about a PreviewImage output (chip_smoke.py)
                         "image_shapes": [list(im.shape) for im in imgs],
                         "duration_s": res.total_s,
                         "finished_at": done_t}
                if k > 1:
                    entry["coalesced"] = k
                self._history[item["id"]] = entry
                # exact-hit result tier: store the per-prompt outputs
                # so a byte-identical re-submission replays instead of
                # recomputing (LRU-bounded by DTPU_CACHE_BYTES)
                if reuse_on and item.get("rkey") and imgs:
                    reuse_mod.store_result(item["rkey"], imgs,
                                           res.total_s)
        elif abandoned:
            # client-gone cancellation: settled, not failed — the WAL
            # completion record below closes the admission record so a
            # crash-recovery never resurrects an abandoned job
            log(f"prompt group {group[0]['id']} (x{k}) abandoned: {err}")
            self.metrics["prompts_abandoned"] += k
            for item in group:
                entry = {"status": "abandoned", "error": str(err),
                         "finished_at": done_t}
                if k > 1:
                    entry["coalesced"] = k
                self._history[item["id"]] = entry
        else:
            log(f"prompt group {group[0]['id']} (x{k}) failed: "
                f"{type(err).__name__}: {err}")
            self.metrics["prompts_failed"] += k
            for item in group:
                entry = {"status": "error", "error": str(err),
                         "finished_at": done_t}
                if k > 1:
                    entry["coalesced"] = k
                self._history[item["id"]] = entry

    def _finalize_group(self, group, res, err, t0) -> None:
        """Join deferred host edges, split per-prompt results, write
        history/metrics, drop orphan tile queues, seal the group's job
        traces into the flight recorder (+ the slow-job log line)."""
        # every way a prompt ends passes here (run, failed, purged as
        # abandoned, a CB slot): what a generate node kept for it and it
        # never asked for goes now
        for item in group:
            self.lm_handover.drop(item["id"])
        if res is not None and err is None:
            try:
                # the join runs under the head job's span so the
                # host-edge wait is visible in the trace tree
                with trace_mod.use_span(group[0].get("span")), \
                        trace_mod.span("finalize"):
                    res.wait_host()
            except Exception as e:  # noqa: BLE001 - host edge failed
                err = e
        # a group whose host edge raised, or that had no image to wait
        # for, is owed nothing either
        self._image_settled(group[0])
        k = len(group)
        done_t = time.time()
        abandoned = isinstance(err, reuse_mod.AbandonedError)
        self._record_queue_to_device(group, done_t)
        with trace_mod.use_span(group[0].get("span")), \
                trace_mod.stage("history_write"):
            self._write_history(group, res, err, done_t)
        for item in group:
            trace_mod.mark_instant("in_history", item.get("span"))
        # seal each prompt's trace: end the job span, commit to the
        # flight recorder under the prompt id, and emit the always-on
        # slow-job line when the end-to-end span exceeds DTPU_SLOW_JOB_S
        status = "ok" if err is None \
            else ("abandoned" if abandoned else "error")
        if self.durable is not None:
            # the completion record closes the admission record: a
            # crash BEFORE this point re-runs the prompt on recovery
            # (deterministic seeds make the redo bit-identical), after
            # it the prompt is settled history
            for item in group:
                self.durable.log_exec_done(item["id"], status)
        for item in group:
            self._drop_tile_queues(item["prompt"])
        slow_thr = 0.0
        try:
            slow_thr = float(os.environ.get(C.SLOW_JOB_ENV, "0") or 0)
        except ValueError:
            pass
        # peak device memory + RSS ride the slow-job line and error
        # traces (satellite: an OOM-adjacent slow job is diagnosed from
        # the log line alone).  Executor-attributed numbers when the run
        # survived; a fresh process probe when it died before reporting.
        # Resolved lazily: with tracing off (no spans) nothing below
        # reads it, and the probe shouldn't tax every finalize.
        _job_res_cache: List[Dict[str, Any]] = []

        def _job_res() -> Dict[str, Any]:
            if _job_res_cache:
                return _job_res_cache[0]
            jr = res.resources if (res is not None
                                   and getattr(res, "resources", None)) \
                else None
            if jr is None:
                mem = resource_mod.device_memory_snapshot()
                jr = {"device_peak_bytes": mem["peak_bytes_in_use"],
                      "host_rss_bytes": resource_mod.host_rss_bytes(),
                      "source": mem["source"]}
            _job_res_cache.append(jr)
            return jr

        def _mem_note() -> str:
            jr = _job_res()
            return (f"mem device_peak="
                    f"{jr['device_peak_bytes'] / 1e6:.1f}MB "
                    f"rss={jr['host_rss_bytes'] / 1e6:.1f}MB "
                    f"({jr['source']})")
        # SLO burn-rate feed (ISSUE 18): EVERY finalized prompt lands in
        # its class's fast/slow windows — span-less ones too (tracing
        # off must not blind the engine).  Abandoned counts as bad: the
        # client saw no completion.
        ok = err is None
        if ok and res is not None:
            fallback_dur = float(res.total_s)
        else:
            fallback_dur = max(time.perf_counter() - t0, 0.0)
        for item in group:
            sp = item.get("span")
            dur_slo = round(done_t - sp.start_s, 6) if sp is not None \
                else fallback_dur
            tenant = str(item.get("tenant")
                         or self.admission.default_class)
            self.slo.record(tenant, dur_slo, ok)
            if sp is not None:
                # trace <-> SLO cross-links: the class on the root span,
                # and an slo_breach event when the job blew its class's
                # latency objective (the spec-driven cousin of the
                # DTPU_SLOW_JOB_S log line)
                sp.attrs.setdefault("tenant", tenant)
                thr = self.slo.latency_threshold(tenant)
                if thr is not None and dur_slo > thr:
                    trace_mod.event_span(
                        "slo_breach", done_t, done_t, parent=sp,
                        attrs={"tenant": tenant, "threshold_s": thr})
        for item in group:
            sp = item.get("span")
            if sp is None:
                continue
            if err is not None:
                sp.set_status(status, str(err))
                # the job never set its execute-span mem attrs (the
                # exception aborted the executor) — stamp the root so
                # the error trace still answers "how much memory"
                sp.attrs.setdefault(
                    "device_peak_mb",
                    round(_job_res()["device_peak_bytes"] / 1e6, 2))
                sp.attrs.setdefault(
                    "rss_mb",
                    round(_job_res()["host_rss_bytes"] / 1e6, 2))
            dur = round(done_t - sp.start_s, 6)
            sp.end()
            # end-to-end latency histogram WITH an exemplar: the bucket
            # this job landed in now points at its trace, so a slow
            # .prom bucket resolves to a flight-recorder/capture entry
            trace_mod.GLOBAL_STAGES.record("job_e2e", dur,
                                           trace_id=sp.trace_id)
            trace_mod.GLOBAL_TRACES.commit(
                item["id"], sp.trace_id, status=status,
                root_span_id=sp.span_id, duration_s=dur)
            if slow_thr > 0 and dur > slow_thr:
                stages = trace_mod.GLOBAL_TRACES.breakdown(sp.trace_id)
                stages.pop("job", None)
                top = sorted(stages.items(), key=lambda kv: -kv[1])[:8]
                log(f"SLOW job {item['id']} ({status}): {dur:.2f}s > "
                    f"{slow_thr:g}s threshold; trace {sp.trace_id}; "
                    f"{_mem_note()}; stages "
                    + ", ".join(f"{n}={s:.2f}s" for n, s in top))
        # drain-rate ring + per-class completion counters: each
        # finalized prompt frees a queue slot, which is what the 429
        # Retry-After hint estimates from
        self._completions.append((time.monotonic(), k))
        if err is None:
            for item in group:
                self.admission.on_complete(
                    item.get("tenant") or self.admission.default_class)
        # preview channel: terminal SSE event for any attached client,
        # and the abandonment flag (if set) is consumed — the job is
        # settled either way
        for item in group:
            reuse_mod.PREVIEWS.finish(item["id"], status)
        with self._queue_lock:
            self._finalize_pending -= 1
            for item in group:
                self._inflight.discard(item["id"])
        debug_log(f"group {group[0]['id']} (x{k}) done in "
                  f"{time.perf_counter() - t0:.2f}s")

    # --- backpressure hints --------------------------------------------------

    def drain_rate(self, window_s: float = 30.0) -> float:
        """Prompts finalized per second over the recent window (0.0
        until anything completed) — the denominator of the Retry-After
        hint."""
        now = time.monotonic()
        n = sum(k for t, k in self._completions if now - t <= window_s)
        if n <= 0:
            return 0.0
        oldest = min(t for t, _ in self._completions
                     if now - t <= window_s)
        return n / max(now - oldest, 0.5)

    def retry_after_hint(self, floor_s: float = 1.0) -> int:
        """Whole seconds a shed client should wait before retrying,
        derived from the current backlog and the measured drain rate:
        roughly "when will a quarter of the queue have drained".
        Conservative bounds [1, 30] — the point is de-synchronizing the
        retry storm, not a precise reservation."""
        depth = self.queue_remaining()
        rate = self.drain_rate()
        if rate <= 0:
            hint = 5.0          # nothing measured yet: a polite default
        else:
            hint = max(depth, 1) / (4.0 * rate)
        return int(min(max(math.ceil(max(hint, floor_s)), 1), 30))

    # --- crash recovery (durability plane) ----------------------------------

    def resume_recovered(self) -> int:
        """Re-enqueue the prompts a crash interrupted (replayed from the
        WAL at construction).  Called from on_startup — by then the
        server loop exists, so the resumed upscale jobs' tile queues and
        collector drains work; idempotent."""
        if self.durable is None:
            return 0
        return self.durable.resume()

    # --- graceful drain -----------------------------------------------------

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown: stop accepting prompts, let the queue, the
        in-flight group and the host-IO pool finish (bounded by
        DTPU_DRAIN_TIMEOUT_S), then cancel what remains.  Returns True
        when everything drained inside the bound."""
        if timeout is None:
            timeout = float(os.environ.get(C.DRAIN_TIMEOUT_ENV,
                                           C.DRAIN_TIMEOUT_DEFAULT))
        if self.autoscaler is not None:
            # a reconciliation firing mid-shutdown would spawn workers
            # into a dying fleet
            self.autoscaler.stop()
        if self.shard is not None:
            # stop gossip + the peer-lease watcher: a dying master must
            # not absorb a peer's shard on its way out
            self.shard.stop()
        with self._queue_lock:
            self._draining = True
        deadline = time.monotonic() + max(timeout, 0.0)
        while True:
            with self._queue_lock:
                # without an exec thread nothing will ever pop the queue
                # — only in-flight/host work is drainable
                idle = (not self._running and self._finalize_pending == 0
                        and (not self._queue or not self._exec_started))
            if idle and self.cb is not None:
                # continuous batching: in-flight slots / decoding tails /
                # fallback groups are in-flight work like the legacy
                # running group
                idle = self.cb.idle()
            if idle and (self.host_pool is None
                         or self.host_pool.pending == 0):
                if self.cb is not None:
                    # drained for shutdown: stop the step driver so a
                    # dead ServerState's threads don't keep polling the
                    # process-global interrupt/queue state (loopback
                    # tests and benches run many states per process)
                    self.cb.stop()
                return True
            if time.monotonic() >= deadline:
                break
            time.sleep(0.05)
        # bound exceeded: purge the not-yet-started queue FIRST (or the
        # exec loop would keep popping groups — and clearing the
        # interrupt flag — right through the shutdown), then cancel the
        # in-flight work instead of dying mid-job silently (the compiled
        # samplers poll the flag per step)
        with self._queue_lock:
            purged, self._queue = self._queue, []
            for item in purged:
                self._inflight.discard(item["id"])
            # a generate node that waits for the device is let go: what
            # was dispatched is being cancelled
            self._owed.clear()
            self._drained_ns = trace_mod.now_ns()
            self._drained.notify_all()
        done_t = time.time()
        for item in purged:
            self.lm_handover.drop(item["id"])
            self._abandon_span(item.get("span"), item["id"],
                               "cancelled: server drain timeout")
            self._history[item["id"]] = {
                "status": "error",
                "error": "cancelled: server drain timeout",
                "finished_at": done_t}
        self.metrics["prompts_failed"] += len(purged)
        log(f"drain timeout after {timeout:.1f}s; cancelled "
            f"{len(purged)} queued prompt(s), interrupting in-flight work")
        self.interrupt_event.set()
        if self.cb is not None:
            # give the driver a beat to consume the interrupt (aborting
            # its slots), then stop its threads — a timed-out drain must
            # not leak a live driver polling process-global state any
            # more than a clean one does
            stop_by = time.monotonic() + 2.0
            while time.monotonic() < stop_by and not self.cb.idle():
                time.sleep(0.02)
            self.cb.stop()
        if self.host_pool is not None:
            self.host_pool.shutdown(wait=False)
        return False


def build_app(state: Optional[ServerState] = None) -> web.Application:
    state = state or ServerState()
    # chaos harness (ISSUE 9): with DTPU_CHAOS armed the middleware may
    # 503/delay a fraction of inbound data-plane requests; unarmed it is
    # one env-change check per request
    app = web.Application(client_max_size=512 * 1024 * 1024,
                          middlewares=[chaos_mod.middleware()])
    app["state"] = state

    async def on_startup(app):
        state.loop = asyncio.get_running_loop()
        trace_mod.thread_role(trace_mod.HTTP)
        # recovery resume off the event loop: it health-polls the
        # workers and may enqueue several prompts.  Needs state.port
        # (the recovery redispatch graphs embed this master's URL) —
        # serve() sets it before run_app; embedded/test servers with a
        # late-bound port call resume_recovered() themselves.
        if state.durable is not None and state.port is not None:
            await state.loop.run_in_executor(None, state.resume_recovered)

    async def on_cleanup(app):
        # graceful drain: refuse new prompts, let the in-flight group and
        # the encoder pool finish (bounded), THEN drop the HTTP client —
        # the exec thread used to be a daemon that died mid-job here
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, state.drain)
        if state.durable is not None:
            # the close fsyncs the WAL tail — off the loop like every
            # other durability edge
            await loop.run_in_executor(None, state.durable.close)
        await net_mod.cleanup_client_session()

    app.on_startup.append(on_startup)
    app.on_cleanup.append(on_cleanup)
    r = app.router

    def ok(payload: Any = None, **kw):
        body = {"status": "ok"}
        if payload is not None:
            body.update(payload)
        body.update(kw)
        return web.json_response(body)

    # --- config CRUD (reference distributed.py:49-364) ---------------------

    async def _mutate(mutator):
        """Config RMW off the event loop: the config lock is shared with the
        exec thread and auto-launch timer, and file IO under it must not
        stall the data plane (same reason PNG decode is offloaded)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, lambda: cfg_mod.mutate_config(mutator, state.config_path))

    async def get_config(request):
        loop = asyncio.get_running_loop()
        cfg = await loop.run_in_executor(
            None, lambda: cfg_mod.load_config(state.config_path))
        return web.json_response(cfg)

    async def update_worker(request):
        data = await request.json()
        if "id" not in data:
            return web.json_response({"error": "missing worker id"},
                                     status=400)
        result = {}
        await _mutate(lambda cfg: result.update(
            cfg_mod.upsert_worker(cfg, data)))
        return ok({"worker": result})

    async def delete_worker(request):
        data = await request.json()
        found = []
        await _mutate(lambda cfg: found.append(
            cfg_mod.delete_worker(cfg, str(data.get("id")))))
        if not found[0]:
            return web.json_response({"error": "worker not found"},
                                     status=404)
        return ok()

    async def update_setting(request):
        data = await request.json()
        if "key" not in data:
            return web.json_response({"error": "missing key"}, status=400)
        await _mutate(lambda cfg: cfg_mod.update_setting(
            cfg, data["key"], data.get("value")))
        return ok()

    async def update_master(request):
        data = await request.json()
        # only keys present in the request are touched — an explicit null
        # deletes a field, an absent key leaves it alone (partial update)
        fields = {k: data[k] for k in ("host", "port", "extra_args")
                  if k in data}
        await _mutate(lambda cfg: cfg_mod.update_master(cfg, **fields))
        return ok()

    # --- info / lifecycle ---------------------------------------------------

    async def network_info(request):
        return web.json_response(net_mod.network_info())

    async def status(request):
        from comfyui_distributed_tpu.parallel.mesh import get_runtime
        # first call may initialize the JAX backend (seconds on real TPU) —
        # keep it off the event loop so the data plane stays responsive
        loop = asyncio.get_running_loop()
        st = await loop.run_in_executor(None,
                                        lambda: get_runtime().status())
        st["jobs"] = state.jobs.snapshot()
        st["queue_remaining"] = state.queue_remaining()
        st["is_worker"] = state.is_worker
        return web.json_response(st)

    async def metrics(request):
        from comfyui_distributed_tpu.utils.trace import (
            GLOBAL_NODES, GLOBAL_PHASES, GLOBAL_TRACES,
            counters_snapshot, pipeline_snapshot, profile_summary,
            tracing_enabled)
        # wal stats list segment files and may contend with an
        # append's fsync/rotation under the WAL lock — off the loop
        dur_stats = {"enabled": False}
        if state.durable is not None:
            dur_stats = await asyncio.get_running_loop() \
                .run_in_executor(None, state.durable.stats)
        # the exporter's first stats() call may construct it (a dir
        # scan) — keep that filesystem touch off the event loop
        export_stats = await asyncio.get_running_loop() \
            .run_in_executor(None, trace_export_mod.stats)
        return web.json_response({**state.metrics,
                                  "phases": GLOBAL_PHASES.snapshot(),
                                  # per-node-type op latency histograms
                                  # (count/mean/p50/p95/p99)
                                  "nodes": GLOBAL_NODES.snapshot(),
                                  # request-tracing health (+ the
                                  # durable capture plane: exporter
                                  # counters, eviction visibility)
                                  "tracing": {
                                      "enabled": tracing_enabled(),
                                      "ring_size": GLOBAL_TRACES.size(),
                                      "ring_max":
                                          GLOBAL_TRACES.max_traces,
                                      "dropped_spans":
                                          GLOBAL_TRACES.dropped_spans,
                                      "evictions": GLOBAL_TRACES
                                          .eviction_count(),
                                      "export": export_stats,
                                  },
                                  # SLO burn-rate engine: per-tenant
                                  # objectives, fast/slow window stats,
                                  # burn rates + budget remaining
                                  "slo": state.slo.evaluate(),
                                  # per-job stage timeline (queue_wait /
                                  # coalesced_batch / compute / d2h /
                                  # encode / upload) + scheduler and wire
                                  # counters: the overlapped-pipeline
                                  # health signals
                                  "pipeline": {
                                      **pipeline_snapshot(),
                                      "overlap": state.overlap_enabled,
                                      "coalesce": state.coalesce_enabled,
                                      "max_queue": state.max_queue,
                                  },
                                  # iteration-level continuous batching:
                                  # slot occupancy, per-bucket admit/
                                  # retire/step/retrace counters, pad set
                                  "batching": (
                                      state.cb.snapshot()
                                      if state.cb is not None
                                      else {"enabled": False}),
                                  # cluster control plane: lease states,
                                  # ledger activity, recovery counters
                                  "cluster": {
                                      **state.cluster.snapshot(),
                                      "ledger": state.ledger.snapshot(),
                                      "policy":
                                          cluster_mod.fault_policy(),
                                      "hedge_armed":
                                          cluster_mod.hedge_armed(),
                                  },
                                  # durability plane: WAL size/sync-lag
                                  # gauges, lease holder + epoch
                                  "durability": dur_stats,
                                  # multi-master shard plane: ring
                                  # membership/epoch, owned shards,
                                  # absorbed takeovers, forward count
                                  "shard": (state.shard.snapshot()
                                            if state.shard is not None
                                            else {"enabled": False}),
                                  # multi-tenant admission: per-class
                                  # admitted/shed/completed counters,
                                  # weights, shed bars, drain rate
                                  "admission": {
                                      **state.admission.snapshot(),
                                      "queued_by_class":
                                          state.queued_by_class(),
                                      "drain_rate_per_s": round(
                                          state.drain_rate(), 4),
                                  },
                                  # elastic fleet: autoscaler decisions
                                  # ring + flap/scale counters
                                  "autoscale": (
                                      state.autoscaler.snapshot()
                                      if state.autoscaler is not None
                                      else {"enabled":
                                            autoscale_mod
                                            .autoscale_armed()}),
                                  # chaos harness: armed spec + injected
                                  # fault counters (all zero unarmed)
                                  "chaos": chaos_mod.get_chaos()
                                  .snapshot(),
                                  # critical-path analytics plane: live
                                  # anomaly counters vs the armed
                                  # baseline profile + per-worker clock
                                  # skew estimates (ISSUE 20)
                                  "analysis": {
                                      **analysis_mod.LIVE.snapshot(),
                                      "skew": state.cluster
                                          .skew_snapshot(),
                                  },
                                  # cross-request compute reuse: per-tier
                                  # hit/miss/eviction counters + byte
                                  # residency, and the preview channel's
                                  # client/abandonment gauges
                                  "reuse": {
                                      **reuse_mod.get_reuse().snapshot(),
                                      "previews":
                                          reuse_mod.PREVIEWS.snapshot(),
                                  },
                                  # resource telemetry: current gauges +
                                  # bounded ring-series stats (device
                                  # memory, RSS, utilization, queue)
                                  "resources": (
                                      state.resources.snapshot()
                                      if state.resources is not None
                                      else {"enabled": False}),
                                  # host<->device transfer bytes per node
                                  # + jit trace/XLA compile counts: the
                                  # tensor-plane health signals (steady
                                  # serving => retraces stop growing)
                                  **counters_snapshot(),
                                  # the program's own reduction of its
                                  # last device trace (profile/stop);
                                  # metrics/reset leaves it
                                  "profile": profile_summary()})

    _build_info_cache: List[Any] = []

    def _build_info_family():
        """``dtpu_build_info`` gauge: constant 1 with package/jax/backend
        labels so every scrape is attributable to a build (satellite:
        which code produced these numbers).  The labels are
        process-lifetime constants, so they're resolved once and cached
        — reading them must never re-hit disk metadata or initialize a
        backend on the scrape path."""
        if _build_info_cache:
            return _build_info_cache[0]
        import comfyui_distributed_tpu
        labels = {"version": comfyui_distributed_tpu.__version__}
        try:
            import importlib.metadata
            labels["version"] = importlib.metadata.version(
                "comfyui-distributed-tpu")
        except Exception:  # noqa: BLE001 - not installed as a dist
            pass
        resolved = True
        try:
            import jax
            labels["jax"] = jax.__version__
            labels["platform"] = jax.default_backend()
        except Exception:  # noqa: BLE001 - jax mid-init / unavailable
            labels.setdefault("jax", "unknown")
            labels.setdefault("platform", "unknown")
            resolved = False
        fam = ("dtpu_build_info", "gauge",
               "Build identity (constant 1; labels carry the info).",
               [(labels, 1)])
        if resolved:  # an "unknown" backend is transient — don't pin it
            _build_info_cache.append(fam)
        return fam

    async def metrics_prom(request):
        """Prometheus text exposition (``/distributed/metrics.prom``):
        the trace module's stage/phase/node histograms and counters plus
        this server's prompt/image counters, queue gauge, build-info
        gauge and current resource gauges — one scrapable endpoint per
        participant."""
        loop = asyncio.get_running_loop()
        # the first probe may initialize the JAX backend (seconds on a
        # real TPU with DTPU_RESOURCE=0, where no monitor thread already
        # did it) — keep that off the event loop so heartbeats and
        # prompts never stall behind a scrape
        build_info = await loop.run_in_executor(None, _build_info_family)
        self_sample = await loop.run_in_executor(None, _self_sample)
        extra = [
            build_info,
            ("dtpu_prompts_executed_total", "counter",
             "Prompts executed to success.",
             [({}, state.metrics["prompts_executed"])]),
            ("dtpu_prompts_failed_total", "counter",
             "Prompts that finished in error.",
             [({}, state.metrics["prompts_failed"])]),
            ("dtpu_images_received_total", "counter",
             "Worker images received on /distributed/job_complete.",
             [({}, state.metrics["images_received"])]),
            ("dtpu_tiles_received_total", "counter",
             "Worker tiles received on /distributed/tile_complete.",
             [({}, state.metrics["tiles_received"])]),
            ("dtpu_queue_remaining", "gauge",
             "Prompts queued or executing.",
             [({}, state.queue_remaining())]),
            ("dtpu_queue_capacity", "gauge",
             "DTPU_MAX_QUEUE backpressure cap.",
             [({}, state.max_queue)]),
        ]
        cl_workers = state.cluster.snapshot()["workers"].values()
        extra.append(
            ("dtpu_cluster_workers", "gauge",
             "Registered workers by lease state.",
             [({"state": st},
               sum(1 for w in cl_workers if w["state"] == st))
              for st in (cluster_mod.HEALTHY, cluster_mod.SUSPECT,
                         cluster_mod.DEAD, cluster_mod.UNKNOWN,
                         cluster_mod.RETIRING)]))
        # multi-tenant admission: per-class queue gauge + decision
        # counters (tenant label), so overload dashboards can draw the
        # shed-first ordering directly
        queued = state.queued_by_class()
        adm = state.admission.snapshot()["per_class"]
        extra.extend([
            ("dtpu_tenant_queued", "gauge",
             "Queued prompts by tenant class.",
             [({"tenant": cls}, n) for cls, n in sorted(queued.items())]),
            ("dtpu_tenant_admitted_total", "counter",
             "Prompts admitted by tenant class.",
             [({"tenant": cls}, v["admitted"])
              for cls, v in sorted(adm.items())]),
            ("dtpu_tenant_shed_total", "counter",
             "Prompts shed (429) by tenant class and reason.",
             [({"tenant": cls, "reason": reason},
               v[f"shed_{reason}"])
              for cls, v in sorted(adm.items())
              for reason in ("rate", "overload")]),
            ("dtpu_tenant_completed_total", "counter",
             "Prompts completed by tenant class.",
             [({"tenant": cls}, v["completed"])
              for cls, v in sorted(adm.items())]),
            ("dtpu_queue_drain_rate", "gauge",
             "Prompts finalized per second (recent window).",
             [({}, round(state.drain_rate(), 4))]),
        ])
        if state.cb is not None:
            # continuous batching: slot occupancy + admit/retire/step
            # counters and the per-bucket steady-state retrace counter
            # (the zero-retrace invariant, scrapeable per shape bucket)
            bsnap = state.cb.snapshot()
            extra.extend([
                ("dtpu_batch_slots", "gauge",
                 "Continuous-batching slots by state (all shape "
                 "buckets).",
                 [({"state": "active"}, bsnap["slots_active"]),
                  ({"state": "free"}, bsnap["slots_free"])]),
                ("dtpu_cb_admits_total", "counter",
                 "Prompts admitted into a running batch at a step "
                 "boundary.",
                 [({}, bsnap["admits"])]),
                ("dtpu_cb_retires_total", "counter",
                 "Slots retired (prompt finished its steps and moved "
                 "to decode).",
                 [({}, bsnap["retires"])]),
                ("dtpu_cb_steps_total", "counter",
                 "Batched denoise steps executed.",
                 [({}, bsnap["steps"])]),
                ("dtpu_cb_fallback_total", "counter",
                 "Prompts dispatched through the legacy fallback "
                 "executor.",
                 [({}, bsnap["fallbacks"])]),
                ("dtpu_cb_bucket_retraces_total", "counter",
                 "Retraces observed during bucket steps (want 0 in "
                 "steady state).",
                 [({"bucket": b["sig"]}, b["retraces"])
                  for b in bsnap["buckets"]]),
                # latent paging + SLO preemption (ISSUE 17)
                ("dtpu_cb_parked", "gauge",
                 "Continuous-batching rows parked to host (started "
                 "jobs waiting on slot residency).",
                 [({}, bsnap["parked"])]),
                ("dtpu_cb_parks_total", "counter",
                 "Slots parked to host at a step boundary.",
                 [({}, bsnap["parks"])]),
                ("dtpu_cb_resumes_total", "counter",
                 "Parked rows resumed into a slot.",
                 [({}, bsnap["resumes"])]),
                ("dtpu_cb_preemptions_total", "counter",
                 "Parks forced by a higher-class admit (SLO "
                 "preemption; subset of parks).",
                 [({}, bsnap["preemptions"])]),
            ])
        # cross-request reuse + preview channel (ISSUE 13): per-tier
        # cache counters and byte gauges, tile-skip and abandonment
        # counters — the acceptance's dtpu_cache_*/dtpu_preview_*
        # families on the scrapeable surface
        rs = reuse_mod.get_reuse().snapshot()
        pv = reuse_mod.PREVIEWS.snapshot()
        tiers = ("result", "embed", "tile")
        extra.extend([
            ("dtpu_cache_hits_total", "counter",
             "Reuse-cache hits by tier.",
             [({"tier": t}, rs[t]["hits"]) for t in tiers]),
            ("dtpu_cache_misses_total", "counter",
             "Reuse-cache misses by tier.",
             [({"tier": t}, rs[t]["misses"]) for t in tiers]),
            ("dtpu_cache_evictions_total", "counter",
             "Reuse-cache LRU evictions by tier.",
             [({"tier": t}, rs[t]["evictions"]) for t in tiers]),
            ("dtpu_cache_bytes", "gauge",
             "Bytes resident in the reuse cache by tier.",
             [({"tier": t}, rs[t]["bytes"]) for t in tiers]),
            ("dtpu_cache_replays_total", "counter",
             "Prompts settled by exact-hit replay.",
             [({}, state.metrics["prompts_replayed"])]),
            ("dtpu_cache_tiles_skipped_total", "counter",
             "Upscale tiles skipped via per-tile content hashes.",
             [({}, trace_mod.GLOBAL_COUNTERS.get("tiles_skipped"))]),
            ("dtpu_preview_clients", "gauge",
             "Attached SSE preview clients.",
             [({}, pv["clients"])]),
            ("dtpu_preview_events_total", "counter",
             "Progressive preview frames published.",
             [({}, trace_mod.GLOBAL_COUNTERS.get("preview_events"))]),
            ("dtpu_jobs_abandoned_total", "counter",
             "Jobs abandoned by client disconnect (queue purges + "
             "freed CB slots).",
             [({}, state.metrics["prompts_abandoned"])]),
        ])
        if state.shard is not None:
            # multi-master shard plane (ISSUE 14): ownership + ring
            # epoch gauges on the scrapeable surface, so a dashboard
            # can draw who owns which shard through a takeover
            ssnap = state.shard.snapshot()
            extra.extend([
                ("dtpu_shard_owner", "gauge",
                 "Shards owned by this master (1 per owned shard; an "
                 "absorbed peer's shard appears after takeover).",
                 [({"shard": s}, 1) for s in ssnap["owned"]]),
                ("dtpu_ring_epoch", "gauge",
                 "Consistent-hash ring membership epoch.",
                 [({}, ssnap["ring_epoch"])]),
                ("dtpu_shard_members", "gauge",
                 "Members in this master's ring view.",
                 [({}, len(ssnap["members"]))]),
                ("dtpu_shard_forwards_total", "counter",
                 "Mis-routed /prompt submissions forwarded to their "
                 "owning shard.",
                 [({}, ssnap["forwards"])]),
                ("dtpu_shard_takeovers_total", "counter",
                 "Dead peer shards absorbed by this master.",
                 [({}, ssnap["takeovers"])]),
            ])
        if state.autoscaler is not None:
            asnap = state.autoscaler.snapshot()
            extra.extend([
                ("dtpu_autoscale_scale_ups_total", "counter",
                 "Autoscaler scale-up actions.",
                 [({}, asnap["scale_ups"])]),
                ("dtpu_autoscale_scale_downs_total", "counter",
                 "Autoscaler scale-down actions.",
                 [({}, asnap["scale_downs"])]),
                ("dtpu_autoscale_flaps_total", "counter",
                 "Direction reversals inside the flap window "
                 "(should stay 0).",
                 [({}, asnap["flaps"])]),
                ("dtpu_autoscale_retiring", "gauge",
                 "Workers currently draining toward retirement.",
                 [({}, len(asnap["retiring"]))]),
            ])
        if state.durable is not None:
            # WAL size/lag + lease gauges (satellite: the durability
            # plane is scrapeable next to everything else).  stats()
            # lists segment files — keep it off the event loop.
            ds = await loop.run_in_executor(None, state.durable.stats)
            wal = ds.get("wal") or {}
            lease = ds.get("lease") or {}
            extra.extend([
                ("dtpu_wal_records_total", "counter",
                 "Records appended to the write-ahead job log.",
                 [({}, wal.get("records_appended", 0))]),
                ("dtpu_wal_bytes", "gauge",
                 "Live WAL segment bytes on disk.",
                 [({}, wal.get("bytes", 0))]),
                ("dtpu_wal_segments", "gauge",
                 "Live WAL segment files.",
                 [({}, wal.get("segments", 0))]),
                ("dtpu_wal_unsynced_records", "gauge",
                 "Appended records not yet fsync'd (sync lag).",
                 [({}, wal.get("unsynced_records", 0))]),
                ("dtpu_wal_last_sync_age_seconds", "gauge",
                 "Seconds since the last WAL fsync.",
                 [({}, wal.get("last_sync_age_s", 0) or 0)]),
                ("dtpu_master_epoch", "gauge",
                 "This process's master-lease epoch (fencing token); "
                 "0 = standby.",
                 [({}, ds.get("epoch", 0))]),
                ("dtpu_master_lease_remaining_seconds", "gauge",
                 "Seconds until the observed master lease expires.",
                 [({}, max(lease.get("expires_in_s", 0) or 0, 0))]),
                ("dtpu_master_takeovers_total", "counter",
                 "Lease takeovers performed by this process.",
                 [({}, ds.get("takeovers", 0))]),
            ])
        # continuous capture plane (ISSUE 18): exporter counters when
        # armed (first stats() may construct the exporter — a dir scan,
        # so off the loop), plus the SLO burn-rate gauges
        exp_stats = await loop.run_in_executor(None,
                                               trace_export_mod.stats)
        if exp_stats.get("enabled"):
            extra.extend([
                ("dtpu_trace_export_traces_total", "counter",
                 "Committed traces appended to capture segments.",
                 [({}, exp_stats["exported"])]),
                ("dtpu_trace_export_dropped_total", "counter",
                 "Capture records dropped (disk errors or "
                 "unserializable payloads).",
                 [({}, exp_stats["dropped"])]),
                ("dtpu_trace_export_bytes_total", "counter",
                 "Bytes appended to capture segments.",
                 [({}, exp_stats["bytes_written"])]),
                ("dtpu_trace_export_rotations_total", "counter",
                 "Capture segment rotations.",
                 [({}, exp_stats["rotations"])]),
                ("dtpu_trace_export_retired_total", "counter",
                 "Oldest capture segments deleted by the retention "
                 "cap.",
                 [({}, exp_stats["retired_segments"])]),
            ])
        extra.extend(state.slo.prom_families())
        # critical-path analytics plane: anomaly counter (always
        # present so dashboards can alert on rate>0 the moment a
        # baseline is armed) + per-worker clock-skew gauges
        extra.append(
            ("dtpu_analysis_anomalies_total", "counter",
             "Per-trace category blame exceeding the armed baseline "
             "profile's tolerance.",
             [({}, analysis_mod.anomalies_total())]))
        skews = state.cluster.skew_snapshot()
        if skews:
            extra.append(
                ("dtpu_clock_skew_seconds", "gauge",
                 "Estimated worker-clock offset vs this master "
                 "(min-filtered heartbeat one-way samples).",
                 [({"worker_id": w}, s["offset_s"])
                  for w, s in sorted(skews.items())]))
        # current resource gauges (unlabelled = this process); the
        # worker_id-labelled fleet view lives on /cluster/metrics.prom
        extra.extend(resource_mod.resource_prom_families(
            {"": self_sample}))
        text = trace_mod.prometheus_text(extra=extra)
        return web.Response(text=text,
                            content_type="text/plain",
                            charset="utf-8")

    async def metrics_reset(request):
        """Guarded aggregate-metrics reset (benches and multi-phase test
        runs stop inheriting cross-run telemetry).  DTPU_METRICS_RESET=0
        disables the route (403).  Body {"include_traces": true} also
        clears the flight recorder; per-prompt history and the monotonic
        retrace counters are never touched."""
        if os.environ.get(C.METRICS_RESET_ENV, "1").lower() \
                in ("0", "false", "off"):
            return web.json_response(
                {"error": "metrics reset disabled "
                          f"({C.METRICS_RESET_ENV}=0)"}, status=403)
        data = await request.json() if request.can_read_body else {}
        cleared = trace_mod.reset_aggregate_metrics()
        # keep the reset surface TOTAL (ISSUE 18): the new planes clear
        # with everything else — SLO windows, exemplar samples (inside
        # the histograms reset_aggregate_metrics just recreated) and the
        # exporter counters (its first touch may scan the capture dir,
        # so off the loop); capture FILES are durable by design and stay
        state.slo.reset()
        cleared["slo_windows"] = True
        await asyncio.get_running_loop().run_in_executor(
            None, trace_export_mod.reset_counters)
        cleared["export_counters"] = True
        # analytics plane: live profiles + anomaly counters + the
        # per-worker clock-skew estimates (they re-converge from the
        # next heartbeats) — ISSUE 20 satellite
        analysis_mod.reset_live()
        cleared["analysis"] = True
        cleared["skew_estimates"] = state.cluster.reset_skew()
        if data.get("include_traces"):
            trace_mod.GLOBAL_TRACES.reset()
            cleared["traces"] = True
        log("aggregate metrics reset "
            f"(by {request.remote or 'unknown'})")
        return ok({"cleared": cleared})

    async def slo_view(request):
        """SLO burn-rate engine snapshot: per-tenant objectives, window
        stats, burn rates and remaining budget (`cli slo` reads this)."""
        return web.json_response(state.slo.evaluate())

    async def analysis_view(request):
        """Critical-path analytics over the live flight-recorder ring
        (`cli analyze` reads this): blame profiles grouped by tenant /
        structural signature / worker, the per-worker straggler
        scorecard next to the WorkLedger's hedging latency EMAs, the
        live anomaly plane and clock-skew estimates (ISSUE 20)."""
        records = trace_mod.GLOBAL_TRACES.records()
        # pure-CPU span crunching over up to the whole ring — off the
        # event loop so a deep ring can't stall heartbeats
        report = await asyncio.get_running_loop().run_in_executor(
            None, analysis_mod.analyze_records, records)
        ledger = state.ledger.snapshot()
        hedging = {jid: j.get("latency_estimate_s")
                   for jid, j in ledger.get("active_jobs", {}).items()}
        return web.json_response({
            **report,
            "hedging_latency_ema_s": hedging,
            "live": analysis_mod.LIVE.snapshot(),
            "skew": state.cluster.skew_snapshot(),
        })

    async def get_trace(request):
        """Flight recorder: one completed job's full span tree."""
        pid = request.match_info["prompt_id"]
        rec = trace_mod.GLOBAL_TRACES.get(pid)
        if rec is None:
            return web.json_response(
                {"error": f"no recorded trace for {pid!r} (completed "
                          "jobs only; ring keeps the most recent "
                          f"{trace_mod.GLOBAL_TRACES.max_traces})"},
                status=404)
        rec["tree"] = trace_mod.build_span_tree(rec["spans"])
        return web.json_response(rec)

    async def list_traces(request):
        """Flight recorder index, newest first."""
        return web.json_response({
            "traces": trace_mod.GLOBAL_TRACES.index(),
            "ring_max": trace_mod.GLOBAL_TRACES.max_traces,
            "tracing_enabled": trace_mod.tracing_enabled()})

    async def warmup(request):
        """AOT warmup (registry.DiffusionPipeline.warmup): compile +
        execute the serving-shaped programs for a checkpoint so the next
        matching /prompt pays dispatch cost only.  Body: {"ckpt_name",
        "width", "height", "batch", "steps", "cfg", "sampler_name",
        "scheduler", "denoise"} — all optional but ckpt_name."""
        from comfyui_distributed_tpu.models import registry
        data = await request.json() if request.can_read_body else {}
        ckpt = data.get("ckpt_name", "model.safetensors")
        kwargs = {k: data[k] for k in
                  ("height", "width", "batch", "steps", "cfg",
                   "sampler_name", "scheduler", "denoise") if k in data}
        loop = asyncio.get_running_loop()

        def run():
            pipe = registry.load_pipeline(ckpt,
                                          models_dir=state.models_dir)
            return pipe.warmup(**kwargs)

        # compile happens off the event loop; the control plane stays up
        timings = await loop.run_in_executor(None, run)
        return ok({"ckpt_name": ckpt, "timings": timings})

    # --- profiling (the subsystem the reference lacks, SURVEY.md §5) -------

    async def profile_start(request):
        # off the loop: start_device_trace mkdirs the output dir and
        # spins up the device profiler (backend touch) — the dtpu-lint
        # async-blocking-transitive finding this route shipped with
        from comfyui_distributed_tpu.utils import trace as trace_mod
        data = await request.json() if request.can_read_body else {}
        try:
            out = await asyncio.get_running_loop().run_in_executor(
                None, lambda: trace_mod.start_device_trace(
                    data.get("dir")))
        except RuntimeError as e:
            return web.json_response({"error": str(e)}, status=409)
        return ok({"dir": out})

    async def profile_stop(request):
        # off the loop for the same reason: stop flushes the collected
        # device trace to disk, then a child process reduces it to the
        # summary (<dir>/summary.json, and "profile" on /metrics)
        from comfyui_distributed_tpu.utils import trace as trace_mod
        try:
            out = await asyncio.get_running_loop().run_in_executor(
                None, trace_mod.stop_device_trace)
        except RuntimeError as e:
            return web.json_response({"error": str(e)}, status=409)
        summary = trace_mod.profile_summary()
        if summary is not None and summary.get("dir") != out:
            summary = None      # this trace left nothing to reduce
        return ok({"dir": out, "summary": summary})

    async def profile_status(request):
        from comfyui_distributed_tpu.utils import trace as trace_mod
        return web.json_response(trace_mod.trace_status())

    async def clear_memory(request):
        # the whole probe/clear/GC pass runs off the event loop: the
        # device probes can initialize a backend, jax.clear_caches walks
        # every live executable and three full GC passes over a loaded
        # model take seconds — a scrape or heartbeat must not queue
        # behind any of it (dtpu-lint: async-blocking)
        def clear():
            import gc

            import jax

            from comfyui_distributed_tpu.models import registry
            # before/after memory_stats() snapshots: the response
            # reports what the clear ACTUALLY freed, not just that it
            # ran (satellite: on a fleet, "clear didn't free anything"
            # is the signal that a worker is holding leaked buffers)
            before = resource_mod.device_memory_snapshot()
            rss_before = resource_mod.host_rss_bytes()
            registry.clear_pipeline_cache()
            # invalidate the cross-request reuse plane (ISSUE 13): a
            # reloaded checkpoint must never replay a stale entry, and
            # the freed residency belongs in this route's before/after
            # snapshot like every other cache it drops
            cache_freed = reuse_mod.get_reuse().clear()
            jax.clear_caches()
            for _ in range(3):
                gc.collect()
            after = resource_mod.device_memory_snapshot()
            rss_after = resource_mod.host_rss_bytes()
            return before, rss_before, after, rss_after, cache_freed

        before, rss_before, after, rss_after, cache_freed = await asyncio \
            .get_running_loop().run_in_executor(None, clear)
        freed = max(before["bytes_in_use"] - after["bytes_in_use"], 0)
        log(f"cleared model/jit caches (freed {freed / 1e6:.1f} MB "
            f"device, {cache_freed / 1e6:.1f} MB reuse cache, "
            f"source={after['source']})")
        return ok({
            "freed_bytes": freed,
            "cache_freed_bytes": cache_freed,
            "device_bytes_before": before["bytes_in_use"],
            "device_bytes_after": after["bytes_in_use"],
            "host_rss_before": rss_before,
            "host_rss_after": rss_after,
            "source": after["source"],
        })

    async def launch_worker(request):
        data = await request.json()
        # config read + subprocess spawn off the loop (dtpu-lint:
        # async-blocking): launch_worker waits on the child and rewrites
        # managed-process state under the manager's file lock
        loop = asyncio.get_running_loop()
        cfg = await loop.run_in_executor(
            None, lambda: cfg_mod.load_config(state.config_path))
        worker = next((w for w in cfg["workers"]
                       if str(w.get("id")) == str(data.get("id"))), None)
        if worker is None:
            return web.json_response({"error": "worker not found"},
                                     status=404)
        try:
            entry = await loop.run_in_executor(
                None, lambda: state.manager.launch_worker(
                    worker, stop_on_master_exit=cfg["settings"].get(
                        "stop_workers_on_master_exit", True)))
        except RuntimeError as e:
            return web.json_response({"error": str(e)}, status=409)
        return ok({"worker": entry})

    async def stop_worker(request):
        data = await request.json()
        # terminate + bounded wait (up to PROCESS_TERMINATION_TIMEOUT)
        # off the loop (dtpu-lint: async-blocking)
        stopped = await asyncio.get_running_loop().run_in_executor(
            None, lambda: state.manager.stop_worker(str(data.get("id"))))
        if not stopped:
            return web.json_response({"error": "not managed"}, status=404)
        return ok()

    async def managed_workers(request):
        # off the loop: liveness of each managed pid is probed via
        # `kill -0` through subprocess on some platforms — the dtpu-lint
        # async-blocking-transitive finding this route shipped with
        managed = await asyncio.get_running_loop().run_in_executor(
            None, state.manager.get_managed_workers)
        return web.json_response(managed)

    async def cluster_info(request):
        """Cluster control plane snapshot: lease-based worker states,
        the work ledger's active/completed jobs, and the effective
        fault/hedge policy knobs."""
        return web.json_response({
            **state.cluster.snapshot(),
            "ledger": state.ledger.snapshot(),
            "policy": cluster_mod.fault_policy(),
            "hedge": {"armed": cluster_mod.hedge_armed(),
                      "min_progress_pct": cluster_mod.hedge_pct(),
                      "factor": cluster_mod.hedge_factor()},
        })

    async def cluster_register(request):
        """Elastic worker registration: a worker that only knows the
        master URL joins the registry (and the lease state machine)
        without appearing in the config file."""
        data = await request.json()
        wid = data.get("worker_id") or data.get("id")
        if not wid:
            return web.json_response({"error": "missing worker_id"},
                                     status=400)
        info = {k: data[k] for k in ("host", "port", "name") if k in data}
        info.setdefault("host", request.remote)
        out = state.cluster.register(str(wid), info=info)
        _feed_skew(str(wid), data)
        return ok({**out, "master_time": time.time()})

    def _feed_skew(wid: str, data: Dict[str, Any]) -> None:
        """Clock-skew sample off a heartbeat/register body (ISSUE 20):
        the payload's ``sent_at`` (the worker's wall clock at send) vs
        this process's wall clock now.  The registry min-filters — the
        sample with the least uplink delay wins."""
        sent = data.get("sent_at")
        if sent is None:
            return
        try:
            state.cluster.update_skew(wid, time.time() - float(sent))
        except (TypeError, ValueError):
            pass

    async def cluster_heartbeat(request):
        """Lease renewal (runtime/cluster.HeartbeatSender posts here
        every lease/3); unknown workers are auto-registered."""
        data = await request.json()
        wid = data.get("worker_id") or data.get("id")
        if not wid:
            return web.json_response({"error": "missing worker_id"},
                                     status=400)
        info = {k: data[k] for k in ("host", "port", "name") if k in data}
        info.setdefault("host", request.remote)
        out = state.cluster.heartbeat(str(wid), info=info)
        # heartbeats carry a resource snapshot (ISSUE 5): retain the
        # latest per worker for the federated metrics endpoints
        if isinstance(data.get("resources"), dict):
            state.cluster.update_resources(str(wid), data["resources"])
        _feed_skew(str(wid), data)
        # the reply carries this master's wall clock so a future
        # worker-side refinement can bound the estimate with the RTT
        return ok({**out, "master_time": time.time()})

    async def fleet_info(request):
        """Elastic-fleet plane (ISSUE 9): autoscaler state + decision
        ring, the live federated signal it scales on, per-class
        admission counters and the chaos-harness spec — the one
        endpoint `cli fleet` renders."""
        scaler = state.autoscaler
        snap = {"enabled": False,
                "armed_env": autoscale_mod.autoscale_armed()}
        signal = None
        if scaler is not None:
            loop = asyncio.get_running_loop()
            snap = scaler.snapshot()
            # the signal probes the registry + resource monitor — keep
            # it off the event loop like every other probe
            signal = await loop.run_in_executor(None,
                                                scaler.fleet_signal)
        return web.json_response({
            "autoscale": {**snap, "signal": signal},
            "admission": {
                **state.admission.snapshot(),
                "queued_by_class": state.queued_by_class(),
                "drain_rate_per_s": round(state.drain_rate(), 4),
                "max_queue": state.max_queue,
            },
            "workers": state.cluster.snapshot()["workers"],
            "chaos": chaos_mod.get_chaos().snapshot(),
        })

    async def durability_info(request):
        """Durability plane snapshot: lease holder/epoch, WAL size and
        sync lag, recovery counters — None-shaped when DTPU_WAL_DIR is
        unset."""
        if state.durable is None:
            return web.json_response({"enabled": False})
        stats = await asyncio.get_running_loop().run_in_executor(
            None, state.durable.stats)
        return web.json_response(stats)

    async def takeover(request):
        """Promote this server to master: acquire the lease (allowed
        when it is expired, or ``{"force": true}``), replay the shared
        WAL, resume the interrupted prompts, re-home workers.  The
        standby's own lease watcher calls the same path automatically on
        expiry; this endpoint is the operator's manual trigger."""
        from comfyui_distributed_tpu.runtime import durable as durable_mod
        if state.durable is None:
            return web.json_response(
                {"error": f"durability off (set {C.WAL_DIR_ENV})"},
                status=409)
        data = await request.json() if request.can_read_body else {}
        loop = asyncio.get_running_loop()
        try:
            out = await loop.run_in_executor(
                None, lambda: state.durable.takeover(
                    force=bool(data.get("force"))))
        except durable_mod.LeaseHeldError as e:
            return web.json_response({"error": str(e)}, status=409)
        return ok(out)

    async def rehome(request):
        """Worker side of master failover: a new master announces
        itself; this worker retargets its lease heartbeat (and registers
        there immediately so the new registry sees it without waiting
        for a probe)."""
        data = await request.json()
        url = str(data.get("master_url", "")).rstrip("/")
        if not url:
            return web.json_response({"error": "missing master_url"},
                                     status=400)
        wid = str(data.get("worker_id", "")
                  or os.environ.get(C.WORKER_ID_ENV, ""))
        os.environ[C.MASTER_URL_ENV] = url
        if wid:
            os.environ.setdefault(C.WORKER_ID_ENV, wid)
        hb = state.heartbeat
        if hb is None and wid:
            hb = state.heartbeat = cluster_mod.HeartbeatSender(
                url, wid, port=state.port)
            hb.start()
        beat = False
        if hb is not None:
            # re-register at the new master NOW, with a short retry
            # burst (HeartbeatSender.rehome): the first beat can race
            # the dying master's teardown, and a single best-effort
            # beat would leave this worker unregistered — reading as
            # lease-expired — for a full heartbeat interval, so the new
            # master needlessly reassigns its in-flight units
            loop = asyncio.get_running_loop()
            beat = await loop.run_in_executor(None,
                                              lambda: hb.rehome(url))
        log(f"re-homed to master {url}"
            + ("" if beat else " (first heartbeat pending)"))
        return ok({"master_url": url, "heartbeat": hb is not None,
                   "registered": beat})

    def _self_sample() -> Dict[str, Any]:
        """This process's resource sample for the metrics surfaces: the
        monitor's latest (it carries the utilization estimate, which
        needs two samples) with the queue depth refreshed from THIS
        state — a multi-state process's global monitor may be bound to
        another state's queue."""
        snap = resource_mod.fleet_sample()
        return {**snap, "queue_depth": state.queue_remaining()}

    async def resource_info(request):
        """This participant's current resource sample + monitor state —
        the unit the federation merges, and the pull-through target when
        a worker's heartbeat snapshot goes stale."""
        snap = await asyncio.get_running_loop().run_in_executor(
            None, _self_sample)
        return web.json_response({
            "resources": snap,
            "monitor": (state.resources.snapshot()
                        if state.resources is not None
                        else {"enabled": False}),
        })

    # wid -> monotonic time of the last FAILED federation pull (the
    # negative cache bounding per-scrape pull latency)
    _res_pull_failed_at: Dict[str, float] = {}

    async def _fleet_resources() -> Dict[str, Any]:
        """Merged master+workers resource view (ISSUE 5 federation).

        Each registered worker contributes its latest heartbeat
        snapshot; snapshots older than DTPU_RES_FED_TTL_S (a missed
        heartbeat) are re-pulled live from the worker's
        ``GET /distributed/resource`` and cached back into the registry,
        so scrapes between heartbeats stay fresh without a per-scrape
        fan-out.  Dead workers keep their last snapshot, aged and marked
        stale, rather than vanishing mid-incident.  A failed pull is
        negative-cached for the same TTL so an unreachable (but not yet
        DEAD) worker costs one timeout per TTL window, not one per
        scrape."""
        import aiohttp

        from comfyui_distributed_tpu.utils.net import get_client_session
        try:
            ttl = float(os.environ.get(C.RES_FED_TTL_ENV,
                                       C.RES_FED_TTL_DEFAULT))
        except ValueError:
            ttl = C.RES_FED_TTL_DEFAULT
        now = time.monotonic()
        reg = state.cluster.resource_snapshots()
        to_pull = [
            (wid, v) for wid, v in reg.items()
            if v.get("host") and v.get("port")
            and v["state"] != cluster_mod.DEAD
            and (v["age_s"] is None or v["age_s"] > ttl)
            and now - _res_pull_failed_at.get(wid, -1e9) > ttl]
        if to_pull:
            session = await get_client_session()

            async def pull(wid, v):
                url = (f"http://{v['host']}:{v['port']}"
                       "/distributed/resource")
                try:
                    async with session.get(
                            url, timeout=aiohttp.ClientTimeout(
                                total=2)) as r:
                        if r.status == 200:
                            body = await r.json()
                            if isinstance(body.get("resources"), dict):
                                state.cluster.update_resources(
                                    wid, body["resources"])
                                _res_pull_failed_at.pop(wid, None)
                                return
                except Exception as e:  # noqa: BLE001 - best-effort pull
                    debug_log(f"resource pull from {wid} failed: {e}")
                _res_pull_failed_at[wid] = time.monotonic()

            await asyncio.gather(*(pull(wid, v) for wid, v in to_pull))
            reg = state.cluster.resource_snapshots()
        self_id = "master" if not state.is_worker \
            else os.environ.get(C.WORKER_ID_ENV, "self")
        self_snap = await asyncio.get_running_loop().run_in_executor(
            None, _self_sample)
        participants: Dict[str, Any] = {
            self_id: {
                "state": "self",
                "resources": self_snap,
                "age_s": 0.0,
                "stale": False,
            }}
        for wid, v in reg.items():
            if wid == self_id:
                # a registered worker colliding with this process's own
                # id (someone named a worker "master") still shows up,
                # disambiguated, instead of silently vanishing
                wid = f"{wid}@registry"
            participants[wid] = {
                "state": v["state"],
                "host": v.get("host"), "port": v.get("port"),
                "resources": v["resources"],
                "age_s": v["age_s"],
                "stale": v["age_s"] is None or v["age_s"] > ttl,
            }
        return {"participants": participants, "ttl_s": ttl}

    async def cluster_metrics(request):
        """Federated fleet resources as JSON (feeds ``cli top``)."""
        return web.json_response(await _fleet_resources())

    async def cluster_metrics_prom(request):
        """Federated fleet resources as Prometheus text: one gauge
        series per participant, distinguished by ``worker_id`` — the
        single scrape point for fleet memory/utilization dashboards."""
        fleet = await _fleet_resources()
        parts = fleet["participants"]
        fams = resource_mod.resource_prom_families(
            {wid: p.get("resources") for wid, p in parts.items()},
            ages={wid: p.get("age_s") for wid, p in parts.items()})
        fams.append(
            ("dtpu_res_participants", "gauge",
             "Participants in the federated resource view.",
             [({}, len(parts))]))
        return web.Response(text=trace_mod.render_prom_families(fams),
                            content_type="text/plain", charset="utf-8")

    async def workers_status(request):
        """Live worker health (the reference panel's 2s status dots,
        ``gpupanel.js:1233-1311``), served from the poller's snapshot."""
        return web.json_response(state.health.snapshot())

    async def _fanout_to_workers(path: str,
                                 bodies: Optional[Dict[str, Any]] = None
                                 ) -> Dict[str, Any]:
        """POST ``path`` on every enabled worker (reference toolbar fan-out,
        ``gpupanel.js:204-306``).  ``bodies`` (optional dict) collects each
        worker's parsed JSON response for callers that aggregate."""
        import aiohttp

        from comfyui_distributed_tpu.utils.net import get_client_session
        from comfyui_distributed_tpu.workflow.dispatcher import worker_url
        loop = asyncio.get_running_loop()
        cfg = await loop.run_in_executor(
            None, lambda: cfg_mod.load_config(state.config_path))
        session = await get_client_session()
        results: Dict[str, Any] = {}

        async def hit(w):
            try:
                async with session.post(
                        worker_url(w) + path,
                        timeout=aiohttp.ClientTimeout(total=10)) as r:
                    results[str(w["id"])] = r.status
                    if bodies is not None and r.status == 200:
                        try:
                            bodies[str(w["id"])] = await r.json()
                        except Exception:  # noqa: BLE001 - non-JSON body
                            pass
            except Exception as e:  # noqa: BLE001 - report per-worker
                results[str(w["id"])] = str(e)

        await asyncio.gather(*(hit(w) for w in cfg_mod.enabled_workers(cfg)))
        return results

    async def cluster_clear_memory(request):
        """Clear caches here AND on every enabled worker (reference
        ``_handleClearMemory``, ``gpupanel.js:259-306``), aggregating
        the bytes each participant actually freed."""
        bodies: Dict[str, Any] = {}
        results = await _fanout_to_workers("/distributed/clear_memory",
                                           bodies=bodies)
        resp = await clear_memory(request)
        local = json.loads(resp.body.decode())
        freed_by = {"master": int(local.get("freed_bytes", 0))}
        for wid, body in bodies.items():
            if isinstance(body, dict) and "freed_bytes" in body:
                freed_by[wid] = int(body["freed_bytes"])
        return ok({"workers": results,
                   "freed_bytes": freed_by,
                   "freed_bytes_total": sum(freed_by.values())})

    async def cluster_interrupt(request):
        """Interrupt here AND on every enabled worker (reference
        ``_handleInterruptWorkers``, ``gpupanel.js:204-257``)."""
        results = await _fanout_to_workers("/interrupt")
        state.interrupt_event.set()
        return ok({"workers": results})

    async def worker_log(request):
        wid = request.query.get("id", "")
        try:
            # log-file seek+read off the loop (dtpu-lint: async-blocking)
            max_bytes = int(request.query.get("bytes", LOG_TAIL_BYTES))
            text = await asyncio.get_running_loop().run_in_executor(
                None, lambda: state.manager.tail_log(
                    wid, max_bytes=max_bytes))
        except FileNotFoundError as e:
            return web.json_response({"error": str(e)}, status=404)
        return web.json_response({"log": text})

    async def clear_launching(request):
        data = await request.json()
        state.manager.clear_launching(str(data.get("id")))
        return ok()

    # --- job data plane -----------------------------------------------------

    async def prepare_job(request):
        t_recv = time.time()
        data = await request.json()
        mj = data.get("multi_job_id")
        if not mj:
            return web.json_response({"error": "missing multi_job_id"},
                                     status=400)
        if data.get("kind") == "tile":
            await state.jobs.prepare_tile_job(mj)
        else:
            await state.jobs.prepare_job(mj)
        tp = trace_mod.parse_traceparent(
            request.headers.get(C.TRACEPARENT_HEADER))
        if tp is not None:
            trace_mod.event_span("prepare_job", t_recv, time.time(),
                                 trace_id=tp[0], parent_id=tp[1],
                                 attrs={"job": str(mj)})
        debug_log(f"prepared {data.get('kind', 'image')} job {mj}")
        return ok()

    async def queue_status(request):
        mj = request.query.get("multi_job_id", "")
        exists = await state.jobs.has_tile_job(mj) or \
            await state.jobs.has_job(mj)
        return web.json_response({"exists": exists,
                                  "queue_remaining":
                                      state.queue_remaining(),
                                  "max_queue": state.max_queue})

    async def wire_formats(request):
        """Wire negotiation (utils.net.negotiate_wire_format): workers
        probe this once per master; a master listing the raw-tensor type
        gets npy uploads instead of PNG — less encode CPU and fewer wire
        bytes on the worker->master hop.  ``tensor_codecs`` names what
        THIS build can decode so a zstd-capable worker never sends zstd
        at a deflate-only master."""
        from comfyui_distributed_tpu.utils.image import tensor_codecs
        return web.json_response({
            "formats": [C.TENSOR_WIRE_CONTENT_TYPE, "image/png"],
            "tensor_codecs": tensor_codecs()})

    def _decode_upload(field) -> Any:
        """Multipart image/tile field -> tensor, honoring the negotiated
        content type (raw tensor or PNG) with wire accounting.  The
        chaos harness may corrupt the payload HERE: the decode then
        raises, the sender's retry re-delivers clean, and the
        idempotency keys keep the redelivery exactly-once."""
        data = field.file.read()
        cm = chaos_mod.get_chaos()
        if cm.active:
            data = cm.corrupt(data, what="tile/image upload")
        if (field.content_type or "") == C.TENSOR_WIRE_CONTENT_TYPE:
            trace_mod.GLOBAL_COUNTERS.bump("wire_tensor_msgs")
            trace_mod.GLOBAL_COUNTERS.bump("wire_tensor_bytes", len(data))
            return decode_tensor(data)
        trace_mod.GLOBAL_COUNTERS.bump("wire_png_msgs")
        trace_mod.GLOBAL_COUNTERS.bump("wire_png_bytes", len(data))
        return decode_png(data)

    def _ingest_remote_trace(request, form, name: str,
                             t_recv: float, attrs: Dict[str, Any]) -> None:
        """Stitch an inbound data-plane POST into the job's distributed
        trace: merge the peer's shipped spans (final upload only) and
        record the server-side receive as a child of the sender's span
        named in its traceparent header."""
        # clock-skew correction (ISSUE 20): shipped spans carry the
        # WORKER's wall clock; shift them onto this master's clock by
        # the registry's heartbeat-derived offset estimate before they
        # land in the ring, so cross-process dispatch edges stop going
        # negative and critical-path network blame isn't fiction
        offset = 0.0
        wid = str(attrs.get("worker") or "")
        if wid and analysis_mod.skew_correction_enabled():
            offset = state.cluster.skew(wid)
        spans_field = form.get("spans")
        if spans_field:
            try:
                shipped = json.loads(spans_field)
                if offset and isinstance(shipped, list):
                    for s in shipped:
                        if not isinstance(s, dict):
                            continue
                        for k in ("start_s", "end_s"):
                            if isinstance(s.get(k), (int, float)):
                                s[k] = s[k] + offset
                trace_mod.GLOBAL_TRACES.ingest(shipped)
            except (ValueError, TypeError) as e:
                debug_log(f"bad spans field on {name}: {e}")
        tp = trace_mod.parse_traceparent(
            request.headers.get(C.TRACEPARENT_HEADER))
        if tp is not None:
            if offset:
                attrs = {**attrs, "skew_ms": round(offset * 1e3, 3)}
            trace_mod.event_span(name, t_recv, time.time(),
                                 trace_id=tp[0], parent_id=tp[1],
                                 attrs=attrs)

    async def job_complete(request):
        t_recv = time.time()
        form = await request.post()
        mj = form.get("multi_job_id", "")
        img_field = form.get("image")
        if not mj or img_field is None:
            return web.json_response({"error": "missing fields"}, status=400)
        # decode off the event loop: concurrent uploads must not stall
        # the control plane (a stalled /prompt fails preflight's 300ms probe)
        loop = asyncio.get_running_loop()
        tensor = await loop.run_in_executor(
            None, lambda: _decode_upload(img_field))
        item = {
            "worker_id": form.get("worker_id", ""),
            "is_last": str(form.get("is_last", "false")).lower() == "true",
            "tensor": tensor,
        }
        # only pass the index through when the sender set one: the collector
        # dedups retransmits by (worker, index), and defaulting indexless
        # uploads to 0 would collapse them into a single image
        if form.get("image_index") is not None:
            item["image_index"] = int(form["image_index"])
        if not await state.jobs.put_result(
                mj, item, idem_key=form.get("idem_key")):
            # unknown job -> 404 so the worker's retry loop backs off
            return web.json_response({"error": f"unknown job {mj}"},
                                     status=404)
        # a data-plane POST proves the sender is alive — renew its lease
        state.cluster.touch(str(form.get("worker_id", "")))
        state.metrics["images_received"] += 1
        _ingest_remote_trace(request, form, "receive_image", t_recv,
                             {"job": str(mj),
                              "worker": str(form.get("worker_id", ""))})
        return ok()

    async def tile_complete(request):
        t_recv = time.time()
        form = await request.post()
        mj = form.get("multi_job_id", "")
        tile_field = form.get("tile")
        if not mj or tile_field is None:
            return web.json_response({"error": "missing fields"}, status=400)
        item = {
            "worker_id": form.get("worker_id", ""),
            "tile_idx": int(form.get("tile_idx", 0)),
            "x": int(form.get("x", 0)),
            "y": int(form.get("y", 0)),
            "extracted_width": int(form.get("extracted_width", 0)),
            "extracted_height": int(form.get("extracted_height", 0)),
            "padding": int(form.get("padding", 0)),
            "is_last": str(form.get("is_last", "false")).lower() == "true",
            "tensor": await asyncio.get_running_loop().run_in_executor(
                None, lambda: _decode_upload(tile_field)),
        }
        if not await state.jobs.put_tile(
                mj, item, idem_key=form.get("idem_key")):
            # unknown/expired tile job -> 404; the worker's retry loop backs
            # off instead of resurrecting an orphan queue
            return web.json_response({"error": f"unknown tile job {mj}"},
                                     status=404)
        state.cluster.touch(str(form.get("worker_id", "")))
        state.metrics["tiles_received"] += 1
        _ingest_remote_trace(request, form, "receive_tile", t_recv,
                             {"job": str(mj),
                              "worker": str(form.get("worker_id", "")),
                              "tile_idx": int(form.get("tile_idx", 0))})
        return ok()

    async def load_image(request):
        """Input-image staging for remote workers (reference
        ``distributed.py:1135-1173``): name -> base64 PNG."""
        data = await request.json()
        name = str(data.get("image_name", ""))
        safe = os.path.normpath(name).lstrip(os.sep)
        if safe.startswith(".."):
            return web.json_response({"error": "bad path"}, status=400)
        path = os.path.join(state.input_dir, safe)
        if not os.path.exists(path):
            return web.json_response({"error": f"not found: {name}"},
                                     status=404)
        def read_b64():
            with open(path, "rb") as f:
                return base64.b64encode(f.read()).decode()
        b64 = await asyncio.get_running_loop().run_in_executor(None, read_b64)
        return web.json_response({"image_data": b64, "name": name})

    # --- ComfyUI-compatible worker surface ---------------------------------

    async def get_prompt(request):
        return web.json_response(
            {"exec_info": {"queue_remaining": state.queue_remaining()}})

    def _is_dispatched_share(prompt: Dict[str, Any]) -> bool:
        """Orchestrated-share predicate (one copy: workflow/orchestrate
        .is_dispatched_share).  Shares bypass this server's own
        admission — re-shedding would silently amputate an admitted
        job's worker shares; the hard queue-full cap still applies."""
        from comfyui_distributed_tpu.workflow.orchestrate import \
            is_dispatched_share
        return is_dispatched_share(prompt)

    async def _forward_prompt(url: str, owner: str,
                              data: Dict[str, Any],
                              traceparent: Optional[str] = None):
        """Single-hop mis-route forward: relay the original /prompt
        body to the owning shard, marked with SHARD_FORWARD_HEADER so
        the receiver never forwards again.  None on failure (the
        caller then accepts locally rather than bouncing the client)."""
        import aiohttp

        from comfyui_distributed_tpu.utils.net import get_client_session
        session = await get_client_session()
        headers = {C.SHARD_FORWARD_HEADER: state.shard.id}
        if traceparent:
            headers[C.TRACEPARENT_HEADER] = traceparent
        try:
            async with session.post(
                    f"{url}/prompt", json=data, headers=headers,
                    timeout=aiohttp.ClientTimeout(total=120)) as r:
                body = await r.json()
        except Exception as e:  # noqa: BLE001 - fall back to local
            debug_log(f"shard: forward to {owner} failed: {e}")
            return None
        state.shard.forwards += 1
        trace_mod.GLOBAL_COUNTERS.bump("shard_forwarded")
        if isinstance(body, dict):
            body.setdefault("shard", owner)
            body["forwarded_from"] = state.shard.id
        resp = web.json_response(body, status=r.status)
        # relay the owner's backpressure hint: a shed (429) loses its
        # HTTP-standard Retry-After if only the JSON body survives the
        # hop, and standards-honoring clients would retry immediately
        ra = r.headers.get("Retry-After")
        if ra is not None:
            resp.headers["Retry-After"] = ra
        return resp

    async def ring_info(request):
        """Consistent-hash ring state (ISSUE 14): membership, epoch,
        vnodes — everything a stateless router or a client-side hasher
        needs to place prompt-ids."""
        if state.shard is None:
            return web.json_response({"enabled": False})
        return web.json_response(state.shard.ring_snapshot())

    async def ring_gossip(request):
        """Peer gossip exchange: merge the sender's ring view, answer
        with ours (pure in-memory merge — event-loop safe)."""
        if state.shard is None:
            return web.json_response({"error": "sharding off "
                                      f"(set {C.SHARD_ID_ENV})"},
                                     status=409)
        data = await request.json()
        return web.json_response(state.shard.merge_gossip(data))

    async def post_prompt(request):
        # the handler from the body's read to the response, on the event
        # loop's thread (the admission itself runs off it, under the GIL)
        with trace_mod.stage("http_prompt"):
            return await _post_prompt(request)

    async def _post_prompt(request):
        data = await request.json()
        prompt = data.get("prompt")
        if not isinstance(prompt, dict) or not prompt:
            return web.json_response({"error": "missing prompt"}, status=400)
        # multi-master routing (ISSUE 14): a router/client-supplied
        # prompt_id hint is the hash key.  Mis-routed submissions are
        # forwarded AT MOST ONE HOP to the owning shard (the forward
        # header makes a ring disagreement terminate here instead of
        # looping) — the admission then lands in the OWNER's WAL before
        # the client gets its prompt-id.  Hint-less direct submissions
        # get a self-owned generated id (enqueue_prompt), so they never
        # forward.
        pid_hint = str(data.get("prompt_id") or "") or None
        fwd_from = request.headers.get(C.SHARD_FORWARD_HEADER)
        span_attrs = {"forwarded_from": fwd_from} if fwd_from else None
        if state.shard is not None and not state.is_worker \
                and pid_hint and not fwd_from \
                and not state.shard.is_mine(pid_hint):
            owner = state.shard.owner_of(pid_hint)
            url = state.shard.member_url(owner)
            if url:
                fwd = await _forward_prompt(
                    url, owner, data,
                    traceparent=request.headers.get(
                        C.TRACEPARENT_HEADER))
                if fwd is not None:
                    return fwd
            # owner unreachable (or url unknown): accept locally — the
            # availability choice; the ring heals via absorb/gossip and
            # the span records where the job actually landed
            trace_mod.GLOBAL_COUNTERS.bump("shard_forward_fallbacks")
        # master-mode tile jobs: pre-create their queues at prompt-queue
        # time, before the exec thread gets anywhere near the upscale node
        # (reference pre-inits at validation time, distributed_upscale.py:
        # 85-105) — otherwise a fast worker's tiles 404 through its retries
        for node in prompt.values():
            if not isinstance(node, dict) \
                    or node.get("class_type") != "UltimateSDUpscaleDistributed":
                continue
            h = {**node.get("inputs", {}), **node.get("hidden", {})}
            mj = h.get("multi_job_id")
            if mj and not h.get("is_worker"):
                await state.jobs.prepare_tile_job(str(mj))
        client_id = data.get("client_id", "unknown")
        # ComfyUI contract: extra_data.extra_pnginfo.workflow rides every
        # dispatch so saved PNGs embed the source workflow (reference
        # gpupanel.js:1344-1358)
        extra_data = data.get("extra_data") or {}
        # multi-tenant admission (ISSUE 9): {"priority": "paid"|"free"|
        # "batch"} classifies the request (untagged -> highest class);
        # {"slo_s": N} stamps its distributed jobs with a deadline that
        # re-keys the hedge machinery on the remaining budget
        tenant = state.admission.classify(
            data.get("priority") or extra_data.get("priority"))
        if data.get("priority") or extra_data.get("priority"):
            # tagged requests keep their class through extra_data (it
            # is WAL'd with the admission record, so a crash-recovery
            # re-enqueue resumes at the SAME priority)
            extra_data = {**extra_data, "priority": tenant}
        slo_s = data.get("slo_s") or extra_data.get("slo_s")
        try:
            slo_s = float(slo_s) if slo_s is not None else None
        except (TypeError, ValueError):
            slo_s = None
        if slo_s is not None and slo_s > 0:
            extra_data = {**extra_data, "slo_s": slo_s}

        def _shed_response(rejection):
            retry_after = max(int(rejection.get("retry_after_s", 1)),
                              state.retry_after_hint())
            return web.json_response(
                {"error": f"shed ({rejection['reason']}): tenant class "
                          f"{rejection['tenant']!r}",
                 "tenant": rejection["tenant"],
                 "reason": rejection["reason"],
                 "retry_after_s": retry_after,
                 "queue_remaining": state.queue_remaining(),
                 "max_queue": state.max_queue},
                status=429, headers={"Retry-After": str(retry_after)})
        # inbound trace context: a dispatching master's traceparent makes
        # this process's execution a child of ITS trace (the worker half
        # of the distributed tree); absent/malformed headers mean a fresh
        # local root — propagation can never fail a request
        trace_parent = trace_mod.parse_traceparent(
            request.headers.get(C.TRACEPARENT_HEADER))
        pid_kw = {"pid": pid_hint} if pid_hint else {}
        try:
            cfg = await _orchestration_config(prompt)
            if cfg is not None:
                # admission BEFORE the fan-out: a request that will be
                # shed must never reach the workers (they would start
                # seed slices for a master share that was 429'd); the
                # master-share enqueue below is then pre-admitted
                with state._queue_lock:
                    depth = len(state._queue)
                rejection = state.admission.admit(
                    tenant, str(client_id), depth, state.max_queue)
                if rejection is not None:
                    return _shed_response(rejection)
                # headless interceptor (reference setupInterceptor,
                # gpupanel.js:819-834): fan out to enabled HTTP workers,
                # enqueue the master's prepared share locally.  ONE root
                # span covers the whole fan-out: preflight/dispatch spans
                # (orchestrate), the local execution and the collector
                # drain all parent under it, and the worker ships its
                # spans back on the final data-plane POST — the flight
                # recorder then holds the full cross-process tree.
                from comfyui_distributed_tpu.workflow.orchestrate import (
                    run_distributed)
                tid, par = trace_parent if trace_parent else (None, None)
                root = trace_mod.start_span(
                    "job", trace_id=tid, parent_id=par,
                    attrs={"client_id": str(client_id), "role": "master",
                           "tenant": tenant, "fanout": True})

                async def enqueue_graph(g):
                    # off the loop: with durability on, admission
                    # appends+fsyncs a WAL record before returning
                    api = g.to_api_format()
                    return await asyncio.get_running_loop() \
                        .run_in_executor(None, lambda: state.enqueue_prompt(
                            api, client_id, extra_data, trace_span=root,
                            tenant=tenant, span_attrs=span_attrs,
                            _preadmitted=True, **pid_kw))

                host = cfg.get("master", {}).get("host") or "127.0.0.1"
                master_url = f"http://{host}:{state.port or 8288}"
                try:
                    with trace_mod.use_span(root):
                        out = await run_distributed(
                            prompt, master_url,
                            workers=cfg_mod.enabled_workers(cfg),
                            master_dispatch=enqueue_graph,
                            job_store=state.jobs,
                            client_id=client_id, extra_data=extra_data,
                            cluster=state.cluster, ledger=state.ledger)
                except Exception:
                    # the fan-out died before the exec thread adopted the
                    # root (finalize would have sealed it) — seal here so
                    # the failure still leaves a postmortem trace
                    if root is not None and root.end_s is None \
                            and not root.attrs.get("prompt_id"):
                        state._abandon_span(
                            root, f"failed_{root.trace_id[:12]}",
                            "fan-out failed before enqueue")
                    raise
                return web.json_response({
                    "prompt_id": out["result"],
                    "number": state.queue_remaining(),
                    "workers": out["workers"],
                    "failed_workers": out.get("failed", []),
                })
            # off the loop: the durable admission record fsyncs before
            # the prompt_id is acked to the client.  Already-orchestrated
            # shares (a peer master dispatched them) skip local admission
            # — their job was admitted where it entered the fleet.
            pre = _is_dispatched_share(prompt)
            pid = await asyncio.get_running_loop().run_in_executor(
                None, lambda: state.enqueue_prompt(
                    prompt, client_id, extra_data,
                    trace_parent=trace_parent, tenant=tenant,
                    span_attrs=span_attrs, _preadmitted=pre,
                    **pid_kw))
        except ShedError as e:
            return _shed_response(e.rejection)
        except QueueFullError as e:
            # backpressure (DTPU_MAX_QUEUE): tell the client how deep the
            # queue is — and when to come back (Retry-After from the
            # measured drain rate, so shed clients back off instead of
            # hammering in lockstep)
            retry_after = state.retry_after_hint()
            return web.json_response(
                {"error": str(e),
                 "queue_remaining": state.queue_remaining(),
                 "retry_after_s": retry_after,
                 "max_queue": state.max_queue}, status=429,
                headers={"Retry-After": str(retry_after)})
        except DrainingError as e:
            return web.json_response({"error": str(e)}, status=503)
        except Exception as e:  # noqa: BLE001
            return web.json_response({"error": str(e)}, status=400)
        return web.json_response({"prompt_id": pid,
                                  "number": state.queue_remaining()})

    async def _orchestration_config(prompt: Dict[str, Any]):
        """Return the loaded config when this prompt should fan out, else
        None.  Conditions: we're a master, the graph has distributed nodes,
        they are not already prepared (no hidden multi_job_id — i.e. not a
        graph some other orchestrator dispatched to us), and HTTP workers
        are enabled (reference routing condition, ``gpupanel.js:826-833``).
        The config is loaded ONCE, off the event loop, and reused for the
        master URL and worker list."""
        if state.is_worker:
            return None
        found = False
        for node in prompt.values():
            if not isinstance(node, dict):
                continue
            if node.get("class_type") in ("DistributedCollector",
                                          "UltimateSDUpscaleDistributed"):
                h = {**node.get("inputs", {}), **node.get("hidden", {})}
                if h.get("multi_job_id"):
                    return None  # already orchestrated elsewhere
                found = True
        if not found:
            return None
        loop = asyncio.get_running_loop()
        cfg = await loop.run_in_executor(
            None, lambda: cfg_mod.load_config(state.config_path))
        return cfg if cfg_mod.enabled_workers(cfg) else None

    async def panel(request):
        """Visual cluster panel (status dots, worker lifecycle, metrics,
        log tail) — one static dependency-free page over the JSON routes;
        the capability analog of the reference's sidebar
        (``gpupanel.js:327-801, 1519-2085``)."""
        return web.FileResponse(
            os.path.join(os.path.dirname(__file__), "panel.html"))

    async def interrupt(request):
        state.interrupt_event.set()
        log("interrupt requested")
        return ok()

    async def upload_image(request):
        form = await request.post()
        img = form.get("image")
        if img is None:
            return web.json_response({"error": "missing image"}, status=400)
        name = os.path.basename(img.filename or "upload.png")

        def write():
            # mkdir + disk write off the loop (dtpu-lint: async-blocking):
            # a slow disk must not stall concurrent data-plane POSTs
            os.makedirs(state.input_dir, exist_ok=True)
            with open(os.path.join(state.input_dir, name), "wb") as f:
                f.write(img.file.read())

        await asyncio.get_running_loop().run_in_executor(None, write)
        return web.json_response({"name": name, "subfolder": "",
                                  "type": "input"})

    async def history(request):
        return web.json_response(state._history)

    def _prompt_live(pid: str) -> bool:
        """Whether the prompt is admitted and not yet finalized (the
        authoritative _inflight set — the queue/CB-slot views have
        handoff windows).  An unknown id must never arm a dangling
        abandonment flag or pin a preview-client slot."""
        with state._queue_lock:
            return pid in state._inflight

    async def preview_stream(request):
        """Server-sent events: step-wise progressive previews for one
        prompt (``event: preview`` frames with a base64 PNG of the
        denoising latent, then one ``event: done``).  The stream is
        ALSO the cancellation channel: when the last subscriber
        disconnects before the job finishes, the job is abandoned — a
        queued prompt is purged, a CB slot exits at the next step
        boundary, and the WAL records the abandonment."""
        if not reuse_mod.previews_enabled():
            return web.json_response(
                {"error": f"previews disabled ({C.PREVIEW_ENV}=0)"},
                status=403)
        pid = request.match_info["prompt_id"]
        if pid not in state._history and not _prompt_live(pid):
            # unknown id: refuse BEFORE subscribing — an endless-ping
            # stream per garbage id would otherwise pin slots under the
            # DTPU_PREVIEW_MAX_CLIENTS cap indefinitely
            return web.json_response(
                {"error": f"unknown prompt {pid!r} (not queued, not "
                          "executing, not in history)"}, status=404)
        bus = reuse_mod.PREVIEWS
        q = bus.subscribe(pid)
        if q is None:
            return web.json_response(
                {"error": "too many preview clients"}, status=429)
        resp = web.StreamResponse(
            headers={"Content-Type": "text/event-stream",
                     "Cache-Control": "no-cache",
                     "X-Accel-Buffering": "no"})
        disconnected = False
        try:
            await resp.prepare(request)
            last_beat = time.monotonic()
            while True:
                ev = None
                try:
                    ev = q.get_nowait()
                except queue.Empty:
                    pass
                if ev is None:
                    hist = state._history.get(pid)
                    if hist is not None:
                        ev = {"type": "done", "prompt_id": pid,
                              "status": hist.get("status", "done")}
                    else:
                        now = time.monotonic()
                        if now - last_beat >= 1.0:
                            # heartbeat comment: disconnect detection
                            # between preview frames (a write to a
                            # closed transport raises)
                            await resp.write(b": ping\n\n")
                            last_beat = now
                        await asyncio.sleep(0.05)
                        continue
                await resp.write(
                    f"event: {ev['type']}\n"
                    f"data: {json.dumps(ev)}\n\n".encode())
                if ev.get("type") == "done":
                    break
            await resp.write_eof()
        except asyncio.CancelledError:
            # aiohttp cancels the handler when the client disconnects
            disconnected = True
            raise
        except (ConnectionResetError, ConnectionError):
            disconnected = True
        finally:
            remaining = bus.unsubscribe(pid, q)
            if disconnected and remaining == 0 \
                    and pid not in state._history and _prompt_live(pid):
                # client gone = cancellation signal: flag the job; the
                # CB driver's boundary scan / queue purge finalizes it
                bus.abandon(pid)
                state._queued_ns = trace_mod.now_ns()
                state._queue_event.set()
                if pid in state._history:
                    # finalize raced the disconnect: the job settled
                    # between our liveness check and the flag — consume
                    # the stale flag (finish() already ran; nothing
                    # else ever would, and the set must not leak)
                    bus.clear_abandoned(pid)
        return resp

    r.add_get("/distributed/config", get_config)
    r.add_post("/distributed/config/update_worker", update_worker)
    r.add_post("/distributed/config/delete_worker", delete_worker)
    r.add_post("/distributed/config/update_setting", update_setting)
    r.add_post("/distributed/config/update_master", update_master)
    r.add_get("/distributed/network_info", network_info)
    r.add_get("/distributed/status", status)
    r.add_get("/distributed/metrics", metrics)
    r.add_get("/distributed/metrics.prom", metrics_prom)
    r.add_post("/distributed/metrics/reset", metrics_reset)
    r.add_get("/distributed/traces", list_traces)
    r.add_get("/distributed/trace/{prompt_id}", get_trace)
    r.add_get("/distributed/slo", slo_view)
    r.add_get("/distributed/analysis", analysis_view)
    r.add_post("/distributed/warmup", warmup)
    r.add_get("/distributed/ring", ring_info)
    r.add_post("/distributed/ring/gossip", ring_gossip)
    r.add_get("/distributed/cluster", cluster_info)
    r.add_get("/distributed/resource", resource_info)
    r.add_get("/distributed/cluster/metrics", cluster_metrics)
    r.add_get("/distributed/cluster/metrics.prom", cluster_metrics_prom)
    r.add_post("/distributed/register", cluster_register)
    r.add_post("/distributed/heartbeat", cluster_heartbeat)
    r.add_get("/distributed/fleet", fleet_info)
    r.add_get("/distributed/durability", durability_info)
    r.add_post("/distributed/takeover", takeover)
    r.add_post("/distributed/rehome", rehome)
    r.add_get("/distributed/workers_status", workers_status)
    r.add_post("/distributed/cluster/clear_memory", cluster_clear_memory)
    r.add_post("/distributed/cluster/interrupt", cluster_interrupt)
    r.add_post("/distributed/profile/start", profile_start)
    r.add_post("/distributed/profile/stop", profile_stop)
    r.add_get("/distributed/profile/status", profile_status)
    r.add_post("/distributed/clear_memory", clear_memory)
    r.add_post("/distributed/launch_worker", launch_worker)
    r.add_post("/distributed/stop_worker", stop_worker)
    r.add_get("/distributed/managed_workers", managed_workers)
    r.add_get("/distributed/worker_log", worker_log)
    r.add_post("/distributed/worker/clear_launching", clear_launching)
    r.add_post("/distributed/prepare_job", prepare_job)
    r.add_get("/distributed/queue_status", queue_status)
    r.add_get("/distributed/wire_formats", wire_formats)
    r.add_post("/distributed/job_complete", job_complete)
    r.add_post("/distributed/tile_complete", tile_complete)
    r.add_post("/distributed/load_image", load_image)
    r.add_get("/distributed/preview/{prompt_id}", preview_stream)
    r.add_get("/prompt", get_prompt)
    r.add_post("/prompt", post_prompt)
    r.add_post("/interrupt", interrupt)
    r.add_get("/panel", panel)
    r.add_post("/upload/image", upload_image)
    r.add_get("/history", history)
    return app


def serve(host: str = "0.0.0.0", port: int = 8288,
          state: Optional[ServerState] = None,
          auto_launch: bool = True) -> None:
    """Blocking server entry point."""
    state = state or ServerState()
    state.port = port
    # optional startup warmup — DTPU_WARMUP='{"ckpt_name": ..., "width":
    # ..., ...}' AOT-compiles the serving shape before the first request
    # NOTE: the warmup thread compiles while the server is already
    # accepting requests; jax.monitoring events are process-wide, so a
    # prompt executed DURING warmup may report the warmup's traces in its
    # ExecutionResult.retraces — read the zero-retrace steady-state
    # signal only after warmup completes (its completion is logged).
    warmup_spec = os.environ.get("DTPU_WARMUP")
    if warmup_spec and not state.is_worker:
        def startup_warmup():
            try:
                spec = json.loads(warmup_spec)
                from comfyui_distributed_tpu.models import registry
                ckpt = spec.pop("ckpt_name", "model.safetensors")
                registry.load_pipeline(
                    ckpt, models_dir=state.models_dir).warmup(**spec)
            except Exception as e:  # noqa: BLE001 - warmup is best-effort
                log(f"startup warmup failed: {type(e).__name__}: {e}")

        threading.Thread(target=startup_warmup, daemon=True,
                         name="dtpu-warmup").start()
    app = build_app(state)
    if not state.is_worker:
        # master-IP autodetect: save the recommended private-range IP as
        # master.host when unset (reference detectMasterIP/saveMasterIp,
        # gpupanel.js:2114-2190) so dispatched remote workers can reach us.
        # Skipped when binding loopback-only — the LAN IP would then be
        # unreachable and 127.0.0.1 (the master_url fallback) is correct.
        if host not in ("127.0.0.1", "localhost"):
            def autodetect(cfg):
                if not cfg.get("master", {}).get("host"):
                    cfg.setdefault("master", {})["host"] = \
                        net_mod.get_recommended_ip()
            cfg_mod.mutate_config(autodetect, state.config_path)
        state.health.start()
        # elastic fleet (ISSUE 9): DTPU_AUTOSCALE=1 arms the
        # reconciliation loop — spawn on sustained queue/utilization
        # pressure, retire by drain + lease non-renewal
        state.autoscaler = autoscale_mod.install(state)
    if auto_launch and not state.is_worker:
        auto_launch_workers(state.manager)
    if state.is_worker:
        # renew this worker's lease at the master (spawned workers
        # inherit DTPU_MASTER_URL/DTPU_WORKER_ID from the process
        # manager; elastic workers export them by hand)
        state.heartbeat = cluster_mod.maybe_start_heartbeat(port=port)
    role = "worker" if state.is_worker else "master"
    log(f"{role} server listening on {host}:{port}")
    web.run_app(app, host=host, port=port, print=None)
