"""Distributed ops: DistributedSeed + DistributedCollector.

Reference: ``distributed.py:1462-1514`` (seed) and ``:1222-1459``
(collector).  Three execution modes:

1. **SPMD (mesh) mode** — the default single-process path: the batch was
   expanded over the data axis by EmptyLatentImage, seeds got per-replica
   offsets in KSampler, and collection is simply fetching the (already
   replica-major-ordered) batch to host.  No serialization, no queues, no
   timeouts — the XLA program *is* the data plane.
2. **Worker (HTTP) mode** — multi-host parity path: PNG-POST every image to
   the master's ``/distributed/job_complete`` (reference
   ``send_image_to_master``, ``distributed.py:1254-1279``).
3. **Master (HTTP) mode** — drain the per-job asyncio queue with timeouts,
   order master-first then by worker id, concatenate (reference
   ``execute`` master branch, ``distributed.py:1292-1459``).
"""

from __future__ import annotations

import asyncio
import json
from typing import Any, Dict

import jax
import numpy as np

from comfyui_distributed_tpu.ops.base import (
    CONTROL,
    DeviceImage,
    DeviceTensor,
    Op,
    OpContext,
    SeedValue,
    as_device_image,
    as_image_array,
    fanout_meta,
    register_op,
)
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils import trace as trace_mod
from comfyui_distributed_tpu.utils.image import encode_png
from comfyui_distributed_tpu.utils.logging import Timer, debug_log, log
from comfyui_distributed_tpu.utils.net import post_form_with_retry, run_async_in_loop


def parse_worker_index(worker_id: str) -> int:
    """'worker_3' -> 3 (reference parses the same string form,
    ``distributed.py:1500-1505``)."""
    try:
        return int(str(worker_id).rsplit("_", 1)[-1])
    except (ValueError, IndexError):
        return 0


@register_op
class DistributedSeed(Op):
    """Master passes the seed through; worker ``i`` gets ``seed + i + 1``.
    In SPMD mode it returns a SeedValue that tells KSampler to apply
    per-replica offsets (replica 0 = master = base seed)."""
    TYPE = "DistributedSeed"
    WIDGETS = ["seed", CONTROL]
    HIDDEN = ["is_worker", "worker_id"]

    def execute(self, ctx: OpContext, seed,
                is_worker=None, worker_id=None):
        base = int(seed)
        is_worker = ctx.is_worker if is_worker is None else is_worker
        worker_id = ctx.worker_id if worker_id is None else worker_id
        if is_worker:
            offset = parse_worker_index(worker_id) + 1
            debug_log(f"DistributedSeed worker {worker_id}: "
                      f"{base} -> {base + offset}")
            return (SeedValue(base + offset, distributed=False),)
        return (SeedValue(base, distributed=True),)


@register_op
class DistributedCollector(Op):
    TYPE = "DistributedCollector"
    # worker_batch_size is accepted for schema parity; completion is driven
    # by per-worker is_last flags, not expected counts (reference
    # distributed.py:1366-1368 does the same).
    HIDDEN = ["multi_job_id", "is_worker", "master_url",
              "enabled_worker_ids", "worker_batch_size", "worker_id",
              "pass_through", "dispatch_attempt"]

    def execute(self, ctx: OpContext, images, multi_job_id="",
                is_worker=None, master_url="", enabled_worker_ids="[]",
                worker_batch_size=1, worker_id="", pass_through=False,
                dispatch_attempt=0):
        if pass_through:
            # downstream of a distributed upscaler: tiles were already
            # collected there (reference gpupanel.js:1146-1154); keep the
            # value's residency — normalizing through host here would be
            # a gratuitous fetch
            if isinstance(images, DeviceTensor):
                return (images,)
            return (as_image_array(images),)
        is_worker = ctx.is_worker if is_worker is None else is_worker

        if is_worker and (master_url or ctx.master_url):
            # true host edge: the images leave this process as PNGs
            arr = as_image_array(images)
            self._send_to_master(ctx, arr, multi_job_id,
                                 master_url or ctx.master_url,
                                 worker_id or ctx.worker_id,
                                 attempt=int(dispatch_attempt or 0))
            return (arr,)

        if multi_job_id and ctx.job_store is not None:
            # true host edge: remote results arrive over HTTP and
            # concatenate with ours on host
            with trace_mod.stage("gather"):
                gathered = self._collect_http(
                    ctx, as_image_array(images), multi_job_id,
                    enabled_worker_ids)
            return (gathered,)

        # SPMD mode: batch already replica-major (master first) by
        # construction — ordering parity with distributed.py:1424-1438.
        # For a device-resident batch the gather is an IN-PROGRAM device
        # operation: the timer measures the actual wait for the sharded
        # batch (flushing XLA's async dispatch), not a host no-op copy,
        # and the batch STAYS on device — downstream ops (tiled upscaler,
        # SaveImage) pull it to host only at their own true edges.  A
        # batch that already lives on host (an image-space numpy op
        # upstream) stays host — uploading it just to re-fetch would ADD
        # a full-batch round trip.
        with Timer("collector_gather"), trace_mod.stage("gather"):
            if isinstance(images, (DeviceTensor, jax.Array)):
                gathered = as_device_image(images)
                if ctx.host_pool is None:
                    # serial path: flush XLA's async dispatch here so the
                    # timer measures the real wait for the sharded batch
                    with trace_mod.device_wait():
                        gathered = jax.block_until_ready(gathered)
                # overlapped pipeline: do NOT synchronize at this op
                # boundary — the deferred host edge (PNG/HTTP in the
                # host-IO pool) absorbs the wait while the next job's
                # compute dispatches
                out = DeviceImage(gathered, **fanout_meta(images))
            else:
                out = as_image_array(images)
        if getattr(images, "fanout", 1) > 1:
            debug_log(f"collector: gathered {out.shape[0]} images from "
                      f"{images.fanout} mesh replicas")
        return (out,)

    # --- worker HTTP path ---------------------------------------------------

    def _send_to_master(self, ctx: OpContext, arr: np.ndarray,
                        multi_job_id: str, master_url: str, worker_id: str,
                        attempt: int = 0):
        """Pipelined upload: image i+1's encode runs on an executor
        thread WHILE image i's POST is in flight (double-buffering), and
        the payload format is negotiated per master — raw tensor
        (npy+zstd/deflate, no quantize/filter pass) when the master
        advertises it, PNG otherwise."""
        from comfyui_distributed_tpu.utils.image import encode_tensor
        from comfyui_distributed_tpu.utils.net import (
            negotiate_wire_format, wire_codec)

        # the executing thread's span context must be re-entered inside
        # the server-loop coroutine: contextvars do not follow
        # run_coroutine_threadsafe (the span analog of the transfer
        # context HostIOPool carries across its handoff)
        captured_span = trace_mod.capture_span_context()

        async def send_all():
            with trace_mod.use_span(captured_span):
                await send_body()

        async def send_body():
            fmt = await negotiate_wire_format(master_url)
            codec = wire_codec(master_url)
            loop = asyncio.get_running_loop()
            n = arr.shape[0]
            trace_id = (captured_span.trace_id
                        if captured_span is not None else None)

            def prep(i):
                # run_in_executor does NOT propagate contextvars: re-enter
                # the job's span context on the pool thread or the encode
                # span would silently fall out of the trace
                with trace_mod.use_span(captured_span), \
                        trace_mod.stage("encode"):
                    if fmt == C.TENSOR_WIRE_CONTENT_TYPE:
                        return (encode_tensor(arr[i:i + 1], codec),
                                fmt, "dtt")
                    return encode_png(arr[i:i + 1]), "image/png", "png"

            nxt = loop.run_in_executor(None, prep, 0)
            for i in range(n):
                payload, ctype, ext = await nxt
                if i + 1 < n:  # prefetch: encode i+1 during i's upload
                    nxt = loop.run_in_executor(None, prep, i + 1)

                def make_form(i=i, payload=payload, ctype=ctype, ext=ext):
                    import aiohttp
                    form = aiohttp.FormData()
                    form.add_field("multi_job_id", multi_job_id)
                    form.add_field("worker_id", str(worker_id))
                    form.add_field("image_index", str(i))
                    # stable across post_form_with_retry resends of THIS
                    # send, distinct across dispatch attempts — JobStore
                    # dedupes replays so a timed-out-but-delivered POST
                    # can't double-insert
                    form.add_field("idem_key",
                                   f"{worker_id}:{i}:{attempt}")
                    form.add_field("is_last", "true" if i == n - 1
                                   else "false")
                    if i == n - 1 and trace_id:
                        # ship this process's spans for the job on the
                        # final upload: the master merges them into its
                        # flight-recorder tree, so ONE master-side GET
                        # reconstructs the full fan-out (the still-open
                        # execute/job spans go provisional)
                        form.add_field("spans", json.dumps(
                            trace_mod.GLOBAL_TRACES.export(trace_id)))
                    form.add_field("image", payload,
                                   filename=f"img_{i}.{ext}",
                                   content_type=ctype)
                    return form

                # retry with backoff — absorbs transient master stalls and
                # the prepare-race 404 exactly like the tile path
                with trace_mod.stage("upload"):
                    await post_form_with_retry(
                        f"{master_url}/distributed/job_complete", make_form,
                        timeout=C.TILE_SEND_TIMEOUT, what="job_complete",
                        headers=trace_mod.traceparent_headers())

        if ctx.server_loop is not None:
            run_async_in_loop(send_all(), ctx.server_loop,
                              timeout=C.JOB_COMPLETION_TIMEOUT)
        else:
            asyncio.run(send_all())
        log(f"worker {worker_id}: sent {arr.shape[0]} images for job "
            f"{multi_job_id}")

    # --- master HTTP path ---------------------------------------------------

    def _collect_http(self, ctx: OpContext, master_images: np.ndarray,
                      multi_job_id: str, enabled_worker_ids: str):
        from comfyui_distributed_tpu.runtime import cluster as cluster_mod
        worker_ids = [str(w) for w in json.loads(enabled_worker_ids or "[]")]
        # the wire carries positional labels ("worker_i"); the ledger and
        # registry speak config ids — enabled order maps between them
        pos_map = {f"worker_{i}": wid for i, wid in enumerate(worker_ids)}
        ledger = ctx.ledger
        registry = ctx.cluster
        policy = cluster_mod.fault_policy()
        if ledger is not None:
            # one ledger unit per seed slice (worker): a worker's slice is
            # complete when its is_last image checks in
            ledger.create_job(multi_job_id,
                              {wid: wid for wid in worker_ids},
                              kind="image")
        # crash recovery (durability plane): slices that completed (and
        # spilled) before the old master died are blended from disk,
        # never re-rendered; a missing payload downgrades the unit to
        # pending HERE, before the drain decides what is outstanding
        recovered_slices = ledger.load_payloads(multi_job_id) \
            if ledger is not None else {}
        captured_span = trace_mod.capture_span_context()

        async def drain():
            q = await ctx.job_store.get_queue(multi_job_id)
            # keyed by (worker, image_index): the worker's send path retries
            # with backoff, so a timed-out-but-delivered POST arrives twice —
            # last write wins instead of duplicating an image in the batch
            # (the JobStore's idempotency dedupe catches most replays
            # upstream; this keying is the in-batch backstop).  Indexless
            # senders get per-worker arrival numbers (sorted after any
            # indexed uploads) so their images are all preserved.
            results: Dict[str, Dict[tuple, Any]] = {}
            arrival: Dict[str, int] = {}
            done = set()
            handled_dead = set()
            # deadline inside the loop: hitting it still returns the partial
            # batch (parity with reference distributed.py:1372-1412); an
            # outer cancellation would discard it
            loop = asyncio.get_running_loop()
            deadline = loop.time() + C.JOB_COMPLETION_TIMEOUT
            # redispatch extensions stay below the outer backstop:
            # blowing past it would cancel the drain and discard the
            # partial batch the deadline semantics exist to save
            hard_deadline = loop.time() + 2 * C.JOB_COMPLETION_TIMEOUT \
                + C.WORKER_JOB_TIMEOUT
            last_progress = loop.time()
            # the master cannot regenerate another participant's seed
            # slice in-op (no model access here) — recovery for image
            # jobs is redispatch-only, so short polls are only worth it
            # when the orchestrator registered a redispatcher
            can_recover = (ledger is not None and registry is not None
                           and policy != "partial"
                           and ledger.has_redispatcher(multi_job_id))
            hedge_on = (cluster_mod.hedge_armed() and ledger is not None
                        and ledger.has_redispatcher(multi_job_id))
            poll_s = C.CLUSTER_POLL_S if (can_recover or hedge_on) \
                else C.WORKER_JOB_TIMEOUT

            async def recover_units(units, owner, reason):
                with trace_mod.use_span(captured_span), \
                        trace_mod.span(reason, job=multi_job_id,
                                       lost=str(owner)):
                    return await ledger.redispatch(multi_job_id,
                                                   list(units), owner)

            # crash recovery: pending units of a recovered job were
            # dispatched by the DEAD master — their owners will never
            # send.  The master cannot regenerate another slice in-op,
            # so this is redispatch-or-partial, decided NOW instead of
            # after the no-progress timeout.
            stale = ledger.take_recovered_lost(multi_job_id) \
                if can_recover else {}
            try:
                for owner, units in stale.items():
                    if policy == "fail":
                        raise cluster_mod.ClusterFaultError(
                            f"recovered job {multi_job_id} lost slices "
                            f"{sorted(units)} with the old master "
                            f"({C.FAULT_POLICY_ENV}=fail)")
                    log(f"collector: recovered job {multi_job_id}: "
                        f"re-issuing slices {sorted(units)} stranded "
                        f"on {owner}")
                    if await recover_units(units, owner, "reassign"):
                        deadline = min(max(
                            deadline,
                            loop.time() + C.JOB_COMPLETION_TIMEOUT / 2),
                            hard_deadline)
                        last_progress = loop.time()
                while True:
                    if ledger is not None:
                        if not ledger.pending(multi_job_id):
                            break
                    elif len(done) >= len(worker_ids):
                        break
                    remaining = deadline - loop.time()
                    if remaining <= 0:
                        done_cfg = {pos_map.get(w, w) for w in done}
                        log(f"collector: collection deadline, missing "
                            f"{set(worker_ids) - done_cfg}; continuing "
                            f"partial")
                        break
                    if ledger is not None and registry is not None \
                            and policy != "partial":
                        # group pending units by their CURRENT owner
                        # (a reassigned unit's key is its original
                        # slice id, not its owner) and act on dead ones
                        dead_units: Dict[str, list] = {}
                        for u, o in ledger.owners_of_pending(
                                multi_job_id, skip_hedged=True).items():
                            if o not in handled_dead \
                                    and registry.state(o) \
                                    == cluster_mod.DEAD:
                                dead_units.setdefault(o, []).append(u)
                        for owner, units in dead_units.items():
                            handled_dead.add(owner)
                            if policy == "fail":
                                raise cluster_mod.ClusterFaultError(
                                    f"worker {owner} died before "
                                    f"delivering slices {sorted(units)} "
                                    f"of {multi_job_id} "
                                    f"({C.FAULT_POLICY_ENV}=fail)")
                            log(f"collector: worker {owner} lease "
                                f"expired; redispatching its slice")
                            if await recover_units(units, owner,
                                                   "reassign"):
                                deadline = min(max(
                                    deadline, loop.time()
                                    + C.JOB_COMPLETION_TIMEOUT / 2),
                                    hard_deadline)
                                last_progress = loop.time()
                            else:
                                log(f"collector: no healthy participant "
                                    f"for {owner}'s slice; will blend "
                                    f"partial")
                    if hedge_on:
                        for unit, owner in sorted(
                                ledger.overdue_units(
                                    multi_job_id).items(), key=str):
                            # off the loop: the hedge mark is a WAL
                            # append (+ fsync under sync=always)
                            hedged = await loop.run_in_executor(
                                None, lambda u=unit: ledger.mark_hedged(
                                    multi_job_id, [u]))
                            if not hedged:
                                continue
                            if await recover_units([unit], owner,
                                                   "hedge"):
                                log(f"collector: hedged straggler "
                                    f"{owner}'s slice")
                            else:
                                # a failed hedge must not pin the unit:
                                # hedged=True would exclude it from the
                                # dead-owner scan forever
                                ledger.unmark_hedged(multi_job_id,
                                                     [unit])
                    try:
                        item = await asyncio.wait_for(
                            q.get(), timeout=max(min(poll_s, remaining),
                                                 0.01))
                    except asyncio.TimeoutError:
                        if loop.time() - last_progress \
                                > C.WORKER_JOB_TIMEOUT:
                            # the wire labels in `done` are positional;
                            # map back to config ids before diffing
                            missing = set(worker_ids) - {
                                pos_map.get(w, w) for w in done}
                            log(f"collector: timeout, missing workers "
                                f"{missing}; continuing with partial "
                                f"results")
                            break
                        continue
                    last_progress = loop.time()
                    wid = str(item["worker_id"])
                    cfg_id = pos_map.get(wid, wid)
                    if registry is not None:
                        # touch the RAW wire label only: a positional
                        # "worker_N" label is unknown to the registry
                        # (no-op) — mapping it to the config id first
                        # would let a redispatched replacement,
                        # impersonating the dead owner's identity,
                        # resurrect the dead worker's lease
                        registry.touch(wid)
                    if "image_index" in item:
                        key = (0, int(item["image_index"]))
                    else:
                        arrival[wid] = n = arrival.get(wid, 0) + 1
                        key = (1, n)
                    results.setdefault(wid, {})[key] = item["tensor"]
                    if item.get("is_last"):
                        done.add(wid)
                        if ledger is not None:
                            # spill the whole slice with its batch keys
                            # so a recovered master re-orders the images
                            # exactly as this drain would have; off the
                            # loop — a WAL-backed check-in compresses
                            # the images and fsyncs
                            slot = results.get(wid, {})
                            keys = sorted(slot)
                            await loop.run_in_executor(
                                None, lambda: ledger.check_in(
                                    multi_job_id, cfg_id, cfg_id,
                                    payload=(
                                        [np.asarray(slot[k], np.float32)
                                         for k in keys],
                                        {"form": "slice", "wid": wid,
                                         "keys": [list(k)
                                                  for k in keys]})))
            finally:
                # drop the queue so late arrivals can't accumulate forever
                await ctx.job_store.remove_job(multi_job_id)
            return results

        # the collect span is the master-side half of the fan-out tree:
        # worker execute spans (ingested off the final job_complete POST)
        # hang next to it under the same trace_id
        try:
            with Timer("collector_http_drain"), \
                    trace_mod.span("collect", job=multi_job_id,
                                   n_workers=len(worker_ids)):
                # outer timeout is a backstop; the in-loop deadline governs
                results = run_async_in_loop(
                    drain(), ctx.server_loop,
                    timeout=2 * C.JOB_COMPLETION_TIMEOUT
                    + 2 * C.WORKER_JOB_TIMEOUT)
            if ledger is not None and policy == "fail":
                lost = ledger.pending(multi_job_id)
                if lost:
                    raise cluster_mod.ClusterFaultError(
                        f"slices {lost} of {multi_job_id} never arrived "
                        f"({C.FAULT_POLICY_ENV}=fail)")
        finally:
            if ledger is not None:
                summary = ledger.finish_job(multi_job_id)
                if summary and summary["pending_units"]:
                    log(f"collector: job {multi_job_id} finished with "
                        f"lost slices {summary['pending_units']} "
                        f"(policy={policy})")

        # blend the recovered slices back in under their original wire
        # labels (fresh arrivals — a redispatched redo — win over disk)
        for u, (tensors, meta) in recovered_slices.items():
            wid = str(meta.get("wid", u))
            slot = results.setdefault(wid, {})
            for k, t in zip(meta.get("keys", []), tensors):
                slot.setdefault(tuple(k), t)
        ordered = [master_images]
        for wid in sorted(results, key=lambda w: (parse_worker_index(w), w)):
            imgs = [results[wid][i] for i in sorted(results[wid])]
            ordered.extend(np.asarray(t, np.float32) for t in imgs)
        out = np.concatenate([as_image_array(o) for o in ordered], axis=0)
        log(f"collector: combined {out.shape[0]} images "
            f"(master {master_images.shape[0]} + {len(results)} workers)")
        return out
