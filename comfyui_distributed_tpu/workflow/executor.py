"""Workflow executor: topo-ordered op execution over the mesh runtime.

The replacement for ComfyUI's graph executor plus the reference's
browser-side fan-out (``gpupanel.js:836-941``): where the reference dispatches
a pruned copy of the graph to every worker process, this executor runs the
graph once and lets the distributed ops expand/shard the batch over the mesh
(SPMD mode).  The HTTP worker/master modes reuse the same executor with
different context flags — the dispatcher module prepares those graphs.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from comfyui_distributed_tpu.ops.base import CBCapture, OpContext, get_op
from comfyui_distributed_tpu.utils import resource as resource_mod
from comfyui_distributed_tpu.utils import trace as trace_mod
from comfyui_distributed_tpu.utils.constants import \
    DISTRIBUTED_NODE_TYPES as DISTRIBUTED_TYPES
from comfyui_distributed_tpu.workflow.graph import (
    Graph, connected_component, parse_workflow)
from comfyui_distributed_tpu.utils.logging import debug_log, log


@dataclasses.dataclass
class ExecutionResult:
    outputs: Dict[str, Tuple]            # node id -> op outputs
    images: List[np.ndarray]             # all Preview/Save collected images
    timings: Dict[str, float]            # node id -> seconds
    total_s: float = 0.0
    # per-node host<->device transfer accounting for THIS run (node id ->
    # {d2h_bytes, d2h_calls, h2d_bytes, h2d_calls}): the proof that the
    # tensor plane stayed on device between ops — zero d2h on the
    # KSampler->VAEDecode->Collector spine, fetches only at true host
    # edges (SaveImage/Preview/HTTP wire)
    transfers: Dict[str, Dict[str, float]] = \
        dataclasses.field(default_factory=dict)
    # jit traces / XLA compiles observed during this run; a repeated
    # workflow must report {"traces": 0, "compiles": 0}
    retraces: Dict[str, int] = dataclasses.field(default_factory=dict)
    # overlapped pipeline: OUTPUT-node host edges still in flight on the
    # host-IO pool (one future per collecting node, submission order =
    # topo order).  ``images`` is complete only after wait_host().
    image_futures: List[Any] = dataclasses.field(default_factory=list)
    # prompts merged into this run by the coalescing scheduler
    coalesced: int = 1
    # per-run resource attribution (ISSUE 5): device memory high-water
    # delta + absolute end-of-run gauges and host RSS, tagged with the
    # probe source ("memory_stats" on real devices, "host_rss" on
    # backends whose devices report None).  The same numbers land as
    # attrs on the run's execute span, so `cli trace` shows HBM next to
    # latency.
    resources: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # node id -> {"peak_delta_bytes", "in_use_delta_bytes"}: which node
    # pushed the high-water mark (peak deltas are against the running
    # maximum, so only new highs attribute — honest, not double-counted)
    node_memory: Dict[str, Dict[str, int]] = \
        dataclasses.field(default_factory=dict)
    # the run's live TransferStats: deferred host fetches record into it
    # AFTER the compute-time snapshot, so wait_host re-snapshots
    _transfer_stats: Any = None

    def wait_host(self, timeout: Optional[float] = None
                  ) -> "ExecutionResult":
        """Join deferred host work (d2h/encode/disk) into ``images``.
        Raises whatever the host-side closure raised."""
        futures, self.image_futures = self.image_futures, []
        for f in futures:
            out = f.result(timeout)
            if out:
                self.images.extend(out)
        if futures and self._transfer_stats is not None:
            self.transfers = self._transfer_stats.snapshot()
        return self

    @property
    def image_batch(self) -> Optional[np.ndarray]:
        if not self.images:
            return None
        return np.stack(self.images, axis=0)

    def host_transfer_bytes(self, direction: str = "d2h",
                            nodes: Optional[List[str]] = None) -> int:
        """Total transferred bytes for the run (optionally restricted to a
        node subset)."""
        items = self.transfers.items() if nodes is None else \
            ((n, self.transfers.get(n, {})) for n in nodes)
        return int(sum(v.get(f"{direction}_bytes", 0) for _, v in items))


class WorkflowExecutor:
    def __init__(self, ctx: Optional[OpContext] = None):
        self.ctx = ctx or OpContext()

    def _decide_fanout(self, graph: Graph) -> int:
        """Distributed path only when the graph contains a distributed node
        and this process is the master — mirroring the browser interceptor's
        routing condition (reference ``gpupanel.js:826-833``)."""
        if self.ctx.is_worker:
            return 1
        if not graph.find_by_type(*DISTRIBUTED_TYPES):
            return 1
        if self.ctx.runtime is None:
            return 1
        return max(self.ctx.runtime.num_participants, 1)

    def execute(self, workflow: Any,
                hidden: Optional[Dict[str, Dict[str, Any]]] = None,
                extra_pnginfo: Optional[Dict[str, Any]] = None,
                cb_capture: Optional[Dict[str, Any]] = None,
                prompt_json: Optional[Any] = None
                ) -> ExecutionResult:
        """Run a workflow (path/JSON/dict/Graph).  ``hidden`` optionally maps
        node id -> hidden-input overrides (the dispatcher's injections).
        ``extra_pnginfo`` (ComfyUI contract, typically
        ``{"workflow": <UI-format doc>}``) is embedded by SaveImage into
        every saved PNG alongside the API-format prompt.

        ``cb_capture`` (continuous batching, workflow/batch_executor.py):
        a dict arms the prefix-capture run — the graph executes UP TO
        its KSampler, which records its resolved inputs into the dict
        and stops the walk (ops.base.CBCapture); the returned result
        then holds only the prefix outputs, and nothing downstream of
        the sampler has run.

        ``prompt_json`` overrides the API-format document SaveImage
        embeds in PNG metadata (default: this graph's own) — the
        continuous-batching tail executes a PRUNED decode graph but
        must embed the client's FULL prompt for provenance."""
        graph = workflow if isinstance(workflow, Graph) \
            else parse_workflow(workflow)
        hidden = hidden or {}
        # cross-request compute reuse (runtime/reuse.py): each
        # addressable node's input-sub-graph content hash, computed as
        # the walk reaches the node (a STRING another node produced is
        # then known, and keyed as the text it is); the encode ops key
        # their device memo caches on it.  DTPU_CACHE=0 skips it
        # entirely (kill switch).
        from comfyui_distributed_tpu.runtime import reuse as reuse_mod
        reuse_on = reuse_mod.reuse_enabled()
        reuse_keys: Dict[str, str] = {}
        resolved_text: Dict[Tuple[str, int], str] = {}
        # fresh per-run collection state (assign, don't clear — prior
        # ExecutionResults keep their own lists)
        self.ctx.saved_images = []
        self.ctx.image_futures = []
        self.ctx.prompt_json = prompt_json if prompt_json is not None \
            else graph.to_api_format()
        # coalesced runs: SaveImage rebuilds per-prompt metadata from the
        # per-prompt widget overrides (coalesced_seeds etc.), so every
        # saved PNG embeds ITS prompt's values, not prompt 0's
        self.ctx.hidden_overrides = dict(hidden)
        self.ctx.extra_pnginfo = extra_pnginfo
        self.ctx.cb_capture = cb_capture
        fanout = self._decide_fanout(graph)
        fan_nodes = None
        if fanout > 1:
            # fan out ONLY the distributed connected component — the SPMD
            # analog of the reference pruning workers to that component
            # (gpupanel.js:1045-1071): a side branch with no distributed
            # node runs once, not fanout times
            fan_nodes = connected_component(
                graph, graph.find_by_type(*DISTRIBUTED_TYPES))
            log(f"distributed run: fan-out x{fanout} over mesh "
                f"data axis ({len(fan_nodes)}/{len(graph.nodes)} nodes)")

        outputs: Dict[str, Tuple] = {}
        timings: Dict[str, float] = {}
        # per-run transfer/retrace accounting: every device edge in the
        # ops layer reports through utils.trace; attribute to the
        # executing node and keep a run-local ledger alongside the
        # process-global one
        trace_mod.install_jax_monitoring()
        run_transfers = trace_mod.TransferStats()
        retrace_mark = trace_mod.GLOBAL_RETRACES.mark()
        # DTPU_RESOURCE=0 is the plane's kill switch: it must also cover
        # the attribution probes (one per node + two per run) on the hot
        # serving path, not just the monitor thread
        res_on = resource_mod.resource_enabled()
        mem_start = resource_mod.device_memory_snapshot() if res_on else None
        rss_start = resource_mod.host_rss_bytes() if res_on else 0
        node_memory: Dict[str, Dict[str, int]] = {}
        prev_node_mem = mem_start
        t_start = time.perf_counter()

        with trace_mod.transfer_sink(run_transfers):
            for nid in graph.topo_order():
                self.ctx.fanout = fanout if (fan_nodes is None
                                             or nid in fan_nodes) else 1
                node = graph.nodes[nid]
                op = get_op(node.class_type)
                if reuse_on:
                    key = reuse_mod.node_key(
                        graph, nid, hidden, reuse_keys, self.ctx.input_dir,
                        self.ctx.models_dir, resolved_text)
                    if key is not None:
                        reuse_keys[nid] = key
                self.ctx.content_key = reuse_keys.get(nid)
                kwargs: Dict[str, Any] = {}
                for name, value in node.inputs.items():
                    if name == "__widgets__":
                        continue
                    if isinstance(value, (list, tuple)) and len(value) == 2 \
                            and not isinstance(value[0], (list, dict)) \
                            and isinstance(value[1], int) \
                            and str(value[0]) in graph.nodes:
                        src, slot = str(value[0]), int(value[1])
                        kwargs[name] = outputs[src][slot]
                    else:
                        kwargs[name] = value
                # hidden inputs: graph-embedded first, then per-run
                # overrides
                for hname, hval in {**node.hidden,
                                    **hidden.get(nid, {})}.items():
                    if hname in op.HIDDEN:
                        kwargs[hname] = hval
                debug_log(f"exec node {nid} ({node.class_type})")
                t0 = time.perf_counter()
                # the previous node's end snapshot (the run-start one for
                # the first node) IS this node's start snapshot — one
                # probe per boundary, not two
                node_mem0 = prev_node_mem
                try:
                    # node-scoped telemetry: transfer attribution + a
                    # child span in the active request trace (no-op
                    # outside a job)
                    with trace_mod.node_scope(nid), \
                            trace_mod.span(node.class_type,
                                           node=nid) as nsp:
                        outputs[nid] = op.execute(self.ctx, **kwargs)
                        if res_on:
                            node_mem1 = \
                                resource_mod.device_memory_snapshot()
                            mem_delta = {
                                "peak_delta_bytes": max(
                                    node_mem1["peak_bytes_in_use"]
                                    - node_mem0["peak_bytes_in_use"], 0),
                                "in_use_delta_bytes":
                                    node_mem1["bytes_in_use"]
                                    - node_mem0["bytes_in_use"],
                            }
                            prev_node_mem = node_mem1
                            node_memory[nid] = mem_delta
                            if nsp is not None \
                                    and mem_delta["peak_delta_bytes"]:
                                nsp.attrs["mem_peak_mb"] = round(
                                    mem_delta["peak_delta_bytes"] / 1e6,
                                    2)
                except CBCapture:
                    # bucket-build prefix run: the sampler recorded its
                    # inputs into ctx.cb_capture — stop the walk here so
                    # the graph tail (decode/save) does NOT run
                    break
                timings[nid] = time.perf_counter() - t0
                for slot, out in enumerate(outputs[nid]):
                    if isinstance(out, str):
                        resolved_text[(nid, slot)] = out
                # per-node-type latency histogram (p50/p95/p99 on
                # /distributed/metrics and the dtpu_node_seconds family)
                trace_mod.GLOBAL_NODES.record(node.class_type, timings[nid])

        total = time.perf_counter() - t_start
        self.ctx.node_timings.update(timings)
        resources: Dict[str, Any] = {}
        if res_on:
            mem_end = resource_mod.device_memory_snapshot()
            rss_end = resource_mod.host_rss_bytes()
            resources = {
                "source": mem_end["source"],
                "device_bytes_in_use": mem_end["bytes_in_use"],
                "device_peak_bytes": mem_end["peak_bytes_in_use"],
                "device_peak_delta_bytes": max(
                    mem_end["peak_bytes_in_use"]
                    - mem_start["peak_bytes_in_use"], 0),
                "host_rss_bytes": rss_end,
                "host_rss_delta_bytes": rss_end - rss_start,
            }
        sp = trace_mod.current_span()
        if sp is not None and res_on:
            # the run executes under the job's "execute" span — stamping
            # memory here puts HBM next to latency in the trace tree
            sp.attrs["device_peak_mb"] = round(
                resources["device_peak_bytes"] / 1e6, 2)
            sp.attrs["mem_peak_delta_mb"] = round(
                resources["device_peak_delta_bytes"] / 1e6, 2)
            sp.attrs["rss_mb"] = round(rss_end / 1e6, 2)
            sp.attrs["mem_source"] = resources["source"]
        return ExecutionResult(
            outputs=outputs,
            images=list(self.ctx.saved_images),
            timings=timings, total_s=total,
            transfers=run_transfers.snapshot(),
            retraces=trace_mod.GLOBAL_RETRACES.since(retrace_mark),
            image_futures=list(self.ctx.image_futures),
            coalesced=max(int(getattr(self.ctx, "coalesce", 1)), 1),
            resources=resources,
            node_memory=node_memory,
            _transfer_stats=run_transfers)
