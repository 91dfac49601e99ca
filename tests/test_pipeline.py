"""Overlapped execution pipeline (ISSUE 2): batch-coalescing scheduler,
compute/host-IO overlap, raw-tensor wire negotiation, backpressure and
graceful drain."""

import asyncio
import os
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.ops.base import OpContext
from comfyui_distributed_tpu.server.app import ServerState, build_app
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils import net as net_mod
from comfyui_distributed_tpu.utils import trace as trace_mod
from comfyui_distributed_tpu.utils.image import (
    decode_tensor,
    encode_png,
    encode_tensor,
)
from comfyui_distributed_tpu.workflow import scheduler as sched
from comfyui_distributed_tpu.workflow.executor import WorkflowExecutor


@pytest.fixture(autouse=True)
def tiny_family(monkeypatch):
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny")
    yield


def make_prompt(seed, steps=1, size=32, text="cat", batch=1):
    return {
        "7": {"class_type": "CheckpointLoaderSimple",
              "inputs": {"ckpt_name": "tiny.safetensors"}},
        "5": {"class_type": "CLIPTextEncode",
              "inputs": {"text": text, "clip": ["7", 1]}},
        "6": {"class_type": "CLIPTextEncode",
              "inputs": {"text": "", "clip": ["7", 1]}},
        "9": {"class_type": "EmptyLatentImage",
              "inputs": {"width": size, "height": size,
                         "batch_size": batch}},
        "8": {"class_type": "KSampler",
              "inputs": {"model": ["7", 0], "positive": ["5", 0],
                         "negative": ["6", 0], "latent_image": ["9", 0],
                         "seed": seed, "steps": steps, "cfg": 2.0,
                         "sampler_name": "euler", "scheduler": "normal",
                         "denoise": 1.0}},
        "1": {"class_type": "VAEDecode",
              "inputs": {"samples": ["8", 0], "vae": ["7", 2]}},
        "3": {"class_type": "PreviewImage", "inputs": {"images": ["1", 0]}},
    }


def make_state(tmp_path, **kw):
    return ServerState(config_path=str(tmp_path / "cfg.json"),
                       input_dir=str(tmp_path / "in"),
                       output_dir=str(tmp_path / "out"), **kw)


def wait_history(state, pids, timeout=120):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(p in state._history for p in pids):
            return {p: state._history[p] for p in pids}
        time.sleep(0.01)
    raise AssertionError(f"prompts never finished: "
                         f"{[p for p in pids if p not in state._history]}")


def staged_burst(state, prompts, client="c"):
    """Enqueue a burst behind the held exec gate so one pop sees it all."""
    state._exec_gate.clear()
    try:
        return [state.enqueue_prompt(p, client) for p in prompts]
    finally:
        state._exec_gate.set()


class TestCoalescingSignature:
    def test_seed_only_difference_shares_signature(self):
        a = sched.coalesce_signature(make_prompt(1))
        b = sched.coalesce_signature(make_prompt(999))
        assert a is not None and a == b

    def test_shape_affecting_widgets_split_signatures(self):
        base = sched.coalesce_signature(make_prompt(1))
        assert sched.coalesce_signature(make_prompt(1, steps=2)) != base
        assert sched.coalesce_signature(make_prompt(1, size=64)) != base
        assert sched.coalesce_signature(make_prompt(1, text="dog")) != base
        assert sched.coalesce_signature(make_prompt(1, batch=2)) != base

    def test_unsafe_graphs_are_not_coalescable(self):
        p = make_prompt(1)
        p["99"] = {"class_type": "DistributedCollector",
                   "inputs": {"images": ["1", 0]}}
        assert sched.coalesce_signature(p) is None
        # hidden orchestration state -> never merged
        p2 = make_prompt(1)
        p2["8"]["hidden"] = {"multi_job_id": "x"}
        assert sched.coalesce_signature(p2) is None
        # no EmptyLatentImage batch source -> no safe way to batch
        p3 = {k: v for k, v in make_prompt(1).items() if k != "9"}
        assert sched.coalesce_signature(p3) is None


class TestCoalescedExecution:
    def test_coalesced_matches_serial_per_prompt(self):
        """Per-prompt results survive batch splitting: the merged run's
        prompt-major chunks equal each prompt's own serial output (each
        prompt keeps its exact (seed, fold-idx) noise streams)."""
        seeds = [11, 22, 33, 44]
        serial = []
        for s in seeds:
            res = WorkflowExecutor(OpContext()).execute(make_prompt(s))
            serial.append(res.images)
        graph, hidden = sched.build_coalesced(
            [make_prompt(s) for s in seeds])
        assert hidden == {"8": {"coalesced_seeds": seeds}}
        ctx = OpContext()
        ctx.coalesce = len(seeds)
        res = WorkflowExecutor(ctx).execute(graph, hidden=hidden)
        chunks = sched.split_images(res.images, len(seeds))
        assert [len(c) for c in chunks] == [1, 1, 1, 1]
        for mine, theirs in zip(chunks, serial):
            for a, b in zip(mine, theirs):
                np.testing.assert_allclose(a, b, atol=1e-4)

    def test_burst_coalesces_into_one_dispatch(self, tmp_path,
                                               assert_nothing_compiled):
        """Acceptance: a 4-prompt signature-identical burst dispatches
        exactly ONE compiled execution (vs 4 serial) with nothing
        lowered or compiled once the shape is warm."""
        st = make_state(tmp_path, overlap=True, coalesce=True)
        # warm both shapes: batch-1 (single) and the coalesced batch-4
        wait_history(st, [st.enqueue_prompt(make_prompt(0), "warm")])
        wait_history(st, staged_burst(
            st, [make_prompt(50 + i) for i in range(4)]))
        runs0 = trace_mod.GLOBAL_COUNTERS.get("exec_runs")
        mark = trace_mod.GLOBAL_RETRACES.mark()
        hist = wait_history(st, staged_burst(
            st, [make_prompt(100 + i) for i in range(4)]))
        assert trace_mod.GLOBAL_COUNTERS.get("exec_runs") - runs0 == 1
        assert_nothing_compiled(trace_mod.GLOBAL_RETRACES.since(mark))
        for h in hist.values():
            assert h["status"] == "success"
            assert h["coalesced"] == 4 and h["images"] == 1
        assert st.drain(10)

    def test_mixed_signatures_keep_client_order(self, tmp_path):
        """Overlap/coalescing never reorders one client's prompts: only a
        CONTIGUOUS same-signature run coalesces, so a later compatible
        prompt cannot jump an incompatible one queued between them."""
        st = make_state(tmp_path, overlap=True, coalesce=True)
        wait_history(st, [st.enqueue_prompt(make_prompt(0), "warm")])
        # the middle prompt differs in TEXT — a different signature but
        # the same compiled program, so the test stays cheap cold
        a1, b, a2 = staged_burst(st, [make_prompt(1),
                                      make_prompt(2, text="dog"),
                                      make_prompt(3)])
        hist = wait_history(st, [a1, b, a2])
        assert all(h["status"] == "success" for h in hist.values())
        # a1 ran alone (b broke the contiguous run), then b, then a2
        assert "coalesced" not in hist[a1]
        assert hist[a1]["finished_at"] <= hist[b]["finished_at"]
        assert hist[b]["finished_at"] <= hist[a2]["finished_at"]
        assert st.drain(10)

    def test_failure_hits_only_its_group(self, tmp_path, monkeypatch):
        """An interrupt (or any failure) during a coalesced group fails
        that group's prompts only; the next group runs clean."""
        from comfyui_distributed_tpu.ops import basic as ops_basic
        real = ops_basic.KSampler.execute
        boom = {"on": True}

        def fake(self, ctx, *a, **kw):
            if boom["on"]:
                raise InterruptedError("execution interrupted (test)")
            return real(self, ctx, *a, **kw)

        monkeypatch.setattr(ops_basic.KSampler, "execute", fake)
        st = make_state(tmp_path, overlap=True, coalesce=True)
        pids = staged_burst(st, [make_prompt(200 + i) for i in range(3)])
        hist = wait_history(st, pids)
        for h in hist.values():
            assert h["status"] == "error" and h["coalesced"] == 3
            assert "interrupted" in h["error"]
        assert st.metrics["prompts_failed"] >= 3
        boom["on"] = False
        ok = wait_history(st, [st.enqueue_prompt(make_prompt(7), "c")])
        assert list(ok.values())[0]["status"] == "success"
        assert st.drain(10)


class TestOverlapInvariants:
    def test_one_dispatch_coalesced_four_serial_nothing_compiled_warm(
            self, tmp_path, monkeypatch, assert_nothing_compiled):
        """Acceptance: the same 4-prompt queue through two real exec
        loops is FOUR dispatches with overlap and coalescing off and ONE
        with them on, and the warm coalesced round lowers and compiles
        nothing."""
        # the exact-hit result cache would replay the second round's
        # identical prompts instead of dispatching them
        monkeypatch.setenv(C.CACHE_ENV, "0")
        burst = [make_prompt(100 + i) for i in range(4)]
        runs = trace_mod.GLOBAL_COUNTERS

        for d in ("serial", "overlapped"):
            (tmp_path / d).mkdir()
        st = make_state(tmp_path / "serial", overlap=False, coalesce=False)
        wait_history(st, [st.enqueue_prompt(make_prompt(1), "warm")])
        runs0 = runs.get("exec_runs")
        wait_history(st, staged_burst(st, burst))
        assert runs.get("exec_runs") - runs0 == 4
        assert st.drain(10)

        st = make_state(tmp_path / "overlapped", overlap=True, coalesce=True)
        wait_history(st, staged_burst(st, burst))       # compile batch-4
        runs0 = runs.get("exec_runs")
        batches0 = runs.get("coalesced_batches")
        mark = trace_mod.GLOBAL_RETRACES.mark()
        hist = wait_history(st, staged_burst(st, burst))
        assert all(h["status"] == "success" for h in hist.values())
        assert runs.get("exec_runs") - runs0 == 1
        assert runs.get("coalesced_batches") - batches0 == 1
        assert_nothing_compiled(trace_mod.GLOBAL_RETRACES.since(mark))
        assert st.drain(10)

    def test_spine_invariants_hold_under_overlapped_executor(
            self, assert_nothing_compiled):
        """PR 1's tensor-plane invariants survive the overlap: with host
        edges deferred to the pool, the KSampler->VAEDecode spine still
        moves zero d2h bytes and a repeated run still compiles nothing
        (the deferred fetch is attributed to the output node)."""
        pool = net_mod.HostIOPool(max_workers=2, max_pending=4)
        try:
            def run():
                ctx = OpContext(host_pool=pool)
                return WorkflowExecutor(ctx).execute(
                    make_prompt(5)).wait_host()

            run()
            res = run()
            assert len(res.images) == 1
            spine = ["8", "1"]          # KSampler, VAEDecode
            assert res.host_transfer_bytes("d2h", nodes=spine) == 0
            assert_nothing_compiled(res.retraces)
            # the deferred fetch was counted — against the output node
            assert res.host_transfer_bytes("d2h") > 0
            assert res.transfers.get("3", {}).get("d2h_bytes", 0) > 0
        finally:
            pool.shutdown()

    def test_coalesced_pngs_embed_their_own_seed(self, tmp_path):
        """Provenance: a coalesced run's saved PNGs each embed the
        metadata of THEIR prompt (seed re-applied from the scheduler's
        overrides), not prompt 0's — reloading any PNG reproduces its
        own image."""
        import json

        from PIL import Image
        seeds = [71, 72, 73]
        prompts = []
        for s in seeds:
            p = make_prompt(s)
            p["3"] = {"class_type": "SaveImage",
                      "inputs": {"images": ["1", 0],
                                 "filename_prefix": "prov"}}
            prompts.append(p)
        graph, hidden = sched.build_coalesced(prompts)
        ctx = OpContext(output_dir=str(tmp_path))
        ctx.coalesce = len(seeds)
        WorkflowExecutor(ctx).execute(graph, hidden=hidden).wait_host()
        names = sorted(os.listdir(tmp_path))
        assert len(names) == 3
        embedded = []
        for n in names:
            meta = json.loads(Image.open(tmp_path / n).info["prompt"])
            embedded.append(meta["8"]["inputs"]["seed"])
        assert embedded == seeds

    def test_deferred_save_writes_pngs(self, tmp_path):
        """SaveImage's disk write rides the pool but still lands, with
        continuing counters, once the run is joined."""
        pool = net_mod.HostIOPool()
        try:
            prompt = make_prompt(5)
            prompt["3"] = {"class_type": "SaveImage",
                           "inputs": {"images": ["1", 0],
                                      "filename_prefix": "ovl"}}
            ctx = OpContext(host_pool=pool, output_dir=str(tmp_path))
            WorkflowExecutor(ctx).execute(prompt).wait_host()
            ctx2 = OpContext(host_pool=pool, output_dir=str(tmp_path))
            WorkflowExecutor(ctx2).execute(prompt).wait_host()
            names = sorted(os.listdir(tmp_path))
            assert names == ["ovl_00000.png", "ovl_00001.png"]
        finally:
            pool.shutdown()


class TestWireFormat:
    def test_tensor_wire_roundtrip_bit_exact(self, rng):
        arr = rng.random((2, 9, 7, 3)).astype(np.float32)
        back = decode_tensor(encode_tensor(arr))
        assert back.dtype == np.float32
        np.testing.assert_array_equal(back, arr)  # BIT-exact, beyond PNG

    def test_negotiation_and_tensor_upload(self, tmp_path, rng):
        """A master advertising the raw-tensor type receives bit-exact
        tensors on /distributed/job_complete; a peer WITHOUT the
        wire_formats route negotiates down to PNG."""
        async def body():
            net_mod.reset_wire_cache()
            state = make_state(tmp_path, start_exec_thread=False)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            # a "legacy" peer: no /distributed/wire_formats route
            from aiohttp import web
            legacy = TestClient(TestServer(web.Application()))
            await legacy.start_server()
            try:
                url = str(client.server.make_url("")).rstrip("/")
                fmt = await net_mod.negotiate_wire_format(url)
                assert fmt == C.TENSOR_WIRE_CONTENT_TYPE
                # codec is the best one BOTH sides support (this build
                # talks to itself, so its own best decoder)
                from comfyui_distributed_tpu.utils.image import \
                    tensor_codecs
                assert net_mod.wire_codec(url) == tensor_codecs()[0]
                legacy_url = str(legacy.server.make_url("")).rstrip("/")
                assert await net_mod.negotiate_wire_format(legacy_url) \
                    == "image/png"

                await state.jobs.prepare_job("j1")
                img = rng.random((1, 8, 8, 3)).astype(np.float32)
                import aiohttp
                form = aiohttp.FormData()
                form.add_field("multi_job_id", "j1")
                form.add_field("worker_id", "worker_0")
                form.add_field("image_index", "0")
                form.add_field("is_last", "true")
                form.add_field("image", encode_tensor(img),
                               filename="img_0.dtt",
                               content_type=C.TENSOR_WIRE_CONTENT_TYPE)
                r = await client.post("/distributed/job_complete",
                                      data=form)
                assert r.status == 200
                q = await state.jobs.get_queue("j1")
                item = q.get_nowait()
                np.testing.assert_array_equal(item["tensor"], img)

                # PNG stays accepted on the same route (fallback path)
                await state.jobs.prepare_job("j2")
                form = aiohttp.FormData()
                form.add_field("multi_job_id", "j2")
                form.add_field("image", encode_png(img),
                               filename="img.png",
                               content_type="image/png")
                r = await client.post("/distributed/job_complete",
                                      data=form)
                assert r.status == 200
            finally:
                net_mod.reset_wire_cache()
                await legacy.close()
                await client.close()
        asyncio.run(body())

    def test_wire_env_forces_png(self, monkeypatch):
        async def body():
            net_mod.reset_wire_cache()
            monkeypatch.setenv(C.WIRE_FORMAT_ENV, "png")
            assert await net_mod.negotiate_wire_format(
                "http://127.0.0.1:1") == "image/png"
            net_mod.reset_wire_cache()
        asyncio.run(body())


class TestBackpressureAndDrain:
    def test_queue_cap_returns_429(self, tmp_path, monkeypatch):
        monkeypatch.setenv(C.MAX_QUEUE_ENV, "2")

        async def body():
            state = make_state(tmp_path, start_exec_thread=False)
            assert state.max_queue == 2
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                for i in range(2):
                    r = await client.post("/prompt", json={
                        "prompt": make_prompt(i), "client_id": "c"})
                    assert r.status == 200
                r = await client.post("/prompt", json={
                    "prompt": make_prompt(9), "client_id": "c"})
                assert r.status == 429
                body_json = await r.json()
                assert body_json["queue_remaining"] == 2
                assert body_json["max_queue"] == 2
                qs = await (await client.get(
                    "/distributed/queue_status")).json()
                assert qs["max_queue"] == 2
                assert qs["queue_remaining"] == 2
            finally:
                await client.close()
        asyncio.run(body())

    def test_drain_finishes_inflight_then_refuses(self, tmp_path):
        st = make_state(tmp_path, overlap=True, coalesce=True)
        pids = staged_burst(st, [make_prompt(300 + i) for i in range(4)])
        assert st.drain() is True
        hist = wait_history(st, pids, timeout=5)
        assert all(h["status"] == "success" for h in hist.values())
        with pytest.raises(RuntimeError, match="draining"):
            st.enqueue_prompt(make_prompt(1), "c")

    def test_metrics_expose_pipeline_block(self, tmp_path):
        async def body():
            state = make_state(tmp_path, start_exec_thread=False)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                m = await (await client.get("/distributed/metrics")).json()
                pipe = m["pipeline"]
                assert {"stages", "counters", "overlap", "coalesce",
                        "max_queue"} <= set(pipe)
                # the stage timeline carries the per-job stages once any
                # pipelined work ran in this process
                for key in ("queue_wait", "compute"):
                    if trace_mod.GLOBAL_STAGES.snapshot().get(key):
                        assert key in pipe["stages"]
            finally:
                await client.close()
        asyncio.run(body())
