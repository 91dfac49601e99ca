"""Cross-request compute reuse + SSE previews (ISSUE 13).

Covers the three cache tiers (exact-hit result, sub-graph embeddings /
VAE conditioning, changed-tile upscaling), the DTPU_CACHE_* budgets
(LRU order, ResourceMonitor residency ring, the DTPU_CACHE=0 kill
switch's zero-lookup guarantee), bit-identical cache-on vs cache-off
outputs with near-miss keys never hitting, and the preview/cancellation
channel (SSE frames from the CB denoise loop; client-gone abandonment
freeing the batch slot and purging queued copies).
"""

import asyncio
import base64
import json
import os
import queue as queue_mod
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.ops.base import OpContext
from comfyui_distributed_tpu.runtime import reuse as reuse_mod
from comfyui_distributed_tpu.server.app import ServerState, build_app
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils import resource as resource_mod
from comfyui_distributed_tpu.utils import trace as trace_mod
from comfyui_distributed_tpu.utils.image import encode_png
from comfyui_distributed_tpu.workflow.executor import WorkflowExecutor


@pytest.fixture(autouse=True)
def tiny_family(monkeypatch):
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny")
    yield


@pytest.fixture(autouse=True)
def fresh_plane():
    """Every test sees an empty plane built from ITS env pins, and
    leaves a clean one behind (the plane is process-global)."""
    plane = reuse_mod.reset_reuse()
    yield plane
    reuse_mod.reset_reuse()


def make_prompt(seed, steps=1, size=32, text="cat", cfg=2.0,
                sampler="euler"):
    return {
        "7": {"class_type": "CheckpointLoaderSimple",
              "inputs": {"ckpt_name": "tiny.safetensors"}},
        "5": {"class_type": "CLIPTextEncode",
              "inputs": {"text": text, "clip": ["7", 1]}},
        "6": {"class_type": "CLIPTextEncode",
              "inputs": {"text": "", "clip": ["7", 1]}},
        "9": {"class_type": "EmptyLatentImage",
              "inputs": {"width": size, "height": size, "batch_size": 1}},
        "8": {"class_type": "KSampler",
              "inputs": {"model": ["7", 0], "positive": ["5", 0],
                         "negative": ["6", 0], "latent_image": ["9", 0],
                         "seed": seed, "steps": steps, "cfg": cfg,
                         "sampler_name": sampler, "scheduler": "normal",
                         "denoise": 1.0}},
        "1": {"class_type": "VAEDecode",
              "inputs": {"samples": ["8", 0], "vae": ["7", 2]}},
        "3": {"class_type": "PreviewImage", "inputs": {"images": ["1", 0]}},
    }


def img2img_prompt(seed, name="cond.png", steps=1):
    return {
        "7": {"class_type": "CheckpointLoaderSimple",
              "inputs": {"ckpt_name": "tiny.safetensors"}},
        "5": {"class_type": "CLIPTextEncode",
              "inputs": {"text": "remix", "clip": ["7", 1]}},
        "6": {"class_type": "CLIPTextEncode",
              "inputs": {"text": "", "clip": ["7", 1]}},
        "10": {"class_type": "LoadImage", "inputs": {"image": name}},
        "11": {"class_type": "VAEEncode",
               "inputs": {"pixels": ["10", 0], "vae": ["7", 2]}},
        "8": {"class_type": "KSampler",
              "inputs": {"model": ["7", 0], "positive": ["5", 0],
                         "negative": ["6", 0], "latent_image": ["11", 0],
                         "seed": seed, "steps": steps, "cfg": 2.0,
                         "sampler_name": "euler", "scheduler": "normal",
                         "denoise": 0.6}},
        "1": {"class_type": "VAEDecode",
              "inputs": {"samples": ["8", 0], "vae": ["7", 2]}},
        "3": {"class_type": "PreviewImage", "inputs": {"images": ["1", 0]}},
    }


def upscale_prompt(seed=7, denoise=0.4, name="src.png"):
    return {
        "7": {"class_type": "CheckpointLoaderSimple",
              "inputs": {"ckpt_name": "tiny.safetensors"}},
        "5": {"class_type": "CLIPTextEncode",
              "inputs": {"text": "a map", "clip": ["7", 1]}},
        "6": {"class_type": "CLIPTextEncode",
              "inputs": {"text": "", "clip": ["7", 1]}},
        "10": {"class_type": "LoadImage", "inputs": {"image": name}},
        "2": {"class_type": "UltimateSDUpscaleDistributed",
              "inputs": {"upscaled_image": ["10", 0], "model": ["7", 0],
                         "positive": ["5", 0], "negative": ["6", 0],
                         "vae": ["7", 2], "seed": seed, "steps": 1,
                         "cfg": 2.0, "sampler_name": "euler",
                         "scheduler": "normal", "denoise": denoise,
                         "tile_width": 32, "tile_height": 32,
                         "padding": 8, "mask_blur": 2,
                         "force_uniform_tiles": True}},
        "3": {"class_type": "PreviewImage", "inputs": {"images": ["2", 0]}},
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


# --- keys --------------------------------------------------------------------

class TestKeys:
    def test_result_key_deterministic(self):
        a = reuse_mod.result_key(make_prompt(42))
        b = reuse_mod.result_key(make_prompt(42))
        assert a is not None and a == b

    @pytest.mark.parametrize("mutate", [
        lambda p: p["8"]["inputs"].__setitem__("seed", 43),
        lambda p: p["8"]["inputs"].__setitem__("cfg", 2.5),
        lambda p: p["8"]["inputs"].__setitem__("steps", 2),
        lambda p: p["5"]["inputs"].__setitem__("text", "dog"),
        lambda p: p["9"]["inputs"].__setitem__("width", 64),
    ])
    def test_near_miss_changes_key(self, mutate):
        base = reuse_mod.result_key(make_prompt(42))
        changed = make_prompt(42)
        mutate(changed)
        assert reuse_mod.result_key(changed) != base

    def test_result_key_load_image_stat_salt(self, tmp_path):
        p = img2img_prompt(1)
        path = tmp_path / "cond.png"
        path.write_bytes(encode_png(np.zeros((1, 8, 8, 3), np.float32)))
        k1 = reuse_mod.result_key(p, input_dir=str(tmp_path))
        assert k1 is not None
        # same name, different content on disk -> different key (a
        # re-upload must never replay the old image's outputs)
        path.write_bytes(encode_png(np.ones((1, 16, 16, 3), np.float32)))
        assert reuse_mod.result_key(p, input_dir=str(tmp_path)) != k1

    def test_uncacheable_graphs(self):
        p = make_prompt(1)
        p["8"]["hidden"] = {"multi_job_id": "j"}   # orchestrated state
        assert reuse_mod.result_key(p) is None
        assert reuse_mod.result_key(
            {"1": {"class_type": "CheckpointLoaderSimple",
                   "inputs": {"ckpt_name": "x"}}}) is None
        # SaveImage graphs never replay: a replay cannot write the new
        # counter-numbered file the node's contract promises per queue
        p = make_prompt(1)
        p["3"] = {"class_type": "SaveImage",
                  "inputs": {"images": ["1", 0],
                             "filename_prefix": "x"}}
        assert reuse_mod.result_key(p) is None

    def test_subgraph_keys_propagate_upstream_changes(self):
        from comfyui_distributed_tpu.workflow.graph import parse_workflow
        g1 = parse_workflow(make_prompt(1, text="cat"))
        g2 = parse_workflow(make_prompt(1, text="dog"))
        k1 = reuse_mod.subgraph_keys(g1, {})
        k2 = reuse_mod.subgraph_keys(g2, {})
        assert k1["5"] != k2["5"]          # the encode node re-keys
        assert k1["7"] == k2["7"]          # the loader does not
        assert k1["6"] == k2["6"]          # untouched branch stable

    def test_subgraph_keys_hidden_override_disqualifies(self):
        from comfyui_distributed_tpu.workflow.graph import parse_workflow
        g = parse_workflow(make_prompt(1))
        keys = reuse_mod.subgraph_keys(g, {"5": {"anything": 1}})
        assert "5" not in keys

    def test_load_image_stat_salt(self, tmp_path):
        from comfyui_distributed_tpu.workflow.graph import parse_workflow
        img = np.zeros((1, 8, 8, 3), np.float32)
        path = tmp_path / "a.png"
        path.write_bytes(encode_png(img))
        g = parse_workflow(img2img_prompt(1, name="a.png"))
        k1 = reuse_mod.subgraph_keys(g, {}, input_dir=str(tmp_path))
        # rewrite with different content (different size on disk)
        path.write_bytes(encode_png(np.ones((1, 16, 16, 3), np.float32)))
        k2 = reuse_mod.subgraph_keys(g, {}, input_dir=str(tmp_path))
        assert k1["10"] != k2["10"]
        assert k1["11"] != k2["11"]        # propagates into VAEEncode


# --- the bounded LRU ---------------------------------------------------------

class TestByteLRU:
    def test_lru_eviction_order_under_byte_budget(self):
        lru = reuse_mod.ByteLRU("t", max_bytes=1000, max_entries=100)
        for i in range(5):
            lru.put(f"k{i}", i, 300)       # 5 x 300 > 1000
        # budget holds and the OLDEST entries were evicted first
        assert lru.bytes <= 1000
        assert lru.keys() == ["k2", "k3", "k4"]
        # a get refreshes recency: k2 survives the next eviction
        assert lru.get("k2") == 2
        lru.put("k5", 5, 300)
        assert "k2" in lru.keys() and "k3" not in lru.keys()
        assert lru.snapshot()["evictions"] == 3

    def test_oversized_value_rejected(self):
        lru = reuse_mod.ByteLRU("t", max_bytes=100, max_entries=10)
        assert not lru.put("big", 1, 101)
        assert len(lru) == 0

    def test_entry_cap_and_clear(self):
        lru = reuse_mod.ByteLRU("t", max_bytes=1 << 20, max_entries=2)
        for i in range(4):
            lru.put(f"k{i}", i, 10)
        assert lru.keys() == ["k2", "k3"]
        assert lru.clear() == 20
        assert len(lru) == 0 and lru.bytes == 0

    def test_budget_env_resolution(self, monkeypatch):
        monkeypatch.setenv(C.CACHE_BYTES_ENV, "4096")
        monkeypatch.setenv(C.CACHE_ENTRIES_ENV, "7")
        plane = reuse_mod.ReusePlane()
        assert plane.result.max_bytes == 4096
        assert plane.result.max_entries == 7

    def test_monitor_ring_bounded_residency(self, monkeypatch):
        """Fill past DTPU_CACHE_BYTES: the plane stays inside the
        budget and the ResourceMonitor's cache_bytes ring reports the
        bounded residency (satellite: eviction under the telemetry
        budget)."""
        monkeypatch.setenv(C.CACHE_BYTES_ENV, "2048")
        plane = reuse_mod.reset_reuse()
        for i in range(16):
            plane.result.put(f"k{i}", {"images": []}, 512)
        assert plane.result.bytes <= 2048
        assert plane.result.snapshot()["evictions"] == 12
        mon = resource_mod.ResourceMonitor(interval=60)
        mon.sample_once()
        pts = mon.series_tail("cache_bytes")
        assert pts and pts[-1][1] == plane.bytes_total()
        assert pts[-1][1] <= 2048


# --- kill switch -------------------------------------------------------------

class TestKillSwitch:
    def test_cache_off_means_zero_lookups(self, tmp_path, monkeypatch):
        """DTPU_CACHE=0 must keep the hot path from touching the caches
        AT ALL (the DTPU_RESOURCE=0 pattern): poison every cache method
        and the key builders — a run must never call them."""
        monkeypatch.setenv(C.CACHE_ENV, "0")

        def boom(*a, **k):
            raise AssertionError("cache touched with DTPU_CACHE=0")

        monkeypatch.setattr(reuse_mod.ByteLRU, "get", boom)
        monkeypatch.setattr(reuse_mod.ByteLRU, "put", boom)
        monkeypatch.setattr(reuse_mod, "result_key", boom)
        monkeypatch.setattr(reuse_mod, "subgraph_keys", boom)
        st = make_state(tmp_path)
        pid = st.enqueue_prompt(make_prompt(11), "c")
        hist = wait_history(st, [pid])
        assert hist[pid]["status"] == "success"
        assert "cache_hit" not in hist[pid]

    def test_cache_off_tile_path(self, tmp_path, monkeypatch):
        monkeypatch.setenv(C.CACHE_ENV, "0")

        def boom(*a, **k):
            raise AssertionError("tile cache touched with DTPU_CACHE=0")

        monkeypatch.setattr(reuse_mod, "tile_keys", boom)
        monkeypatch.setattr(reuse_mod, "conditioning_fingerprint", boom)
        (tmp_path / "src.png").write_bytes(
            encode_png(np.zeros((1, 64, 64, 3), np.float32)))
        res = WorkflowExecutor(OpContext(
            input_dir=str(tmp_path), output_dir=str(tmp_path))).execute(
            upscale_prompt())
        assert len(res.images) == 1


# --- result tier (server level) ----------------------------------------------

class TestResultTier:
    def test_exact_hit_replay_and_near_miss(self, tmp_path,
                                            assert_nothing_compiled):
        st = make_state(tmp_path)
        pid1 = st.enqueue_prompt(make_prompt(42), "c")
        wait_history(st, [pid1])
        retraces = trace_mod.GLOBAL_RETRACES.mark()
        # byte-identical re-submission: settled synchronously, stamped
        t0 = time.perf_counter()
        pid2 = st.enqueue_prompt(make_prompt(42), "c")
        replay_s = time.perf_counter() - t0
        assert st._history[pid2]["cache_hit"] is True
        assert st._history[pid2]["status"] == "success"
        assert replay_s < 1.0
        assert st.metrics["prompts_replayed"] == 1
        # the replayed job committed a trace with the cache attrs
        rec = trace_mod.GLOBAL_TRACES.get(pid2)
        assert rec is not None
        root = next(s for s in rec["spans"]
                    if s["span_id"] == rec["root_span_id"])
        assert root["attrs"]["cache_hit"] is True
        assert root["attrs"]["cache_tier"] == "result"
        # the replay ran no program, so it compiled none
        assert_nothing_compiled(
            trace_mod.GLOBAL_RETRACES.since(retraces))
        # near miss: ONE widget changed -> full execution, no hit
        pid3 = st.enqueue_prompt(make_prompt(42, cfg=2.5), "c")
        hist = wait_history(st, [pid3])
        assert "cache_hit" not in hist[pid3]
        assert st.metrics["prompts_replayed"] == 1

    def test_replay_bit_identical_to_recompute(self, tmp_path,
                                               fresh_plane):
        st = make_state(tmp_path)
        pid1 = st.enqueue_prompt(make_prompt(7), "c")
        wait_history(st, [pid1])
        key = reuse_mod.result_key(make_prompt(7),
                                   input_dir=st.input_dir)
        stored = fresh_plane.result.get(key)["images"]
        # recompute from scratch (cache emptied): same bytes
        fresh_plane.result.clear()
        pid2 = st.enqueue_prompt(make_prompt(7), "c")
        wait_history(st, [pid2])
        again = fresh_plane.result.get(key)["images"]
        assert len(stored) == len(again) == 1
        assert np.array_equal(stored[0], again[0])

    def test_clear_memory_invalidates_and_reports(self, tmp_path):
        async def go():
            state = make_state(tmp_path)
            app = build_app(state)
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                loop = asyncio.get_running_loop()
                pid = await loop.run_in_executor(
                    None, lambda: state.enqueue_prompt(
                        make_prompt(5), "c"))
                await loop.run_in_executor(
                    None, lambda: wait_history(state, [pid]))
                plane = reuse_mod.get_reuse()
                assert plane.bytes_total() > 0
                r = await client.post("/distributed/clear_memory")
                body = await r.json()
                assert r.status == 200
                assert body["cache_freed_bytes"] > 0
                assert plane.bytes_total() == 0
                # a re-submission now re-executes (no stale replay)
                pid2 = await loop.run_in_executor(
                    None, lambda: state.enqueue_prompt(
                        make_prompt(5), "c"))
                hist = await loop.run_in_executor(
                    None, lambda: wait_history(state, [pid2]))
                assert "cache_hit" not in hist[pid2]
            finally:
                await client.close()
        asyncio.run(go())


# --- sub-graph tier ----------------------------------------------------------

class TestEmbedTier:
    def test_variant_storm_hits_and_stays_bit_identical(
            self, tmp_path, monkeypatch, assert_nothing_compiled):
        """Seed variants share the text encodes; the cached-conditioning
        run's image is bit-identical to a cache-off run."""
        ctx = lambda: OpContext(input_dir=str(tmp_path),  # noqa: E731
                                output_dir=str(tmp_path))
        WorkflowExecutor(ctx()).execute(make_prompt(1))    # warm the cache
        before = reuse_mod.get_reuse().subgraph.snapshot()["hits"]
        cached = WorkflowExecutor(ctx()).execute(make_prompt(2))
        assert reuse_mod.get_reuse().subgraph.snapshot()["hits"] \
            >= before + 2                                  # both encodes
        assert_nothing_compiled(cached.retraces)
        monkeypatch.setenv(C.CACHE_ENV, "0")
        plain = WorkflowExecutor(ctx()).execute(make_prompt(2))
        assert np.array_equal(cached.images[0], plain.images[0])

    def test_vae_encode_tier_bit_identical(self, tmp_path, monkeypatch):
        (tmp_path / "cond.png").write_bytes(encode_png(
            np.linspace(0, 1, 1 * 32 * 32 * 3, dtype=np.float32)
            .reshape(1, 32, 32, 3)))
        ctx = lambda: OpContext(input_dir=str(tmp_path),  # noqa: E731
                                output_dir=str(tmp_path))
        WorkflowExecutor(ctx()).execute(img2img_prompt(1))
        hits0 = trace_mod.GLOBAL_COUNTERS.get("cache_embed_hits")
        cached = WorkflowExecutor(ctx()).execute(img2img_prompt(2))
        assert trace_mod.GLOBAL_COUNTERS.get("cache_embed_hits") \
            >= hits0 + 3                   # 2 text encodes + VAE encode
        monkeypatch.setenv(C.CACHE_ENV, "0")
        plain = WorkflowExecutor(ctx()).execute(img2img_prompt(2))
        assert np.array_equal(cached.images[0], plain.images[0])


# --- tile tier ---------------------------------------------------------------

@pytest.mark.slow
class TestTileTier:
    def _write_src(self, tmp_path, mutate_corner=False):
        rng = np.random.default_rng(3)
        img = rng.random((1, 64, 64, 3)).astype(np.float32)
        if mutate_corner:
            img[0, :16, :16, :] = 0.5      # dirties ONLY tile 0 (of 4)
        (tmp_path / "src.png").write_bytes(encode_png(img))

    def test_changed_tile_only_refine_bit_identical(self, tmp_path):
        ctx = lambda: OpContext(input_dir=str(tmp_path),  # noqa: E731
                                output_dir=str(tmp_path))
        self._write_src(tmp_path)
        r1 = WorkflowExecutor(ctx()).execute(upscale_prompt())
        # clean re-run: every tile skips, blend identical
        sk0 = trace_mod.GLOBAL_COUNTERS.get("tiles_skipped")
        r2 = WorkflowExecutor(ctx()).execute(upscale_prompt())
        assert trace_mod.GLOBAL_COUNTERS.get("tiles_skipped") == sk0 + 4
        assert np.array_equal(r1.images[0], r2.images[0])
        # dirty ONE tile: skip count == clean-tile count...
        self._write_src(tmp_path, mutate_corner=True)
        sk1 = trace_mod.GLOBAL_COUNTERS.get("tiles_skipped")
        r3 = WorkflowExecutor(ctx()).execute(upscale_prompt())
        assert trace_mod.GLOBAL_COUNTERS.get("tiles_skipped") == sk1 + 3
        # ...and the partial blend matches a full re-run bit-identically
        # at the PNG wire (uint8) level — the same oracle the cluster
        # recovery tests use: XLA may differ at the last float ulp
        # between batch-of-1 and batch-of-4 refine programs, which the
        # 8-bit quantize absorbs exactly like the worker->master wire
        reuse_mod.get_reuse().clear()
        r4 = WorkflowExecutor(ctx()).execute(upscale_prompt())
        assert np.allclose(r3.images[0], r4.images[0], atol=1e-5)
        q = lambda a: np.clip(a * 255.0 + 0.5, 0,  # noqa: E731
                              255).astype(np.uint8)
        assert np.array_equal(q(r3.images[0]), q(r4.images[0]))

    def test_param_near_miss_never_hits(self, tmp_path):
        ctx = lambda: OpContext(input_dir=str(tmp_path),  # noqa: E731
                                output_dir=str(tmp_path))
        self._write_src(tmp_path)
        WorkflowExecutor(ctx()).execute(upscale_prompt(denoise=0.4))
        hits0 = reuse_mod.get_reuse().tiles.snapshot()["hits"]
        WorkflowExecutor(ctx()).execute(upscale_prompt(denoise=0.5))
        assert reuse_mod.get_reuse().tiles.snapshot()["hits"] == hits0


# --- previews + client-gone cancellation -------------------------------------

class TestPreviewChannel:
    def test_latent_preview_png(self):
        png = reuse_mod.latent_preview_png(
            np.random.default_rng(0).normal(size=(1, 8, 8, 4)))
        assert png[:4] == b"\x89PNG"

    def test_bus_subscribe_publish_finish(self):
        bus = reuse_mod.PreviewBus(max_clients=2)
        q = bus.subscribe("p1")
        assert bus.wants("p1") and not bus.wants("p2")
        bus.publish_latent("p1", 3, 10, np.zeros((1, 4, 4, 4)))
        ev = q.get_nowait()
        assert ev["type"] == "preview" and ev["step"] == 3
        bus.finish("p1", "success")
        assert q.get_nowait()["type"] == "done"
        assert bus.unsubscribe("p1", q) == 0
        # client cap
        a, b = bus.subscribe("x"), bus.subscribe("y")
        assert a is not None and b is not None
        assert bus.subscribe("z") is None

    def test_abandoned_queued_prompt_is_purged(self, tmp_path):
        st = make_state(tmp_path)
        st._exec_gate.clear()
        try:
            pid = st.enqueue_prompt(make_prompt(21, steps=1), "c")
            reuse_mod.PREVIEWS.abandon(pid)
        finally:
            st._exec_gate.set()
        hist = wait_history(st, [pid])
        assert hist[pid]["status"] == "abandoned"
        assert st.metrics["prompts_abandoned"] == 1
        # the flag was consumed at finalize
        assert not reuse_mod.PREVIEWS.is_abandoned(pid)

    def test_preview_route_disabled(self, tmp_path, monkeypatch):
        monkeypatch.setenv(C.PREVIEW_ENV, "0")

        async def go():
            state = make_state(tmp_path, start_exec_thread=False)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                r = await client.get("/distributed/preview/p_x")
                assert r.status == 403
            finally:
                await client.close()
        asyncio.run(go())

    def test_done_event_for_finished_prompt(self, tmp_path):
        async def go():
            state = make_state(tmp_path)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                loop = asyncio.get_running_loop()
                pid = await loop.run_in_executor(
                    None, lambda: state.enqueue_prompt(
                        make_prompt(31), "c"))
                await loop.run_in_executor(
                    None, lambda: wait_history(state, [pid]))
                r = await client.get(f"/distributed/preview/{pid}")
                assert r.status == 200
                body = (await r.content.read()).decode()
                assert "event: done" in body
                assert '"status": "success"' in body
            finally:
                await client.close()
        asyncio.run(go())


@pytest.mark.slow
class TestPreviewSSEAcceptance:
    def test_sse_stream_and_client_gone_frees_slot(self, tmp_path):
        """THE channel acceptance over real HTTP: preview frames stream
        from the CB denoise loop; dropping the connection mid-stream
        abandons the job — its slot exits at the next step boundary
        (freeing capacity for the sibling, which completes), and the
        history records the abandonment."""
        async def go():
            state = make_state(tmp_path, cb=True)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                loop = asyncio.get_running_loop()
                pid_long = await loop.run_in_executor(
                    None, lambda: state.enqueue_prompt(
                        make_prompt(1, steps=80), "c"))
                resp = await client.get(
                    f"/distributed/preview/{pid_long}")
                assert resp.status == 200
                # read until one COMPLETE preview frame arrives (the
                # base64 PNG spans several reads; a frame ends at \n\n)
                buf = b""
                deadline = time.monotonic() + 120
                marker = b"event: preview\ndata: "
                while time.monotonic() < deadline:
                    buf += await resp.content.read(256)
                    at = buf.find(marker)
                    if at >= 0 and buf.find(b"\n\n", at) >= 0:
                        break
                at = buf.find(marker)
                assert at >= 0, buf[:200]
                frame = buf[at + len(marker):buf.find(b"\n\n", at)]
                ev = json.loads(frame)
                png = base64.b64decode(ev["png_b64"])
                assert png[:4] == b"\x89PNG"
                assert ev["total_steps"] == 80
                # client gone: hard-close the connection mid-stream
                resp.close()
                await asyncio.sleep(0)
                pid_next = await loop.run_in_executor(
                    None, lambda: state.enqueue_prompt(
                        make_prompt(2, steps=2, text="dog"), "c"))
                hist = await loop.run_in_executor(
                    None, lambda: wait_history(
                        state, [pid_long, pid_next], 120))
                assert hist[pid_long]["status"] == "abandoned"
                assert hist[pid_next]["status"] == "success"
                assert state.cb.snapshot()["slots_active"] == 0
                assert state.cb.snapshot()["abandoned"] == 1
                # the freed slot's exit is in the abandoned job's trace
                rec = trace_mod.GLOBAL_TRACES.get(pid_long)
                assert any(s["name"] == "cb_exit" for s in rec["spans"])
                # both metrics surfaces carry the counters
                m = await (await client.get(
                    "/distributed/metrics")).json()
                assert m["reuse"]["previews"]["clients"] == 0
                assert m["prompts_abandoned"] == 1
                prom = await (await client.get(
                    "/distributed/metrics.prom")).text()
                assert "dtpu_jobs_abandoned_total 1" in prom
                assert "dtpu_preview_events_total" in prom
                assert "dtpu_cache_hits_total" in prom
            finally:
                await client.close()
        asyncio.run(go())


# --- metrics surfaces --------------------------------------------------------

class TestMetricsSurfaces:
    def test_reuse_block_and_prom_families(self, tmp_path):
        async def go():
            state = make_state(tmp_path, start_exec_thread=False)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                m = await (await client.get(
                    "/distributed/metrics")).json()
                assert m["reuse"]["enabled"] is True
                assert set(m["reuse"]) >= {"result", "embed", "tile",
                                           "previews", "bytes_total"}
                prom = await (await client.get(
                    "/distributed/metrics.prom")).text()
                for family in ("dtpu_cache_hits_total",
                               "dtpu_cache_misses_total",
                               "dtpu_cache_bytes",
                               "dtpu_cache_replays_total",
                               "dtpu_cache_tiles_skipped_total",
                               "dtpu_preview_clients",
                               "dtpu_jobs_abandoned_total"):
                    assert family in prom, family
            finally:
                await client.close()
        asyncio.run(go())
