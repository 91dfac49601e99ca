"""Control/data-plane HTTP API (reference route surface, SURVEY.md §2)."""

import asyncio
import base64
import json
import os

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.server.app import ServerState, build_app
from comfyui_distributed_tpu.utils.image import decode_png, encode_png


@pytest.fixture(autouse=True)
def tiny_family(monkeypatch):
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny")
    yield


def run_with_client(fn, tmp_path, **state_kw):
    """Spin the app in a private event loop and run the async test body."""
    async def go():
        state = ServerState(
            config_path=str(tmp_path / "cfg.json"),
            input_dir=str(tmp_path / "input"),
            output_dir=str(tmp_path / "output"),
            **state_kw)
        app = build_app(state)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            return await fn(client, state)
        finally:
            await client.close()
    return asyncio.run(go())


class TestConfigRoutes:
    def test_config_crud(self, tmp_path):
        async def body(client, state):
            r = await client.get("/distributed/config")
            assert r.status == 200
            assert (await r.json())["workers"] == []

            r = await client.post("/distributed/config/update_worker",
                                  json={"id": "w1", "name": "n", "port": 9000,
                                        "enabled": True})
            assert r.status == 200
            r = await client.post("/distributed/config/update_worker",
                                  json={"id": "w1", "name": None})
            cfg = await (await client.get("/distributed/config")).json()
            assert "name" not in cfg["workers"][0]

            r = await client.post("/distributed/config/update_setting",
                                  json={"key": "debug", "value": True})
            assert r.status == 200
            r = await client.post("/distributed/config/update_master",
                                  json={"host": "1.2.3.4"})
            cfg = await (await client.get("/distributed/config")).json()
            assert cfg["master"]["host"] == "1.2.3.4"
            assert cfg["settings"]["debug"] is True

            r = await client.post("/distributed/config/delete_worker",
                                  json={"id": "w1"})
            assert r.status == 200
            r = await client.post("/distributed/config/delete_worker",
                                  json={"id": "w1"})
            assert r.status == 404

            r = await client.post("/distributed/config/update_worker",
                                  json={"name": "no id"})
            assert r.status == 400
        run_with_client(body, tmp_path, start_exec_thread=False)


class TestInfoRoutes:
    def test_network_info_status_metrics(self, tmp_path):
        async def body(client, state):
            info = await (await client.get("/distributed/network_info")).json()
            assert "recommended_ip" in info
            st = await (await client.get("/distributed/status")).json()
            assert st["num_devices"] == 8
            assert st["queue_remaining"] == 0
            m = await (await client.get("/distributed/metrics")).json()
            assert m["prompts_executed"] == 0
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_clear_memory(self, tmp_path):
        async def body(client, state):
            r = await client.post("/distributed/clear_memory")
            assert r.status == 200
        run_with_client(body, tmp_path, start_exec_thread=False)


class TestDataPlane:
    def test_prepare_then_job_complete(self, tmp_path, rng):
        async def body(client, state):
            r = await client.post("/distributed/prepare_job",
                                  json={"multi_job_id": "j1"})
            assert r.status == 200

            img = rng.random((1, 8, 8, 3)).astype(np.float32)
            import aiohttp
            form = aiohttp.FormData()
            form.add_field("multi_job_id", "j1")
            form.add_field("worker_id", "worker_0")
            form.add_field("image_index", "0")
            form.add_field("is_last", "true")
            form.add_field("image", encode_png(img), filename="i.png",
                           content_type="image/png")
            r = await client.post("/distributed/job_complete", data=form)
            assert r.status == 200

            q = await state.jobs.get_queue("j1")
            item = q.get_nowait()
            assert item["worker_id"] == "worker_0"
            assert item["is_last"] is True
            assert item["tensor"].shape == (1, 8, 8, 3)
            np.testing.assert_allclose(item["tensor"], img, atol=1 / 255 + 1e-6)
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_job_complete_unknown_job_404(self, tmp_path, rng):
        async def body(client, state):
            import aiohttp
            form = aiohttp.FormData()
            form.add_field("multi_job_id", "nope")
            form.add_field("image", encode_png(
                rng.random((1, 4, 4, 3)).astype(np.float32)),
                filename="i.png", content_type="image/png")
            r = await client.post("/distributed/job_complete", data=form)
            assert r.status == 404
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_tile_complete_and_queue_status(self, tmp_path, rng):
        async def body(client, state):
            r = await client.get("/distributed/queue_status",
                                 params={"multi_job_id": "t1"})
            assert (await r.json())["exists"] is False

            import aiohttp
            png = encode_png(rng.random((1, 8, 8, 3)).astype(np.float32))

            def mkform():  # FormData payloads are single-use
                form = aiohttp.FormData()
                form.add_field("multi_job_id", "t1")
                form.add_field("worker_id", "worker_0")
                form.add_field("tile_idx", "3")
                form.add_field("x", "64")
                form.add_field("y", "0")
                form.add_field("extracted_width", "96")
                form.add_field("extracted_height", "96")
                form.add_field("is_last", "true")
                form.add_field("tile", png, filename="t.png",
                               content_type="image/png")
                return form

            # unknown tile job -> 404 (worker retry loop backs off; the
            # master pre-creates the queue before dispatch)
            r = await client.post("/distributed/tile_complete", data=mkform())
            assert r.status == 404

            await state.jobs.get_tile_queue("t1")  # master-side pre-create
            r = await client.post("/distributed/tile_complete", data=mkform())
            assert r.status == 200

            r = await client.get("/distributed/queue_status",
                                 params={"multi_job_id": "t1"})
            assert (await r.json())["exists"] is True
            q = await state.jobs.get_tile_queue("t1")
            item = q.get_nowait()
            assert item["tile_idx"] == 3 and item["x"] == 64
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_load_image_staging(self, tmp_path, rng):
        async def body(client, state):
            os.makedirs(state.input_dir, exist_ok=True)
            img = rng.random((1, 8, 8, 3)).astype(np.float32)
            with open(os.path.join(state.input_dir, "x.png"), "wb") as f:
                f.write(encode_png(img))
            r = await client.post("/distributed/load_image",
                                  json={"image_name": "x.png"})
            assert r.status == 200
            data = await r.json()
            back = decode_png(base64.b64decode(data["image_data"]))
            assert back.shape == (1, 8, 8, 3)

            r = await client.post("/distributed/load_image",
                                  json={"image_name": "missing.png"})
            assert r.status == 404
            r = await client.post("/distributed/load_image",
                                  json={"image_name": "../../etc/passwd"})
            assert r.status in (400, 404)
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_upload_image(self, tmp_path, rng):
        async def body(client, state):
            import aiohttp
            form = aiohttp.FormData()
            form.add_field("image", encode_png(
                rng.random((1, 4, 4, 3)).astype(np.float32)),
                filename="up.png", content_type="image/png")
            r = await client.post("/upload/image", data=form)
            assert r.status == 200
            assert os.path.exists(os.path.join(state.input_dir, "up.png"))
        run_with_client(body, tmp_path, start_exec_thread=False)


class TestCollectorDedup:
    def test_retransmitted_upload_not_duplicated(self, tmp_path, rng):
        """ADVICE r1: the worker send path retries with backoff, so a
        timed-out-but-delivered job_complete POST arrives twice; the master
        must key results by (worker, image_index), not append."""
        import threading
        from comfyui_distributed_tpu.ops.base import OpContext
        from comfyui_distributed_tpu.ops.distributed import DistributedCollector
        from comfyui_distributed_tpu.runtime.jobs import JobStore

        loop = asyncio.new_event_loop()
        t = threading.Thread(target=loop.run_forever, daemon=True)
        t.start()
        try:
            store = JobStore()
            img0 = rng.random((1, 4, 4, 3)).astype(np.float32)
            img1 = rng.random((1, 4, 4, 3)).astype(np.float32)

            async def seed():
                await store.prepare_job("j1")
                for idx, tensor, last in ((0, img0, False), (0, img0, False),
                                          (1, img1, True)):
                    await store.put_result("j1", {
                        "worker_id": "worker_1", "image_index": idx,
                        "tensor": tensor, "is_last": last})

            asyncio.run_coroutine_threadsafe(seed(), loop).result(10)
            ctx = OpContext(job_store=store, server_loop=loop)
            master = rng.random((1, 4, 4, 3)).astype(np.float32)
            (out,) = DistributedCollector().execute(
                ctx, master, multi_job_id="j1",
                enabled_worker_ids='["worker_1"]')
            # 1 master + 2 distinct worker images — not 4
            assert out.shape[0] == 3
            np.testing.assert_allclose(out[1], img0[0], atol=1e-6)
            np.testing.assert_allclose(out[2], img1[0], atol=1e-6)
        finally:
            loop.call_soon_threadsafe(loop.stop)
            t.join(5)


class TestPromptSurface:
    def test_get_prompt_health(self, tmp_path):
        async def body(client, state):
            r = await client.get("/prompt")
            assert (await r.json())["exec_info"]["queue_remaining"] == 0
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_post_prompt_executes(self, tmp_path):
        """Full /prompt -> exec queue -> history flow with a tiny graph."""
        prompt = {
            "7": {"class_type": "CheckpointLoaderSimple",
                  "inputs": {"ckpt_name": "tiny.safetensors"}},
            "5": {"class_type": "CLIPTextEncode",
                  "inputs": {"text": "cat", "clip": ["7", 1]}},
            "6": {"class_type": "CLIPTextEncode",
                  "inputs": {"text": "", "clip": ["7", 1]}},
            "9": {"class_type": "EmptyLatentImage",
                  "inputs": {"width": 32, "height": 32, "batch_size": 1}},
            "8": {"class_type": "KSampler",
                  "inputs": {"model": ["7", 0], "positive": ["5", 0],
                             "negative": ["6", 0], "latent_image": ["9", 0],
                             "seed": 1, "steps": 1, "cfg": 1.0,
                             "sampler_name": "euler", "scheduler": "normal",
                             "denoise": 1.0}},
            "1": {"class_type": "VAEDecode",
                  "inputs": {"samples": ["8", 0], "vae": ["7", 2]}},
            "3": {"class_type": "PreviewImage",
                  "inputs": {"images": ["1", 0]}},
        }

        async def body(client, state):
            r = await client.post("/prompt", json={"prompt": prompt,
                                                   "client_id": "t"})
            assert r.status == 200
            pid = (await r.json())["prompt_id"]
            for _ in range(1800):  # generous: exec thread may be compiling
                hist = await (await client.get("/history")).json()
                if pid in hist:
                    assert hist[pid]["status"] == "success", hist[pid]
                    assert hist[pid]["images"] == 1
                    break
                await asyncio.sleep(0.1)
            else:
                raise AssertionError("prompt never finished")
            m = await (await client.get("/distributed/metrics")).json()
            assert m["prompts_executed"] == 1
        run_with_client(body, tmp_path, start_exec_thread=True)

    def test_post_prompt_missing(self, tmp_path):
        async def body(client, state):
            r = await client.post("/prompt", json={})
            assert r.status == 400
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_interrupt(self, tmp_path):
        async def body(client, state):
            from comfyui_distributed_tpu.runtime import interrupt as itr
            r = await client.post("/interrupt")
            assert r.status == 200
            assert state.interrupt_event.is_set()
            # the server's event IS the process-global flag the executor,
            # the CB step loop and (DTPU_INTERRUPT_POLL=1) the compiled
            # samplers read (runtime/interrupt.py)
            assert itr.is_interrupted()
            itr.clear_interrupt()
        run_with_client(body, tmp_path, start_exec_thread=False)


class TestPanel:
    def test_panel_serves_html(self, tmp_path):
        async def body(client, state):
            r = await client.get("/panel")
            assert r.status == 200
            assert "text/html" in r.headers.get("Content-Type", "")
            text = await r.text()
            # drives the existing JSON routes, no external deps
            for needle in ("/distributed/workers_status", "_worker",
                           "/distributed/metrics", "<script>"):
                assert needle in text, needle
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_panel_settings_form_contract(self, tmp_path):
        """The panel's settings form (reference settings dialog analog)
        drives exactly these routes with exactly these payload shapes —
        exercise them the way the form does (no browser in CI)."""
        async def body(client, state):
            text = await (await client.get("/panel")).text()
            for needle in ("config/update_worker", "config/delete_worker",
                           "config/update_setting", "config/update_master",
                           "saveWorker", "wf-port"):
                assert needle in text, needle
            # saveWorker(): upsert with explicit nulls for cleared fields
            r = await client.post("/distributed/config/update_worker",
                                  json={"id": "p1", "name": "p1",
                                        "port": 18999, "host": None,
                                        "extra_args": None})
            assert r.status == 200
            w = (await r.json())["worker"]
            assert w["port"] == 18999 and "host" not in w
            # settings checkbox + master host field
            r = await client.post("/distributed/config/update_setting",
                                  json={"key": "debug", "value": True})
            assert r.status == 200
            r = await client.post("/distributed/config/update_master",
                                  json={"host": "10.0.0.9"})
            assert r.status == 200
            cfg = await (await client.get("/distributed/config")).json()
            assert cfg["settings"]["debug"] is True
            assert cfg["master"]["host"] == "10.0.0.9"
            # delete button path
            r = await client.post("/distributed/config/delete_worker",
                                  json={"id": "p1"})
            assert r.status == 200
            cfg = await (await client.get("/distributed/config")).json()
            assert all(x["id"] != "p1" for x in cfg["workers"])
        run_with_client(body, tmp_path, start_exec_thread=False)


    def test_panel_js_endpoints_exist_in_route_table(self, tmp_path):
        """VERDICT r3 #8: every endpoint string the panel's JS fetches
        must resolve against the app's actual route table — a renamed
        route must fail THIS test, not a user's browser session."""
        import re

        async def body(client, state):
            text = await (await client.get("/panel")).text()
            # endpoint literals in quotes or template strings, query/
            # template suffix stripped
            paths = set()
            for m in re.findall(
                    r"[\"'`](/(?:distributed|prompt|interrupt|panel)"
                    r"[A-Za-z0-9_/]*)", text):
                paths.add(m)
            assert len(paths) >= 10, sorted(paths)  # the panel is rich
            table = set()
            for route in client.server.app.router.routes():
                info = route.resource.get_info() if route.resource else {}
                table.add(info.get("path") or info.get("formatter") or "")
            missing = []
            for p in sorted(paths):
                if p.endswith("/"):
                    # a concatenation base ('/distributed/' + kind + ...):
                    # some routed path must extend it
                    if not any(t.startswith(p) for t in table):
                        missing.append(p)
                elif p not in table:
                    missing.append(p)
            assert not missing, f"panel JS fetches unrouted: {missing}"
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_panel_checkbox_and_null_host_semantics(self, tmp_path):
        """VERDICT r3 #8: the enable-checkbox's exact contract, by direct
        endpoint calls.  The checkbox posts ONLY {id, enabled}: a partial
        upsert must flip the flag without clobbering other fields; a
        rejected post must leave config unchanged (that atomicity is what
        makes the JS revert-on-reject correct); update_master with an
        explicit null host clears it (the autodetect mode)."""
        async def body(client, state):
            r = await client.post("/distributed/config/update_worker",
                                  json={"id": "cb1", "name": "worker one",
                                        "port": 18901, "host": "10.0.0.2",
                                        "enabled": True})
            assert r.status == 200
            # the checkbox's exact payload: partial update
            r = await client.post("/distributed/config/update_worker",
                                  json={"id": "cb1", "enabled": False})
            assert r.status == 200
            cfg = await (await client.get("/distributed/config")).json()
            (w,) = [x for x in cfg["workers"] if x["id"] == "cb1"]
            assert w["enabled"] is False
            assert w["name"] == "worker one" and w["port"] == 18901 \
                and w["host"] == "10.0.0.2"   # untouched fields preserved
            # reject path: no id -> 400 and NOTHING changed (the panel's
            # .catch() reverts the checkbox; server must not half-apply)
            r = await client.post("/distributed/config/update_worker",
                                  json={"enabled": True})
            assert r.status == 400
            cfg2 = await (await client.get("/distributed/config")).json()
            assert cfg2["workers"] == cfg["workers"]
            # master host: explicit null clears (autodetect mode)
            r = await client.post("/distributed/config/update_master",
                                  json={"host": "10.9.9.9"})
            assert r.status == 200
            r = await client.post("/distributed/config/update_master",
                                  json={"host": None})
            assert r.status == 200
            cfg3 = await (await client.get("/distributed/config")).json()
            assert not cfg3["master"].get("host")
        run_with_client(body, tmp_path, start_exec_thread=False)


class TestLifecycleRoutes:
    def test_launch_unknown_worker_404(self, tmp_path):
        async def body(client, state):
            r = await client.post("/distributed/launch_worker",
                                  json={"id": "zzz"})
            assert r.status == 404
            r = await client.post("/distributed/stop_worker",
                                  json={"id": "zzz"})
            assert r.status == 404
            r = await client.get("/distributed/worker_log",
                                 params={"id": "zzz"})
            assert r.status == 404
            r = await client.get("/distributed/managed_workers")
            assert await r.json() == {}
        run_with_client(body, tmp_path, start_exec_thread=False)


class TestProfiling:
    def test_profile_endpoints(self, tmp_path):
        async def body(client, state):
            r = await client.get("/distributed/profile/status")
            assert (await r.json())["running"] is False

            r = await client.post("/distributed/profile/start",
                                  json={"dir": str(tmp_path / "tr")})
            assert r.status == 200

            r = await client.post("/distributed/profile/start",
                                  json={"dir": str(tmp_path / "tr2")})
            assert r.status == 409  # already running

            r = await client.get("/distributed/profile/status")
            assert (await r.json())["running"] is True

            r = await client.post("/distributed/profile/stop")
            assert r.status == 200
            assert (await r.json())["dir"] == str(tmp_path / "tr")

            r = await client.post("/distributed/profile/stop")
            assert r.status == 409
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_metrics_include_phases(self, tmp_path):
        from comfyui_distributed_tpu.utils.logging import Timer
        with Timer("unit_test_phase"):
            pass

        async def body(client, state):
            r = await client.get("/distributed/metrics")
            data = await r.json()
            assert "phases" in data
            assert data["phases"]["unit_test_phase"]["count"] >= 1
        run_with_client(body, tmp_path, start_exec_thread=False)


class TestClusterActions:
    def test_workers_status_and_cluster_endpoints(self, tmp_path):
        async def body(client, state):
            r = await client.get("/distributed/workers_status")
            assert r.status == 200 and await r.json() == {}

            # no enabled workers -> fan-out is a no-op but self still acts
            from comfyui_distributed_tpu.runtime import interrupt as itr
            r = await client.post("/distributed/cluster/interrupt")
            assert r.status == 200
            assert (await r.json())["workers"] == {}
            assert state.interrupt_event.is_set()
            itr.clear_interrupt()  # the process-global sampler flag
            # (conftest's _no_leaked_interrupt also guards every test)

            r = await client.post("/distributed/cluster/clear_memory")
            assert r.status == 200
        run_with_client(body, tmp_path, start_exec_thread=False)


class TestPromptExtraPnginfo:
    def test_extra_data_reaches_saved_pngs(self, tmp_path):
        """/prompt's extra_data.extra_pnginfo rides the exec thread into
        SaveImage: the saved PNG embeds the prompt AND the workflow
        chunk (the reference ships extra_pnginfo with every dispatch,
        gpupanel.js:1344-1358)."""
        from PIL import Image
        prompt = {
            "7": {"class_type": "CheckpointLoaderSimple",
                  "inputs": {"ckpt_name": "tiny.safetensors"}},
            "5": {"class_type": "CLIPTextEncode",
                  "inputs": {"text": "cat", "clip": ["7", 1]}},
            "9": {"class_type": "EmptyLatentImage",
                  "inputs": {"width": 32, "height": 32, "batch_size": 1}},
            "8": {"class_type": "KSampler",
                  "inputs": {"model": ["7", 0], "positive": ["5", 0],
                             "negative": ["5", 0], "latent_image": ["9", 0],
                             "seed": 1, "steps": 1, "cfg": 1.0,
                             "sampler_name": "euler", "scheduler": "normal",
                             "denoise": 1.0}},
            "1": {"class_type": "VAEDecode",
                  "inputs": {"samples": ["8", 0], "vae": ["7", 2]}},
            "3": {"class_type": "SaveImage",
                  "inputs": {"images": ["1", 0],
                             "filename_prefix": "meta_http"}},
        }
        ui_doc = {"nodes": [], "links": [], "note": "source workflow"}

        async def body(client, state):
            r = await client.post("/prompt", json={
                "prompt": prompt, "client_id": "t",
                "extra_data": {"extra_pnginfo": {"workflow": ui_doc}}})
            assert r.status == 200
            pid = (await r.json())["prompt_id"]
            for _ in range(1800):
                hist = await (await client.get("/history")).json()
                if pid in hist:
                    assert hist[pid]["status"] == "success", hist[pid]
                    break
                await asyncio.sleep(0.1)
            else:
                raise AssertionError("prompt never finished")
            outs = sorted(os.listdir(state.output_dir))
            assert outs, "SaveImage wrote nothing"
            im = Image.open(os.path.join(state.output_dir, outs[0]))
            assert json.loads(im.info["workflow"]) == ui_doc
            embedded = json.loads(im.info["prompt"])
            assert set(embedded) == set(prompt)
        run_with_client(body, tmp_path, start_exec_thread=True)
