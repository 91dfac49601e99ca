"""Dispatcher graph-rewrite parity (gpupanel.js semantics)."""

import asyncio
import json

from comfyui_distributed_tpu.workflow import parse_workflow
from comfyui_distributed_tpu.workflow import dispatcher as dsp
from comfyui_distributed_tpu.workflow.graph import Graph, Node

from tests.test_workflow import TXT2IMG, UPSCALE, _only


class TestPrune:
    def test_connected_graph_kept_whole(self):
        g = parse_workflow(TXT2IMG)
        pruned = dsp.prune_for_worker(g)
        assert set(pruned.nodes) == set(g.nodes)

    def test_disconnected_branch_pruned(self):
        g = parse_workflow(TXT2IMG)
        # an island node with no links to the distributed component
        g.nodes["99"] = Node(id="99", class_type="EmptyLatentImage",
                             inputs={"width": 8, "height": 8,
                                     "batch_size": 1})
        pruned = dsp.prune_for_worker(g)
        assert "99" not in pruned.nodes
        assert _only(g, "DistributedCollector") in pruned.nodes

    def test_prune_does_not_mutate_original(self):
        g = parse_workflow(TXT2IMG)
        before = json.dumps(g.to_api_format(), sort_keys=True, default=str)
        dsp.prune_for_worker(g)
        assert json.dumps(g.to_api_format(), sort_keys=True,
                          default=str) == before


class TestInjection:
    def test_master_injection(self):
        g = parse_workflow(TXT2IMG)
        jm = dsp.make_job_id_map(g, prefix="exec_t")
        out = dsp.prepare_for_participant(g, "master", jm, ["worker_0",
                                                            "worker_1"])
        seed = out.nodes[_only(g, "DistributedSeed")].hidden
        assert seed["is_worker"] is False
        cid = _only(g, "DistributedCollector")
        coll = out.nodes[cid].hidden
        assert coll["multi_job_id"] == f"exec_t_{cid}"
        assert json.loads(coll["enabled_worker_ids"]) == ["worker_0",
                                                          "worker_1"]
        assert "master_url" not in coll

    def test_worker_injection(self):
        g = parse_workflow(TXT2IMG)
        jm = dsp.make_job_id_map(g, prefix="exec_t")
        out = dsp.prepare_for_participant(
            g, "worker", jm, ["worker_0", "worker_1"],
            master_url="http://10.0.0.1:8288", worker_index=1, batch_size=4)
        seed = out.nodes[_only(g, "DistributedSeed")].hidden
        assert seed["is_worker"] is True
        assert seed["worker_id"] == "worker_1"
        coll = out.nodes[_only(g, "DistributedCollector")].hidden
        assert coll["master_url"] == "http://10.0.0.1:8288"
        assert coll["worker_batch_size"] == 4
        assert "enabled_worker_ids" not in coll

    def test_upscaler_injection_both_sides(self):
        g = parse_workflow(UPSCALE)
        jm = dsp.make_job_id_map(g)
        m = dsp.prepare_for_participant(g, "master", jm, ["worker_0"])
        w = dsp.prepare_for_participant(g, "worker", jm, ["worker_0"],
                                        master_url="http://m:1", worker_index=0)
        up = _only(g, "UltimateSDUpscaleDistributed")
        # workers need the enabled list for tile math (gpupanel.js:1157-1174)
        assert json.loads(m.nodes[up].hidden["enabled_worker_ids"]) == \
            ["worker_0"]
        assert json.loads(w.nodes[up].hidden["enabled_worker_ids"]) == \
            ["worker_0"]
        assert w.nodes[up].hidden["master_url"] == "http://m:1"

    def test_collector_downstream_of_upscaler_passthrough(self):
        """A collector fed (transitively) by a distributed upscaler becomes
        pass_through (gpupanel.js:1146-1154)."""
        g = parse_workflow(UPSCALE)
        up = _only(g, "UltimateSDUpscaleDistributed")
        g.nodes["20"] = Node(id="20", class_type="DistributedCollector",
                             inputs={"images": [up, 0]})
        g.nodes[_only(g, "PreviewImage")].inputs["images"] = ["20", 0]
        jm = dsp.make_job_id_map(g)
        out = dsp.prepare_for_participant(g, "master", jm, ["worker_0"])
        assert out.nodes["20"].hidden.get("pass_through") is True
        assert "multi_job_id" not in out.nodes["20"].hidden

    def test_job_id_map(self):
        g = parse_workflow(TXT2IMG)
        jm = dsp.make_job_id_map(g)
        cid = _only(g, "DistributedCollector")
        assert set(jm) == {cid}
        assert jm[cid].endswith(f"_{cid}")
        assert jm[cid].startswith("exec_")


class TestUpstream:
    def test_has_upstream_type(self):
        g = parse_workflow(UPSCALE)
        # the preview is downstream of the upscaler
        assert dsp.has_upstream_type(g, _only(g, "PreviewImage"),
                                     ("UltimateSDUpscaleDistributed",))
        assert not dsp.has_upstream_type(
            g, _only(g, "UltimateSDUpscaleDistributed"),
            ("UltimateSDUpscaleDistributed",))

    def test_cycle_safe(self):
        g = Graph(nodes={
            "a": Node(id="a", class_type="X", inputs={"i": ["b", 0]}),
            "b": Node(id="b", class_type="X", inputs={"i": ["a", 0]}),
        })
        assert not dsp.has_upstream_type(g, "a", ("Y",))


def test_prune_without_distributed_nodes_returns_copy():
    """Regression: a graph with no collector/upscaler must still be deep
    copied, or per-participant hidden inputs leak into the caller's graph."""
    from comfyui_distributed_tpu.workflow.dispatcher import (
        make_job_id_map, prepare_for_participant, prune_for_worker)
    from comfyui_distributed_tpu.workflow.graph import parse_api_format

    g = parse_api_format({
        "1": {"class_type": "DistributedSeed", "inputs": {"seed": 5}},
        "2": {"class_type": "PreviewImage", "inputs": {"images": ["1", 0]}},
    })
    pruned = prune_for_worker(g)
    assert pruned is not g
    assert all(pruned.nodes[n] is not g.nodes[n] for n in g.nodes)

    w0 = prepare_for_participant(g, "worker", {}, ["0", "1"],
                                 worker_index=0)
    w1 = prepare_for_participant(g, "worker", {}, ["0", "1"],
                                 worker_index=1)
    assert w0.nodes["1"].hidden["worker_id"] == "worker_0"
    assert w1.nodes["1"].hidden["worker_id"] == "worker_1"
    assert "worker_id" not in g.nodes["1"].hidden


class TestStagedImageCache:
    """VERDICT r4 #6: images pulled from the master are cached (30 s,
    reference gpupanel.js:1364-1416) so a multi-worker dispatch does ONE
    master read per image and N worker pushes."""

    def test_one_master_read_for_two_workers(self):
        import base64

        from aiohttp import web
        from comfyui_distributed_tpu.workflow import orchestrate as orch

        counts = {"load": 0, "upload": 0}

        async def load_image(request):
            counts["load"] += 1
            return web.json_response(
                {"image_data": base64.b64encode(b"pngbytes").decode()})

        async def upload(request):
            counts["upload"] += 1
            await request.post()
            return web.json_response({"name": "in.png"})

        async def go():
            app = web.Application()
            app.router.add_post("/distributed/load_image", load_image)
            app.router.add_post("/upload/image", upload)
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = site._server.sockets[0].getsockname()[1]
            url = f"http://127.0.0.1:{port}"
            orch._stage_cache.clear()
            try:
                workers = [{"id": f"worker_{i}", "host": "127.0.0.1",
                            "port": port} for i in range(2)]
                # parallel staging, exactly like run_distributed's gather
                await asyncio.gather(*(
                    orch.stage_images_on_worker(url, w, ["in.png"])
                    for w in workers))
            finally:
                await runner.cleanup()
            return counts

        out = asyncio.run(go())
        assert out["upload"] == 2        # every worker got the image
        assert out["load"] == 1, "master was read once per worker"
