"""Resource telemetry plane (ISSUE 5): probes, rings, monitor, per-job
HBM attribution, fleet federation, freed-bytes clear_memory and the
build-info gauge.

All CPU-only: the device-memory probe exercises the RSS fallback the CPU
backend forces (its ``memory_stats()`` returns None on this JAX), and
the federation acceptance runs a real loopback master+worker pair over
aiohttp test servers.
"""

import asyncio
import time

import pytest

from aiohttp.test_utils import TestClient, TestServer

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.server.app import ServerState, build_app
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils import resource as res
from comfyui_distributed_tpu.utils import trace as tr

from test_observability import (make_prompt, run_with_client,
                                validate_prometheus)


@pytest.fixture(autouse=True)
def tiny_family(monkeypatch):
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny")
    yield


@pytest.fixture(autouse=True)
def tracing_on():
    was = tr.tracing_enabled()
    tr.set_tracing(True)
    yield
    tr.set_tracing(was)


FAKE_SNAPSHOT = {
    "t": 1.0, "device_bytes_in_use": 111, "device_peak_bytes": 222,
    "device_bytes_limit": None, "host_rss_bytes": 333,
    "utilization": 0.5, "queue_depth": 2, "source": "memory_stats",
}


def test_prom_families_skip_non_numeric_wire_values():
    # a version-skewed worker shipping "n/a" costs its row, not the
    # whole fleet exposition
    fams = res.resource_prom_families({
        "good": dict(FAKE_SNAPSHOT),
        "bad": {**FAKE_SNAPSHOT, "device_bytes_in_use": "n/a"},
    })
    by_name = {f[0]: f[3] for f in fams}
    in_use = by_name["dtpu_res_device_bytes_in_use"]
    assert [lbl["worker_id"] for lbl, _ in in_use] == ["good"]
    # the bad worker's other, numeric series still render
    rss = by_name["dtpu_res_host_rss_bytes"]
    assert {lbl["worker_id"] for lbl, _ in rss} == {"good", "bad"}


# --- probes ------------------------------------------------------------------

class TestProbes:
    def test_host_rss_positive(self):
        assert res.host_rss_bytes() > 1_000_000
        assert res.host_rss_peak_bytes() >= res.host_rss_bytes() * 0 + 1

    def test_device_snapshot_shape_and_source(self):
        snap = res.device_memory_snapshot()
        assert snap["source"] in ("memory_stats", "host_rss")
        assert snap["bytes_in_use"] > 0
        assert snap["peak_bytes_in_use"] >= 0

    def test_cpu_backend_falls_back_to_rss(self):
        """On a backend whose devices report no memory_stats (the CPU
        backend here), the probe must fall back to host RSS — never
        return zeros or raise."""
        import jax
        if jax.local_devices()[0].memory_stats() is not None:
            pytest.skip("backend reports real memory_stats")
        snap = res.device_memory_snapshot()
        assert snap["source"] == "host_rss"
        assert snap["n_devices"] == 0
        assert snap["bytes_in_use"] == pytest.approx(
            res.host_rss_bytes(), rel=0.5)

    def test_snapshot_now_wire_shape(self):
        snap = res.snapshot_now(queue_depth=7)
        for key in ("t", "device_bytes_in_use", "device_peak_bytes",
                    "host_rss_bytes", "utilization", "queue_depth",
                    "source"):
            assert key in snap
        assert snap["queue_depth"] == 7


# --- ring timeseries ---------------------------------------------------------

class TestRingTimeseries:
    def test_bounded_newest_wins(self):
        ring = res.RingTimeseries("x", maxlen=4)
        for i in range(10):
            ring.append(float(i), float(i * 10))
        assert len(ring) == 4
        assert ring.total_samples == 10
        vals = ring.values()
        assert [t for t, _ in vals] == [6.0, 7.0, 8.0, 9.0]
        assert ring.last() == (9.0, 90.0)

    def test_stats(self):
        ring = res.RingTimeseries("x", maxlen=8)
        assert ring.stats()["n"] == 0
        for i in range(4):
            ring.append(i, i)
        st = ring.stats()
        assert st == {"n": 4, "last": 3.0, "min": 0.0, "max": 3.0,
                      "mean": 1.5}


# --- the monitor -------------------------------------------------------------

class TestResourceMonitor:
    def test_sampling_and_ring_bounds(self):
        m = res.ResourceMonitor(interval=0.01, ring=8,
                                queue_depth_fn=lambda: 3)
        for _ in range(12):
            m.sample_once()
        snap = m.snapshot()
        assert snap["n_samples"] == 12
        assert snap["ring_max"] == 8
        for name, st in snap["series"].items():
            assert st["n"] <= 8, name
        assert snap["series"]["host_rss_bytes"]["n"] == 8
        latest = snap["latest"]
        assert latest["queue_depth"] == 3
        assert latest["host_rss_bytes"] > 0
        assert len(m.series_tail("host_rss_bytes")) == 8
        assert len(m.series_tail("host_rss_bytes", n=3)) == 3

    def test_thread_start_stop_restart(self):
        m = res.ResourceMonitor(interval=0.01, ring=64)
        m.start()
        deadline = time.monotonic() + 2.0
        while m.snapshot()["n_samples"] < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        m.stop(join=True)
        n = m.snapshot()["n_samples"]
        assert n >= 2
        time.sleep(0.05)
        assert m.snapshot()["n_samples"] == n  # actually stopped
        m.start()  # restartable
        assert m.running
        m.stop(join=True)

    def test_utilization_from_stage_timeline(self):
        m = res.ResourceMonitor(interval=0.01, ring=8)
        assert m.sample_once()["utilization"] is None  # needs two marks
        tr.GLOBAL_STAGES.record("compute", 1000.0)
        assert m.sample_once()["utilization"] == 1.0  # clamped busy
        time.sleep(0.02)
        util = m.sample_once()["utilization"]  # no new compute -> idle
        assert util == 0.0

    def test_queue_depth_fn_failure_tolerated(self):
        def boom():
            raise RuntimeError("torn down")
        m = res.ResourceMonitor(interval=0.01, ring=4,
                                queue_depth_fn=boom)
        snap = m.sample_once()
        assert snap["queue_depth"] is None

    def test_latest_samples_on_demand(self):
        m = res.ResourceMonitor(interval=9999, ring=4)
        assert m.latest()["host_rss_bytes"] > 0

    def test_stop_without_join_then_start_keeps_sampling(self):
        # stop() doesn't join; an immediate start() must not see the
        # dying thread as alive, skip the spawn, and leave the monitor
        # permanently dead while running looks True
        m = res.ResourceMonitor(interval=0.01, ring=64)
        m.start()
        m.stop()
        m.start()
        assert m.running
        n0 = m.snapshot()["n_samples"]
        deadline = time.monotonic() + 2.0
        while m.snapshot()["n_samples"] <= n0 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        m.stop(join=True)
        assert m.snapshot()["n_samples"] > n0

    def test_weak_callable_does_not_pin_owner(self):
        class Owner:
            def depth(self):
                return 7
        owner = Owner()
        fn = res._weak_callable(owner.depth)
        assert fn() == 7
        import gc
        import weakref
        ref = weakref.ref(owner)
        del owner
        gc.collect()
        assert ref() is None  # the wrapper didn't keep it alive
        m = res.ResourceMonitor(interval=9999, ring=4,
                                queue_depth_fn=fn)
        assert m.sample_once()["queue_depth"] is None  # raises -> None
        plain = lambda: 1  # noqa: E731
        assert res._weak_callable(plain) is plain


# --- per-job attribution -----------------------------------------------------

class TestPerJobAttribution:
    def test_execution_result_and_trace_attrs(self, tmp_path):
        """A real tiny run reports per-run resources + per-node memory
        in ExecutionResult AND stamps memory attrs on the execute span,
        so the flight-recorder trace shows HBM next to latency."""
        from comfyui_distributed_tpu.ops.base import OpContext
        from comfyui_distributed_tpu.parallel.mesh import get_runtime
        from comfyui_distributed_tpu.workflow.executor import \
            WorkflowExecutor

        root = tr.start_span("job", attrs={"prompt_id": "p_res_attr"})
        with tr.use_span(root), tr.span("execute"):
            result = WorkflowExecutor(OpContext(
                runtime=get_runtime(),
                output_dir=str(tmp_path))).execute(make_prompt(seed=3))
        root.end()
        tr.GLOBAL_TRACES.commit("p_res_attr", root.trace_id, status="ok",
                                root_span_id=root.span_id)

        r = result.resources
        assert r["source"] in ("memory_stats", "host_rss")
        assert r["host_rss_bytes"] > 0
        assert r["device_bytes_in_use"] > 0
        assert r["device_peak_delta_bytes"] >= 0
        # every executed node got a memory ledger entry
        assert set(result.node_memory) == set(result.timings)
        for entry in result.node_memory.values():
            assert entry["peak_delta_bytes"] >= 0

        rec = tr.GLOBAL_TRACES.get("p_res_attr")
        execute = [s for s in rec["spans"] if s["name"] == "execute"]
        assert execute, "execute span missing from trace"
        attrs = execute[0].get("attrs") or {}
        assert "device_peak_mb" in attrs
        assert "rss_mb" in attrs and attrs["rss_mb"] > 0
        assert attrs["mem_source"] == r["source"]

    def test_kill_switch_disables_attribution_probes(self, tmp_path,
                                                     monkeypatch):
        """DTPU_RESOURCE=0 must cover the executor's per-node/per-run
        probes on the hot path, not just the monitor thread."""
        from comfyui_distributed_tpu.ops.base import OpContext
        from comfyui_distributed_tpu.parallel.mesh import get_runtime
        from comfyui_distributed_tpu.workflow.executor import \
            WorkflowExecutor

        monkeypatch.setenv(C.RESOURCE_ENV, "0")
        result = WorkflowExecutor(OpContext(
            runtime=get_runtime(),
            output_dir=str(tmp_path))).execute(make_prompt(seed=4))
        assert result.resources == {}
        assert result.node_memory == {}


# --- fleet federation --------------------------------------------------------

class TestFederation:
    def test_merge_master_and_heartbeat_worker(self, tmp_path):
        async def body(client, state):
            r = await client.post("/distributed/heartbeat", json={
                "worker_id": "w0", "port": 1234,
                "resources": dict(FAKE_SNAPSHOT)})
            assert r.status == 200
            r = await client.get("/distributed/cluster/metrics")
            assert r.status == 200
            body = await r.json()
            parts = body["participants"]
            assert set(parts) == {"master", "w0"}
            assert parts["master"]["resources"]["host_rss_bytes"] > 0
            assert parts["master"]["age_s"] == 0.0
            w0 = parts["w0"]
            assert w0["resources"]["device_bytes_in_use"] == 111
            assert w0["age_s"] is not None and w0["age_s"] < 5
            assert w0["stale"] is False
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_prom_exposition_labels_both_participants(self, tmp_path):
        async def body(client, state):
            await client.post("/distributed/heartbeat", json={
                "worker_id": "w0", "port": 1234,
                "resources": dict(FAKE_SNAPSHOT)})
            r = await client.get("/distributed/cluster/metrics.prom")
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            text = await r.text()
            types = validate_prometheus(text)
            assert types["dtpu_res_device_bytes_in_use"] == "gauge"
            assert types["dtpu_res_host_rss_bytes"] == "gauge"
            assert 'dtpu_res_device_bytes_in_use{worker_id="master"}' \
                in text
            assert 'dtpu_res_device_bytes_in_use{worker_id="w0"} 111' \
                in text
            assert 'dtpu_res_utilization_ratio{worker_id="w0"} 0.5' \
                in text
            assert 'dtpu_res_snapshot_age_seconds{worker_id="w0"}' in text
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_stale_snapshot_ages_and_flags(self, tmp_path):
        async def body(client, state):
            await client.post("/distributed/heartbeat", json={
                "worker_id": "w0", "resources": dict(FAKE_SNAPSHOT)})
            # age the retained snapshot past the federation TTL; no
            # host:port -> pull-through can't refresh it, the merged
            # view must serve the cached value marked stale
            with state.cluster._lock:
                state.cluster._workers["w0"]["resources_at"] -= 100.0
                state.cluster._workers["w0"]["info"].pop("host", None)
            r = await client.get("/distributed/cluster/metrics")
            w0 = (await r.json())["participants"]["w0"]
            assert w0["age_s"] > 99
            assert w0["stale"] is True
            assert w0["resources"]["device_bytes_in_use"] == 111
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_pull_through_refreshes_from_loopback_worker(self, tmp_path):
        """Federation acceptance: a REAL loopback worker server is
        registered with no heartbeat snapshot at all; the master's
        merged view pulls GET /distributed/resource from it live and
        caches the result back into the registry."""
        async def go():
            wtmp = tmp_path / "worker"
            wtmp.mkdir()
            wstate = ServerState(config_path=str(wtmp / "cfg.json"),
                                 input_dir=str(wtmp),
                                 output_dir=str(wtmp),
                                 is_worker=True,
                                 start_exec_thread=False)
            wclient = TestClient(TestServer(build_app(wstate)))
            await wclient.start_server()
            mstate = ServerState(config_path=str(tmp_path / "cfg.json"),
                                 input_dir=str(tmp_path),
                                 output_dir=str(tmp_path),
                                 start_exec_thread=False)
            mclient = TestClient(TestServer(build_app(mstate)))
            await mclient.start_server()
            try:
                r = await mclient.post("/distributed/heartbeat", json={
                    "worker_id": "w0", "host": "127.0.0.1",
                    "port": wclient.server.port})  # NO resources field
                assert r.status == 200
                r = await mclient.get("/distributed/cluster/metrics")
                parts = (await r.json())["participants"]
                w0 = parts["w0"]
                assert w0["resources"] is not None, \
                    "pull-through never fetched the worker snapshot"
                assert w0["resources"]["host_rss_bytes"] > 0
                # cached back: the registry now holds it
                reg = mstate.cluster.resource_snapshots()["w0"]
                assert reg["resources"] is not None
                assert reg["age_s"] < 5
                # the prom view shows BOTH participants by worker_id
                text = await (await mclient.get(
                    "/distributed/cluster/metrics.prom")).text()
                validate_prometheus(text)
                assert 'worker_id="master"' in text
                assert 'worker_id="w0"' in text
            finally:
                await mclient.close()
                await wclient.close()
        asyncio.run(go())


# --- clear_memory freed bytes ------------------------------------------------

class TestClearMemoryFreed:
    def test_reports_before_after_and_freed(self, tmp_path):
        async def body(client, state):
            r = await client.post("/distributed/clear_memory")
            assert r.status == 200
            body = await r.json()
            assert body["freed_bytes"] >= 0
            assert body["device_bytes_before"] > 0
            assert body["device_bytes_after"] > 0
            assert body["host_rss_before"] > 0
            assert body["source"] in ("memory_stats", "host_rss")
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_cluster_variant_aggregates(self, tmp_path):
        async def body(client, state):
            r = await client.post("/distributed/cluster/clear_memory")
            assert r.status == 200
            body = await r.json()
            assert body["workers"] == {}  # no configured workers
            assert "master" in body["freed_bytes"]
            assert body["freed_bytes_total"] >= 0
        run_with_client(body, tmp_path, start_exec_thread=False)


# --- local metrics surfaces --------------------------------------------------

class TestLocalMetricsSurfaces:
    def test_json_metrics_resources_block(self, tmp_path):
        async def body(client, state):
            m = await (await client.get("/distributed/metrics")).json()
            blk = m["resources"]
            if blk.get("enabled") is False:
                pytest.skip("DTPU_RESOURCE=0 in this environment")
            assert blk["ring_max"] >= 1
            assert set(blk["series"]) == set(res.SERIES)
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_prom_has_build_info_and_resource_gauges(self, tmp_path):
        async def body(client, state):
            text = await (await client.get(
                "/distributed/metrics.prom")).text()
            types = validate_prometheus(text)
            assert types["dtpu_build_info"] == "gauge"
            line = [l for l in text.splitlines()
                    if l.startswith("dtpu_build_info{")][0]
            assert 'jax="' in line and 'platform="' in line \
                and 'version="' in line
            assert line.rstrip().endswith(" 1")
            assert types["dtpu_res_host_rss_bytes"] == "gauge"
            assert types["dtpu_res_device_bytes_in_use"] == "gauge"
            # unlabelled on the per-process surface
            assert any(l.startswith("dtpu_res_host_rss_bytes ")
                       for l in text.splitlines())
        run_with_client(body, tmp_path, start_exec_thread=False)
