"""Fault-tolerant cluster control plane (ISSUE 4): worker registry with
leases, per-job work ledger with exactly-once check-in, automatic
reassignment of lost tiles/slices, hedged straggler dispatch, the
idempotency-key dedupe in the queue layer, and the registry-aware
preflight.

CPU-only, tier-1-eligible except the two marked-slow loopback
integration tests: THE acceptance (master + 2 workers run a tiled
upscale over real loopback HTTP, one worker is killed mid-job, the
final image contains ALL tiles via reassignment and the trace tree
shows the reassign spans) and the hedge-beats-straggler run.  The
cheap tests drive the same drain/ledger/registry code paths with fed
queues and fake refine callbacks — no model, no compile.
"""

import asyncio
import json
import threading
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.ops.base import OpContext
from comfyui_distributed_tpu.runtime import cluster as cl
from comfyui_distributed_tpu.runtime.jobs import JobStore
from comfyui_distributed_tpu.server.app import ServerState, build_app
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils import trace as tr
from comfyui_distributed_tpu.utils.net import run_async_in_loop


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


@pytest.fixture
def server_loop():
    """A real event loop on a side thread (the server-loop stand-in the
    drain coroutines are scheduled onto)."""
    loop = asyncio.new_event_loop()
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()
    yield loop
    loop.call_soon_threadsafe(loop.stop)
    t.join(timeout=5)


# --- registry: lease state machine -------------------------------------------

class TestClusterRegistry:
    def test_lease_expiry_healthy_to_dead(self):
        reg = cl.ClusterRegistry(lease_s=0.15, suspect_probes=2)
        reg.observe_probe("w0", True)
        assert reg.state("w0") == cl.HEALTHY
        time.sleep(0.2)
        assert reg.state("w0") == cl.DEAD
        # contact resurrects: a restarted worker re-earns its lease
        reg.heartbeat("w0")
        assert reg.state("w0") == cl.HEALTHY

    def test_failed_probes_mark_suspect_then_recovery(self):
        reg = cl.ClusterRegistry(lease_s=30.0, suspect_probes=2)
        reg.observe_probe("w0", True)
        reg.observe_probe("w0", False)
        assert reg.state("w0") == cl.HEALTHY  # one failure < threshold
        reg.observe_probe("w0", False)
        assert reg.state("w0") == cl.SUSPECT
        reg.observe_probe("w0", True)
        assert reg.state("w0") == cl.HEALTHY

    def test_config_seed_stays_unknown_until_contact(self):
        reg = cl.ClusterRegistry(lease_s=0.05, suspect_probes=1)
        reg.seed_from_config([
            {"id": "w0", "enabled": True, "port": 1},
            {"id": "off", "enabled": False, "port": 2}])
        time.sleep(0.1)
        # never contacted: UNKNOWN, not DEAD — preflight probes it
        assert reg.state("w0") == cl.UNKNOWN
        assert reg.state("off") == cl.UNKNOWN  # disabled never seeded
        assert "off" not in reg.snapshot()["workers"]

    def test_touch_only_renews_known_ids(self):
        reg = cl.ClusterRegistry(lease_s=30.0)
        reg.touch("worker_0")   # positional wire label, unknown
        assert "worker_0" not in reg.snapshot()["workers"]
        reg.register("w1")
        reg.touch("w1")
        assert reg.state("w1") == cl.HEALTHY

    def test_transitions_recorded(self):
        reg = cl.ClusterRegistry(lease_s=0.1, suspect_probes=1)
        reg.observe_probe("w0", True)
        time.sleep(0.15)
        reg.state("w0")
        trans = reg.snapshot()["transitions"]
        assert [(t["from"], t["to"]) for t in trans
                if t["worker_id"] == "w0"] == [
            (cl.UNKNOWN, cl.HEALTHY), (cl.HEALTHY, cl.DEAD)]


# --- ledger: exactly-once, reassignment, hedging -----------------------------

class TestWorkLedger:
    def test_check_in_exactly_once(self):
        led = cl.WorkLedger()
        led.create_job("j", {0: "master", 1: "w0"})
        assert led.check_in("j", 0, "master") is True
        assert led.check_in("j", 0, "master") is False   # retried POST
        assert led.check_in("j", 0, "w0") is False       # hedge loser
        assert led.pending("j") == [1]
        assert led.progress("j") == (1, 2)
        # unknown jobs are a no-op pass-through (worker side, SPMD mode)
        assert led.check_in("nope", 5, "x") is True

    def test_reassign_skips_done_units(self):
        led = cl.WorkLedger()
        led.create_job("j", {0: "w0", 1: "w0", 2: "w1"})
        led.check_in("j", 0, "w0")
        moved = led.reassign("j", [0, 1], "master")
        assert moved == [1]
        assert led.pending("j", owner="master") == [1]
        assert led.attempts("j", 1) == 2

    def test_hedge_first_completion_wins(self):
        led = cl.WorkLedger()
        led.create_job("j", {0: "w0", 1: "w0"})
        assert led.mark_hedged("j", [0, 1], "master") == [0, 1]
        assert led.mark_hedged("j", [0], "master") == []  # already hedged
        w0 = tr.GLOBAL_COUNTERS.get("cluster_hedge_wins")
        l0 = tr.GLOBAL_COUNTERS.get("cluster_hedge_losses")
        # unit 0: the hedge (master) lands first -> win; the owner's
        # late completion is deduped
        assert led.check_in("j", 0, "master") is True
        assert led.check_in("j", 0, "w0") is False
        # unit 1: the owner beats the hedge -> loss
        assert led.check_in("j", 1, "w0") is True
        assert tr.GLOBAL_COUNTERS.get("cluster_hedge_wins") == w0 + 1
        assert tr.GLOBAL_COUNTERS.get("cluster_hedge_losses") == l0 + 1

    def test_overdue_units_gated_on_progress_and_latency(self):
        led = cl.WorkLedger()
        led.create_job("j", {i: ("master" if i < 2 else "w0")
                             for i in range(4)})
        # no completions yet: no latency estimate, nothing overdue
        assert led.overdue_units("j", factor=0.0, min_progress_pct=0.0,
                                 min_wait_s=0.0) == {}
        led.check_in("j", 0, "master")
        led.check_in("j", 1, "master")
        # progress gate: 50% done < 75% required
        assert led.overdue_units("j", factor=0.0, min_progress_pct=75.0,
                                 min_wait_s=0.0) == {}
        # the wait floor keeps sub-threshold units un-hedged even with
        # a tiny latency estimate
        assert led.overdue_units("j", factor=0.0, min_progress_pct=50.0,
                                 min_wait_s=30.0) == {}
        time.sleep(0.02)
        over = led.overdue_units("j", factor=0.0, min_progress_pct=50.0,
                                 min_wait_s=0.0)
        assert set(over) == {2, 3} and over[2] == "w0"

    def test_unmark_hedged_restores_eligibility(self):
        """A hedge that never launched (no target / dispatch failed)
        must not pin the unit: rolled back, it stays visible to the
        dead-owner scan and future hedges."""
        led = cl.WorkLedger()
        led.create_job("j", {0: "w0"})
        assert led.mark_hedged("j", [0]) == [0]
        assert led.owners_of_pending("j", skip_hedged=True) == {}
        led.unmark_hedged("j", [0])
        assert led.owners_of_pending("j", skip_hedged=True) == {0: "w0"}
        assert led.attempts("j", 0) == 1
        assert led.mark_hedged("j", [0]) == [0]  # hedgeable again
        led.finish_job("j")

    def test_finish_job_summary(self):
        led = cl.WorkLedger()
        led.create_job("j", {0: "w0", 1: "w1"})
        led.check_in("j", 0, "w0")
        led.reassign("j", [1], "master")
        summary = led.finish_job("j")
        assert summary["done_units"] == 1
        assert summary["pending_units"] == ["1"]
        assert summary["reassigned_units"] == 1
        assert not led.has_job("j")
        assert led.snapshot()["completed_jobs"][-1]["job_id"] == "j"

    def test_redispatch_callback(self):
        led = cl.WorkLedger()
        led.create_job("j", {0: "w0"})
        calls = []

        async def fn(units, lost):
            calls.append((list(units), lost))
            return True

        led.set_redispatcher("j", fn)
        assert led.has_redispatcher("j")
        assert asyncio.run(led.redispatch("j", [0], "w0")) is True
        assert calls == [([0], "w0")]
        # a raising redispatcher degrades to False, never crashes
        async def boom(units, lost):
            raise RuntimeError("no route")

        led.set_redispatcher("j", boom)
        assert asyncio.run(led.redispatch("j", [0], "w0")) is False
        led.finish_job("j")
        assert not led.has_redispatcher("j")


# --- queue-layer idempotency (satellite) -------------------------------------

class TestJobStoreIdempotency:
    def _drain_all(self, q):
        out = []
        while not q.empty():
            out.append(q.get_nowait())
        return out

    def test_tile_replay_acked_but_not_requeued(self):
        async def run():
            js = JobStore()
            await js.prepare_tile_job("j")
            item = {"tile_idx": 3, "worker_id": "w0"}
            assert await js.put_tile("j", item, idem_key="w0:3:0")
            # the retried POST of the SAME send: acknowledged, dropped
            assert await js.put_tile("j", item, idem_key="w0:3:0")
            # a new dispatch attempt is a distinct key: enqueued
            assert await js.put_tile("j", item, idem_key="w0:3:1")
            q = await js.get_tile_queue("j")
            items = self._drain_all(q)
            # key state dies with the queue
            await js.remove_tile_queue("j")
            await js.prepare_tile_job("j")
            assert await js.put_tile("j", item, idem_key="w0:3:0")
            q2 = await js.get_tile_queue("j")
            return items, self._drain_all(q2)

        items, after = asyncio.run(run())
        assert len(items) == 2
        assert len(after) == 1

    def test_image_replay_and_keyless_passthrough(self):
        async def run():
            js = JobStore()
            await js.prepare_job("j")
            assert await js.put_result("j", {"worker_id": "w"},
                                       idem_key="w:0:0")
            assert await js.put_result("j", {"worker_id": "w"},
                                       idem_key="w:0:0")
            # keyless senders (older peers) keep the old semantics
            assert await js.put_result("j", {"worker_id": "w"})
            assert await js.put_result("j", {"worker_id": "w"})
            q = await js.get_queue("j")
            return self._drain_all(q)

        assert len(asyncio.run(run())) == 3


# --- registry-aware preflight (satellite) ------------------------------------

class TestPreflightRegistry:
    def test_dead_worker_skipped_without_probe(self, tmp_path):
        """A registry-DEAD worker is dropped even though its socket
        still answers — the died-between-jobs case the probe alone
        cannot catch."""
        from comfyui_distributed_tpu.workflow import dispatcher as dsp

        async def go():
            state = ServerState(config_path=str(tmp_path / "c.json"),
                                start_exec_thread=False)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                port = client.server.port
                workers = [{"id": "wdead", "host": "127.0.0.1",
                            "port": port, "enabled": True},
                           {"id": "wok", "host": "127.0.0.1",
                            "port": port, "enabled": True}]
                reg = cl.ClusterRegistry(lease_s=0.05, suspect_probes=1)
                reg.observe_probe("wdead", True)
                await asyncio.sleep(0.1)     # lease expires -> DEAD
                alive = await dsp.preflight_check(workers, registry=reg)
                assert [w["id"] for w in alive] == ["wok"]
                # the probe result fed the registry: wok is now healthy
                assert reg.state("wok") == cl.HEALTHY
            finally:
                await client.close()

        asyncio.run(go())


class TestRedispatcherIdentity:
    def test_image_redispatch_follows_unit_not_current_owner(
            self, monkeypatch):
        """Cascade regression: wA's slice was moved to wB, then wB dies.
        The re-redispatch must render unit wA's slice (worker_index of
        wA) on a healthy target — deriving identity from the CURRENT
        owner (wB) would re-render wB's already-delivered slice and
        strand wA's forever."""
        from comfyui_distributed_tpu.workflow import dispatcher as dsp
        from comfyui_distributed_tpu.workflow import orchestrate as orch
        from comfyui_distributed_tpu.workflow.graph import parse_workflow

        graph = parse_workflow(
            {"1": {"class_type": "DistributedCollector", "inputs": {}}})
        enabled = ["wA", "wB", "wC"]
        alive = [{"id": w, "host": "127.0.0.1", "port": 1}
                 for w in enabled]
        reg = cl.ClusterRegistry(lease_s=60.0, suspect_probes=1)
        reg.observe_probe("wA", False)   # the first casualty: not healthy
        reg.observe_probe("wC", True)
        led = cl.WorkLedger()
        led.create_job("jimg", {w: w for w in enabled}, kind="image")
        sent = []

        async def fake_dispatch(worker, wgraph, client_id=None,
                                extra_data=None):
            sent.append((str(worker["id"]), wgraph))

        monkeypatch.setattr(dsp, "dispatch_to_worker", fake_dispatch)
        orch._register_redispatchers(graph, {"1": "jimg"}, enabled,
                                     alive, "http://m", "c", None,
                                     reg, led)
        led.check_in("jimg", "wB", "wB")
        led.check_in("jimg", "wC", "wC")
        led.reassign("jimg", ["wA"], "wB")    # first recovery attempt
        # wB dies: the drain asks to redispatch pending unit wA
        assert asyncio.run(led.redispatch("jimg", ["wA"], "wB")) is True
        target, wgraph = sent[-1]
        assert target == "wC"                 # the only healthy peer
        col = next(n for n in wgraph.nodes.values()
                   if n.class_type == "DistributedCollector")
        # identity = unit wA's slot (index 0), NOT wB's (index 1)
        assert col.hidden["worker_id"] == "worker_0"
        assert col.hidden["dispatch_attempt"] == 3
        assert led.owners_of_pending("jimg") == {"wA": "wC"}
        led.finish_job("jimg")


# --- heartbeat + routes ------------------------------------------------------

class TestClusterRoutes:
    def test_register_heartbeat_and_snapshot(self, tmp_path):
        async def go():
            state = ServerState(config_path=str(tmp_path / "c.json"),
                                start_exec_thread=False)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                r = await client.post("/distributed/register",
                                      json={"worker_id": "ext0",
                                            "port": 9999})
                assert r.status == 200
                body = await r.json()
                assert body["state"] == cl.HEALTHY
                assert body["lease_s"] == state.cluster.lease_s
                r = await client.post("/distributed/heartbeat",
                                      json={"worker_id": "ext0"})
                assert r.status == 200
                r = await client.get("/distributed/cluster")
                snap = await r.json()
                assert snap["workers"]["ext0"]["state"] == cl.HEALTHY
                assert snap["policy"] in C.FAULT_POLICIES
                assert "ledger" in snap and "hedge" in snap
                # metrics carry the cluster block + prom gauge
                m = await (await client.get("/distributed/metrics")).json()
                assert "ext0" in m["cluster"]["workers"]
                prom = await (await client.get(
                    "/distributed/metrics.prom")).text()
                assert 'dtpu_cluster_workers{state="healthy"}' in prom
                # missing id -> 400
                r = await client.post("/distributed/heartbeat", json={})
                assert r.status == 400
            finally:
                await client.close()

        asyncio.run(go())

    def test_heartbeat_sender_renews_lease(self, tmp_path):
        async def go():
            state = ServerState(config_path=str(tmp_path / "c.json"),
                                start_exec_thread=False)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                url = f"http://127.0.0.1:{client.server.port}"
                hb = cl.HeartbeatSender(url, "hb0", interval=999,
                                        port=8290)
                loop = asyncio.get_running_loop()
                ok = await loop.run_in_executor(None, hb.beat_once)
                assert ok and hb.beats_sent == 1
                assert state.cluster.state("hb0") == cl.HEALTHY
                assert state.cluster.snapshot()["workers"]["hb0"][
                    "port"] == 8290
            finally:
                await client.close()

        asyncio.run(go())


# --- drain-level recovery (fed queues, fake refine — no model) ---------------

def _mk_ctx(server_loop, ledger=None, registry=None):
    return OpContext(job_store=JobStore(), server_loop=server_loop,
                     ledger=ledger, cluster=registry)


def _tile_item(idx, wid, is_last=False):
    return {"tile_idx": idx, "worker_id": wid, "is_last": is_last,
            "x": 0, "y": 0, "extracted_width": 1, "extracted_height": 1,
            "padding": 0, "tensor": np.zeros((1, 1, 3), np.float32)}


class TestCollectDrainRecovery:
    def _op(self):
        from comfyui_distributed_tpu.ops.tiled_upscale import (
            UltimateSDUpscaleDistributed)
        return UltimateSDUpscaleDistributed()

    def test_dead_owner_units_reassigned_to_master(self, server_loop,
                                                   monkeypatch):
        """Lease expiry mid-drain: the dead worker's pending units are
        refined master-side (fake refine) and check in exactly once —
        the collect returns with ZERO pending units."""
        monkeypatch.setenv(C.FAULT_POLICY_ENV, "reassign")
        monkeypatch.setenv(C.HEDGE_ENV, "0")
        ledger = cl.WorkLedger()
        registry = cl.ClusterRegistry(lease_s=0.2, suspect_probes=1)
        registry.observe_probe("w0", True)
        registry.observe_probe("w1", True)
        ctx = _mk_ctx(server_loop, ledger, registry)
        mj = "job_reassign"
        ledger.create_job(mj, {0: "master", 1: "w0", 2: "w1", 3: "w1"})
        ledger.check_in(mj, 0, "master")
        refined_units = []

        def refine(units):
            refined_units.extend(units)
            return {u: np.zeros((2, 2, 3), np.float32) for u in units}

        run_async_in_loop(ctx.job_store.prepare_tile_job(mj),
                          server_loop, timeout=5)
        # w0 delivers; w1 never does and its lease expires
        run_async_in_loop(ctx.job_store.put_tile(
            mj, _tile_item(1, "w0", is_last=True)), server_loop,
            timeout=5)
        t0 = time.monotonic()
        collected = self._op()._collect_tiles(ctx, mj, 2,
                                              refine_window=refine)
        assert sorted(refined_units) == [2, 3]
        assert set(collected) == {1, 2, 3}
        assert "window_tensor" in collected[2]
        assert ledger.pending(mj) == []
        # recovery came from the lease, not the 60s drain deadline
        assert time.monotonic() - t0 < C.TILE_COLLECTION_TIMEOUT / 2
        summary = ledger.finish_job(mj)
        assert summary["reassigned_units"] == 2

    def test_policy_fail_raises_on_dead_owner(self, server_loop,
                                              monkeypatch):
        monkeypatch.setenv(C.FAULT_POLICY_ENV, "fail")
        monkeypatch.setenv(C.HEDGE_ENV, "0")
        ledger = cl.WorkLedger()
        registry = cl.ClusterRegistry(lease_s=0.1, suspect_probes=1)
        registry.observe_probe("w0", True)
        ctx = _mk_ctx(server_loop, ledger, registry)
        mj = "job_fail"
        ledger.create_job(mj, {0: "w0"})
        run_async_in_loop(ctx.job_store.prepare_tile_job(mj),
                          server_loop, timeout=5)
        with pytest.raises(cl.ClusterFaultError, match="w0"):
            self._op()._collect_tiles(ctx, mj, 1,
                                      refine_window=lambda u: {})
        ledger.finish_job(mj)

    def test_policy_partial_keeps_seed_semantics(self, server_loop,
                                                 monkeypatch):
        """partial: the drain NEVER recovers — it waits out the
        no-progress timeout and returns what arrived (the seed
        behavior), leaving the lost units pending."""
        monkeypatch.setenv(C.FAULT_POLICY_ENV, "partial")
        monkeypatch.setenv(C.HEDGE_ENV, "0")
        monkeypatch.setattr(C, "TILE_WAIT_TIMEOUT", 0.3)
        ledger = cl.WorkLedger()
        registry = cl.ClusterRegistry(lease_s=0.05, suspect_probes=1)
        registry.observe_probe("w0", True)
        ctx = _mk_ctx(server_loop, ledger, registry)
        mj = "job_partial"
        ledger.create_job(mj, {0: "w0", 1: "w1"})
        run_async_in_loop(ctx.job_store.prepare_tile_job(mj),
                          server_loop, timeout=5)
        run_async_in_loop(ctx.job_store.put_tile(
            mj, _tile_item(0, "w0", is_last=True)), server_loop,
            timeout=5)
        refine_calls = []
        collected = self._op()._collect_tiles(
            ctx, mj, 2, refine_window=lambda u: refine_calls.append(u))
        assert set(collected) == {0}
        assert refine_calls == []
        assert ledger.pending(mj) == [1]
        ledger.finish_job(mj)

    def test_hedge_refines_overdue_straggler_first_wins(self, server_loop,
                                                        monkeypatch):
        """The straggler's units get speculatively refined master-side
        once the job passes the progress gate; its late tiles then
        dedupe as hedge losses."""
        monkeypatch.setenv(C.FAULT_POLICY_ENV, "reassign")
        monkeypatch.setenv(C.HEDGE_ENV, "1")
        monkeypatch.setenv(C.HEDGE_PCT_ENV, "25")
        monkeypatch.setenv(C.HEDGE_FACTOR_ENV, "0.1")
        monkeypatch.setenv(C.HEDGE_MIN_WAIT_ENV, "0.05")
        ledger = cl.WorkLedger()
        registry = cl.ClusterRegistry(lease_s=60.0, suspect_probes=9)
        registry.observe_probe("w0", True)
        ctx = _mk_ctx(server_loop, ledger, registry)
        mj = "job_hedge"
        ledger.create_job(mj, {0: "master", 1: "master",
                               2: "w0", 3: "w0"})
        ledger.check_in(mj, 0, "master")
        time.sleep(0.05)
        ledger.check_in(mj, 1, "master")   # latency estimate exists now
        run_async_in_loop(ctx.job_store.prepare_tile_job(mj),
                          server_loop, timeout=5)
        wins0 = tr.GLOBAL_COUNTERS.get("cluster_hedge_wins")

        def refine(units):
            return {u: np.zeros((2, 2, 3), np.float32) for u in units}

        collected = self._op()._collect_tiles(ctx, mj, 1,
                                              refine_window=refine)
        assert set(collected) == {2, 3}
        assert all("window_tensor" in collected[u] for u in (2, 3))
        assert ledger.pending(mj) == []
        assert tr.GLOBAL_COUNTERS.get("cluster_hedge_wins") == wins0 + 2
        summary = ledger.finish_job(mj)
        assert summary["hedged_units"] == 2

    def test_no_ledger_keeps_precluster_drain(self, server_loop):
        """Without a ledger the drain is the seed's done-count loop."""
        ctx = _mk_ctx(server_loop)
        mj = "job_legacy"
        run_async_in_loop(ctx.job_store.prepare_tile_job(mj),
                          server_loop, timeout=5)
        for idx, last in ((0, False), (1, True)):
            run_async_in_loop(ctx.job_store.put_tile(
                mj, _tile_item(idx, "w0", is_last=last)), server_loop,
                timeout=5)
        collected = self._op()._collect_tiles(ctx, mj, 1)
        assert set(collected) == {0, 1}


# --- loopback integration ----------------------------------------------------

def upscale_prompt(seed=7, size=64, tile=32, steps=1):
    """LoadImage synthesizes a deterministic 512px card (missing file),
    scaled to 64px -> 4 tiles of 32px: master [0,1], w0 [2], w1 [3]."""
    return {
        "7": {"class_type": "CheckpointLoaderSimple",
              "inputs": {"ckpt_name": "tiny.safetensors"}},
        "5": {"class_type": "CLIPTextEncode",
              "inputs": {"text": "a map", "clip": ["7", 1]}},
        "6": {"class_type": "CLIPTextEncode",
              "inputs": {"text": "", "clip": ["7", 1]}},
        "10": {"class_type": "LoadImage",
               "inputs": {"image": "__cluster_card__.png"}},
        "11": {"class_type": "ImageScale",
               "inputs": {"image": ["10", 0],
                          "upscale_method": "bilinear",
                          "width": size, "height": size,
                          "crop": "disabled"}},
        "2": {"class_type": "UltimateSDUpscaleDistributed",
              "inputs": {"upscaled_image": ["11", 0], "model": ["7", 0],
                         "positive": ["5", 0], "negative": ["6", 0],
                         "vae": ["7", 2], "seed": seed, "steps": steps,
                         "cfg": 2.0, "sampler_name": "euler",
                         "scheduler": "normal", "denoise": 0.4,
                         "tile_width": tile, "tile_height": tile,
                         "padding": 8, "mask_blur": 2,
                         "force_uniform_tiles": True}},
        "3": {"class_type": "PreviewImage", "inputs": {"images": ["2", 0]}},
    }


async def _wait_history(client, pid, timeout_s=240.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        hist = await (await client.get("/history")).json()
        if pid in hist:
            return hist[pid]
        await asyncio.sleep(0.1)
    raise AssertionError(f"prompt {pid} never finished")


class _Cluster:
    """Master + N workers as in-process ServerStates over real loopback
    HTTP sockets (the test_observability topology, plus health polling
    feeding the lease registry)."""

    def __init__(self, tmp_path, n_workers=2):
        self.tmp_path = tmp_path
        self.n_workers = n_workers
        self.workers = []        # (state, client)
        self.master_state = None
        self.master_client = None

    async def start(self):
        import os
        cfg_workers = []
        for i in range(self.n_workers):
            wdir = self.tmp_path / f"worker{i}"
            os.makedirs(wdir / "in"), os.makedirs(wdir / "out")
            st = ServerState(config_path=str(wdir / "cfg.json"),
                             input_dir=str(wdir / "in"),
                             output_dir=str(wdir / "out"),
                             is_worker=True, start_exec_thread=True)
            client = TestClient(TestServer(build_app(st)))
            await client.start_server()
            self.workers.append((st, client))
            cfg_workers.append({"id": f"w{i}", "host": "127.0.0.1",
                                "port": client.server.port,
                                "enabled": True})
        mdir = self.tmp_path / "master"
        os.makedirs(mdir / "in"), os.makedirs(mdir / "out")
        with open(mdir / "cfg.json", "w") as f:
            json.dump({"workers": cfg_workers,
                       "master": {"host": "127.0.0.1"},
                       "settings": {}}, f)
        self.master_state = ServerState(
            config_path=str(mdir / "cfg.json"),
            input_dir=str(mdir / "in"), output_dir=str(mdir / "out"),
            is_worker=False, start_exec_thread=True)
        self.master_client = TestClient(
            TestServer(build_app(self.master_state)))
        await self.master_client.start_server()
        self.master_state.port = self.master_client.server.port
        return self

    async def stop(self):
        self.master_state.health.stop()
        if self.master_client is not None:
            await self.master_client.close()
        for st, client in self.workers:
            try:
                await client.close()
            except Exception:  # noqa: BLE001 - may already be closed
                pass
        self.master_state.drain(5)
        for st, _ in self.workers:
            st.drain(5)


class TestFaultAcceptance:
    @pytest.mark.slow
    def test_kill_one_worker_mid_job_all_tiles_recovered(self, tmp_path,
                                                         monkeypatch):
        """THE acceptance criterion: with DTPU_FAULT_POLICY=reassign,
        killing 1 of 2 workers mid tiled-upscale still yields a complete
        image — every ledger unit checked in exactly once — and the
        reassignment is visible as spans in the job's trace tree."""
        monkeypatch.setenv(C.FAULT_POLICY_ENV, "reassign")
        monkeypatch.setenv(C.HEDGE_ENV, "0")      # isolate the lease path
        monkeypatch.setenv(C.LEASE_ENV, "1.0")
        monkeypatch.setenv(C.SUSPECT_PROBES_ENV, "1")

        async def go():
            clu = await _Cluster(tmp_path, n_workers=2).start()
            try:
                # w1 will die mid-job: it refines its tile but the send
                # loop drops everything (0 tiles sent, no is_last)
                clu.workers[1][0].fault_inject = {"drop_tiles_after": 0}
                # establish w1's lease so its death is a real
                # healthy->dead transition, then poll fast
                clu.master_state.health.interval = 0.2
                await asyncio.get_running_loop().run_in_executor(
                    None, clu.master_state.health.poll_once)
                assert clu.master_state.cluster.state("w1") == cl.HEALTHY
                clu.master_state.health.start()

                r = await clu.master_client.post("/prompt", json={
                    "prompt": upscale_prompt(), "client_id": "acc"})
                assert r.status == 200, await r.text()
                body = await r.json()
                assert sorted(body["workers"]) == ["w0", "w1"], body
                pid = body["prompt_id"]
                # the dispatch landed (the POST above returned after
                # fan-out) — now the worker's server dies
                await clu.workers[1][1].close()

                hist = await _wait_history(clu.master_client, pid)
                assert hist["status"] == "success", hist
                assert hist["images"] == 1

                # ledger: every unit checked in exactly once, the lost
                # one via reassignment
                snap = await (await clu.master_client.get(
                    "/distributed/cluster")).json()
                jobs = [j for j in snap["ledger"]["completed_jobs"]
                        if j["kind"] == "tile"]
                assert jobs, snap["ledger"]
                job = jobs[-1]
                assert job["done_units"] == job["total_units"] == 4
                assert job["pending_units"] == []
                assert job["reassigned_units"] >= 1
                assert snap["workers"]["w1"]["state"] == cl.DEAD

                # the reassignment is visible in the trace tree
                r = await clu.master_client.get(
                    f"/distributed/trace/{pid}")
                assert r.status == 200
                rec = await r.json()
                names = {s["name"] for s in rec["spans"]}
                assert "reassign" in names, sorted(names)
                assert "collect" in names
                re_spans = [s for s in rec["spans"]
                            if s["name"] == "reassign"]
                assert any((s.get("attrs") or {}).get("lost") == "w1"
                           for s in re_spans), re_spans
                # exactly-once at the blend: no duplicate check-ins won
                assert {s["trace_id"] for s in rec["spans"]} == \
                    {rec["trace_id"]}
            finally:
                await clu.stop()

        asyncio.run(go())

    @pytest.mark.slow
    def test_armed_and_idle_does_no_speculative_work(
            self, tmp_path, monkeypatch, assert_nothing_compiled):
        """While every worker is healthy the armed control plane
        (reassign policy, hedging on) is free where it can be counted:
        no unit reassigned or hedged, and a warm job lowers and compiles
        nothing."""
        monkeypatch.setenv(C.FAULT_POLICY_ENV, "reassign")
        monkeypatch.setenv(C.HEDGE_ENV, "1")
        # jax compute starves the shared loop of this one process: leases
        # generous enough that live workers are not taken for dead
        monkeypatch.setenv(C.LEASE_ENV, "30.0")
        monkeypatch.setenv(C.SUSPECT_PROBES_ENV, "3")

        async def go():
            clu = await _Cluster(tmp_path, n_workers=2).start()
            try:
                await asyncio.get_running_loop().run_in_executor(
                    None, clu.master_state.health.poll_once)
                clu.master_state.health.start()
                # two jobs warm every participant's programs (the second
                # still loads a few); the third is the one held to zero
                for seed in (4, 5, 6):
                    mark = tr.GLOBAL_RETRACES.mark()
                    r = await clu.master_client.post("/prompt", json={
                        "prompt": upscale_prompt(seed=seed),
                        "client_id": "acc"})
                    body = await r.json()
                    assert sorted(body["workers"]) == ["w0", "w1"], body
                    hist = await _wait_history(clu.master_client,
                                               body["prompt_id"])
                    assert hist["status"] == "success", hist
                assert_nothing_compiled(tr.GLOBAL_RETRACES.since(mark))
                snap = await (await clu.master_client.get(
                    "/distributed/cluster")).json()
                jobs = [j for j in snap["ledger"]["completed_jobs"]
                        if j["kind"] == "tile"]
                assert len(jobs) == 3
                for job in jobs:
                    assert job["done_units"] == job["total_units"] == 4
                    assert job["reassigned_units"] == 0, job
                    assert job["hedged_units"] == 0, job
            finally:
                await clu.stop()

        asyncio.run(go())

    @pytest.mark.slow
    def test_policy_partial_preserves_seed_behavior(self, tmp_path,
                                                    monkeypatch):
        """Opt-out: DTPU_FAULT_POLICY=partial blends what arrived (the
        seed's semantics) — the job still succeeds, the ledger records
        the loss, and no reassign span exists."""
        monkeypatch.setenv(C.FAULT_POLICY_ENV, "partial")
        monkeypatch.setenv(C.HEDGE_ENV, "0")
        monkeypatch.setenv(C.LEASE_ENV, "1.0")
        monkeypatch.setenv(C.SUSPECT_PROBES_ENV, "1")
        monkeypatch.setattr(C, "TILE_WAIT_TIMEOUT", 3.0)
        monkeypatch.setattr(C, "TILE_COLLECTION_TIMEOUT", 20.0)

        async def go():
            clu = await _Cluster(tmp_path, n_workers=2).start()
            try:
                clu.workers[1][0].fault_inject = {"drop_tiles_after": 0}
                r = await clu.master_client.post("/prompt", json={
                    "prompt": upscale_prompt(seed=21),
                    "client_id": "par"})
                assert r.status == 200, await r.text()
                pid = (await r.json())["prompt_id"]
                await clu.workers[1][1].close()
                hist = await _wait_history(clu.master_client, pid)
                assert hist["status"] == "success", hist
                snap = await (await clu.master_client.get(
                    "/distributed/cluster")).json()
                job = [j for j in snap["ledger"]["completed_jobs"]
                       if j["kind"] == "tile"][-1]
                assert job["done_units"] == 3
                assert job["pending_units"] == ["3"]
                assert job["reassigned_units"] == 0
                rec = await (await clu.master_client.get(
                    f"/distributed/trace/{pid}")).json()
                assert "reassign" not in {s["name"]
                                          for s in rec["spans"]}
            finally:
                await clu.stop()

        asyncio.run(go())
