"""Elastic fleet under overload (ISSUE 9): token-bucket / fair-dequeue
math, autoscaler hysteresis, the chaos injectors, jittered transport
backoff with Retry-After, deadline-aware hedging, the rehome-heartbeat
regression, and one slow loopback acceptance run (3 tenants + a killed
worker + injected faults)."""

import asyncio
import os
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.runtime import autoscale as autoscale_mod
from comfyui_distributed_tpu.runtime import cluster as cluster_mod
from comfyui_distributed_tpu.server.app import ServerState, build_app
from comfyui_distributed_tpu.utils import chaos as chaos_mod
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils import net as net_mod
from comfyui_distributed_tpu.workflow import scheduler as sched


@pytest.fixture(autouse=True)
def tiny_family(monkeypatch):
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny")
    yield


@pytest.fixture(autouse=True)
def no_leaked_chaos():
    chaos_mod.set_chaos(None)
    yield
    chaos_mod.set_chaos(None)


def make_state(tmp_path, **kw):
    return ServerState(config_path=str(tmp_path / "cfg.json"),
                       input_dir=str(tmp_path / "in"),
                       output_dir=str(tmp_path / "out"), **kw)


# --- token buckets -----------------------------------------------------------

class TestTokenBucket:
    def test_burst_cap_then_refill(self):
        tb = sched.TokenBucket(rate=2.0, burst=3)
        now = 100.0
        assert [tb.try_take(now) for _ in range(5)] == \
            [True, True, True, False, False]
        # 1 second at 2 tokens/s refills two takes
        assert tb.try_take(now + 1.0) and tb.try_take(now + 1.0)
        assert not tb.try_take(now + 1.0)

    def test_zero_rate_is_unlimited(self):
        tb = sched.TokenBucket(rate=0.0, burst=1)
        assert all(tb.try_take() for _ in range(100))

    def test_seconds_until_token(self):
        tb = sched.TokenBucket(rate=4.0, burst=1)
        now = 5.0
        assert tb.try_take(now)
        wait = tb.seconds_until_token(now)
        assert 0.0 < wait <= 0.25


# --- admission ---------------------------------------------------------------

def controller(**kw):
    kw.setdefault("weights", dict(C.TENANT_WEIGHTS_DEFAULT))
    kw.setdefault("shed", dict(C.TENANT_SHED_DEFAULT))
    kw.setdefault("rate", {cls: 0.0 for cls in C.TENANT_CLASSES})
    kw.setdefault("burst", {cls: 10.0 for cls in C.TENANT_CLASSES})
    return sched.AdmissionController(**kw)


class TestAdmission:
    def test_classify_default_is_highest_class(self):
        a = controller()
        assert a.classify(None) == "paid"
        assert a.classify("") == "paid"
        assert a.classify("nonsense") == "paid"
        assert a.classify("BATCH") == "batch"
        assert a.classify("free") == "free"

    def test_shed_ladder_batch_first_paid_never(self):
        a = controller()     # defaults: batch 0.5, free 0.85, paid 1.0
        # at half occupancy only batch sheds
        assert a.admit("batch", "c", 5, 10)["reason"] == "overload"
        assert a.admit("free", "c", 5, 10) is None
        assert a.admit("paid", "c", 5, 10) is None
        # at 90% free sheds too; paid still admitted
        assert a.admit("free", "c", 9, 10)["reason"] == "overload"
        assert a.admit("paid", "c", 9, 10) is None
        # paid sheds only at a genuinely full queue
        assert a.admit("paid", "c", 10, 10)["reason"] == "overload"

    def test_token_bucket_rate_shed_carries_retry_after(self):
        a = controller(rate={"paid": 0.0, "free": 1.0, "batch": 0.0},
                       burst={"paid": 1.0, "free": 2.0, "batch": 1.0})
        assert a.admit("free", "alice", 0, 100) is None
        assert a.admit("free", "alice", 0, 100) is None
        rej = a.admit("free", "alice", 0, 100)
        assert rej["reason"] == "rate" and rej["retry_after_s"] >= 1.0
        # buckets are per client: bob is unaffected by alice's flood
        assert a.admit("free", "bob", 0, 100) is None
        snap = a.snapshot()
        assert snap["per_class"]["free"]["shed_rate"] == 1
        assert snap["per_class"]["free"]["admitted"] == 3

    def test_counters_track_decisions(self):
        a = controller()
        a.admit("paid", "c", 0, 10)
        a.admit("batch", "c", 9, 10)
        a.on_complete("paid")
        per = a.snapshot()["per_class"]
        assert per["paid"] == {"admitted": 1, "shed_rate": 0,
                               "shed_overload": 0, "completed": 1}
        assert per["batch"]["shed_overload"] == 1


class TestFairDequeue:
    def test_stride_distribution_matches_weights(self):
        a = controller()
        queued = {"paid": 50, "free": 50, "batch": 50}
        picks = [a.next_class(queued) for _ in range(20)]
        assert picks.count("paid") == 12
        assert picks.count("free") == 6
        assert picks.count("batch") == 2

    def test_idle_class_cannot_bank_credit(self):
        a = controller()
        # paid runs alone for a long stretch...
        for _ in range(50):
            assert a.next_class({"paid": 1}) == "paid"
        # ...then free arrives: it gets its weighted share, not a
        # starvation burst paid banked against
        picks = [a.next_class({"paid": 5, "free": 5}) for _ in range(9)]
        assert picks.count("free") == 3
        assert picks.count("paid") == 6

    def _item(self, pid, tenant, sig=None):
        return {"id": pid, "tenant": tenant, "sig": sig}

    def test_single_class_is_legacy_contiguous_pop(self):
        a = controller()
        q = [self._item("a", "paid", "s1"), self._item("b", "paid", "s1"),
             self._item("c", "paid", "s2"), self._item("d", "paid", "s1")]
        group = sched.pop_fair_group(q, a, coalesce_max=8)
        assert [g["id"] for g in group] == ["a", "b"]
        assert [i["id"] for i in q] == ["c", "d"]

    def test_fair_pop_keeps_per_class_fifo_and_coalesces(self):
        a = controller(weights={"paid": 1.0, "free": 1.0, "batch": 1.0})
        q = [self._item("f1", "free", "x"), self._item("p1", "paid", "y"),
             self._item("f2", "free", "x"), self._item("p2", "paid", "y")]
        seen = []
        while q:
            group = sched.pop_fair_group(q, a, coalesce_max=8)
            seen.append([g["id"] for g in group])
        flat = [pid for grp in seen for pid in grp]
        # per-class FIFO: f1 before f2, p1 before p2 — always
        assert flat.index("f1") < flat.index("f2")
        assert flat.index("p1") < flat.index("p2")
        # coalescing groups a class's signature-run even when another
        # class's items sit between them in the global queue
        assert ["f1", "f2"] in seen or ["p1", "p2"] in seen


# --- autoscaler hysteresis ---------------------------------------------------

def make_scaler(**kw):
    reg = cluster_mod.ClusterRegistry(lease_s=60.0)
    spawned = []
    retired = []

    def spawner():
        wid = f"auto{len(spawned)}"
        spawned.append(wid)
        reg.register(wid, info={"host": "h", "port": 1}, alive=True)
        return wid

    def retirer(wid):
        retired.append(wid)
        return True

    depth = {"v": 0}
    kw.setdefault("min_workers", 0)
    kw.setdefault("max_workers", 3)
    kw.setdefault("up_queue", 4.0)
    kw.setdefault("down_queue", 1.0)
    kw.setdefault("window", 3)
    kw.setdefault("cooldown_s", 10.0)
    kw.setdefault("interval_s", 0.05)
    kw.setdefault("drain_s", 5.0)
    sc = autoscale_mod.FleetAutoscaler(
        registry=reg, queue_depth_fn=lambda: depth["v"],
        spawner=spawner, retirer=retirer,
        worker_queue_fn=lambda wid: 0, **kw)
    return sc, reg, depth, spawned, retired


class TestAutoscalerHysteresis:
    def test_scale_up_needs_sustained_window(self):
        sc, reg, depth, spawned, _ = make_scaler()
        depth["v"] = 100
        t = 0.0
        sc.sample_once(t)
        sc.sample_once(t + 1)
        assert not spawned          # 2 samples < window of 3
        sc.sample_once(t + 2)
        assert spawned == ["auto0"]

    def test_dip_resets_the_streak(self):
        sc, reg, depth, spawned, _ = make_scaler()
        depth["v"] = 100
        sc.sample_once(0.0)
        sc.sample_once(1.0)
        depth["v"] = 0              # one calm sample resets the streak
        sc.sample_once(2.0)
        depth["v"] = 100
        sc.sample_once(3.0)
        sc.sample_once(4.0)
        assert not spawned
        sc.sample_once(5.0)
        assert len(spawned) == 1

    def test_oscillating_signal_never_flaps(self):
        """The acceptance case: a signal bouncing between the up and
        down bars every sample must produce ZERO actions (the sustained
        window filters it) and therefore zero flaps."""
        sc, reg, depth, spawned, retired = make_scaler(cooldown_s=0.0)
        for i in range(30):
            depth["v"] = 100 if i % 2 == 0 else 0
            sc.sample_once(float(i))
        assert spawned == [] and retired == []
        assert sc.flaps == 0

    def test_cooldown_blocks_consecutive_actions(self):
        sc, reg, depth, spawned, _ = make_scaler(cooldown_s=10.0)
        depth["v"] = 100
        for i in range(3):
            sc.sample_once(float(i))
        assert len(spawned) == 1
        for i in range(3, 9):        # still over bar, inside cooldown
            sc.sample_once(float(i))
        assert len(spawned) == 1
        for i in range(13, 17):      # cooldown over: second spawn
            sc.sample_once(float(i))
        assert len(spawned) == 2

    def test_scale_down_drains_then_retires_and_forgets(self):
        sc, reg, depth, spawned, retired = make_scaler(cooldown_s=0.0)
        depth["v"] = 100
        for i in range(3):
            sc.sample_once(float(i))
        assert spawned == ["auto0"]
        depth["v"] = 0
        for i in range(10, 14):
            sc.sample_once(float(i))
        assert retired == ["auto0"]
        assert reg.snapshot()["workers"].get("auto0") is None  # forgotten
        assert sc.scale_downs == 1

    def test_retiring_worker_is_not_dispatchable(self):
        reg = cluster_mod.ClusterRegistry(lease_s=60.0)
        reg.register("w0", info={}, alive=True)
        assert reg.state("w0") == cluster_mod.HEALTHY
        assert reg.set_retiring("w0")
        assert reg.state("w0") == cluster_mod.RETIRING
        assert "w0" not in reg.healthy_ids()
        reg.set_retiring("w0", False)
        assert reg.state("w0") == cluster_mod.HEALTHY

    def test_forced_retirement_keeps_registry_record(self):
        """A worker stopped at the drain DEADLINE (still owing units)
        must stay in the registry: the collector drains detect lost
        owners via state()==DEAD after the lease ages out — forgetting
        the id would read UNKNOWN forever and skip the reassignment."""
        reg = cluster_mod.ClusterRegistry(lease_s=0.1)
        retired = []
        sc = autoscale_mod.FleetAutoscaler(
            registry=reg, queue_depth_fn=lambda: 100,
            spawner=lambda: (reg.register("autoX", alive=True)
                             and None) or "autoX",
            retirer=lambda wid: retired.append(wid) or True,
            worker_queue_fn=lambda wid: 7,   # NEVER drains
            min_workers=0, max_workers=1, up_queue=4.0,
            down_queue=200.0,                # immediately "under"
            window=1, cooldown_s=0.0, interval_s=0.05, drain_s=1.0)
        sc.sample_once(0.0)                  # spawns autoX
        sc.sample_once(1.0)                  # marks it retiring
        assert reg.state("autoX") == cluster_mod.RETIRING
        sc.sample_once(3.0)                  # deadline passed: forced
        assert retired == ["autoX"]
        # record kept; the expired lease now reads DEAD, which is what
        # the drain-recovery path keys on
        time.sleep(0.15)
        assert reg.state("autoX") == cluster_mod.DEAD

    def test_reversal_inside_flap_window_counts(self):
        sc, reg, depth, spawned, retired = make_scaler(
            cooldown_s=0.0, window=1, flap_window_s=100.0)
        depth["v"] = 100
        sc.sample_once(0.0)
        assert spawned
        depth["v"] = 0
        sc.sample_once(1.0)          # immediate reversal = flap
        assert sc.flaps == 1


# --- chaos injectors ---------------------------------------------------------

class TestChaosInjectors:
    def test_deterministic_with_seed(self):
        a = chaos_mod.ChaosMonkey({"drop_pct": 30, "seed": 5})
        b = chaos_mod.ChaosMonkey({"drop_pct": 30, "seed": 5})

        def rolls(cm):
            out = []
            for _ in range(20):
                try:
                    cm.client_edge("u")
                    out.append(False)
                except chaos_mod.ChaosDropError:
                    out.append(True)
            return out
        assert rolls(a) == rolls(b)
        assert any(rolls(chaos_mod.ChaosMonkey(
            {"drop_pct": 30, "seed": 5})))

    def test_drop_delay_and_5xx(self):
        cm = chaos_mod.ChaosMonkey({"drop_pct": 100})
        with pytest.raises(chaos_mod.ChaosDropError):
            cm.client_edge("http://x")
        cm = chaos_mod.ChaosMonkey({"delay_pct": 100, "delay_s": 0.7})
        assert cm.client_edge("http://x") == 0.7
        cm = chaos_mod.ChaosMonkey({"http_5xx_pct": 100,
                                    "routes": ["/prompt"]})
        assert cm.server_edge("/prompt")[0] == 503
        assert cm.server_edge("/history")[0] is None   # route-scoped

    def test_corrupt_flips_bytes_not_length(self):
        cm = chaos_mod.ChaosMonkey({"corrupt_pct": 100})
        data = bytes(range(64))
        out = cm.corrupt(data)
        assert len(out) == len(data) and out != data
        cm = chaos_mod.ChaosMonkey({})
        assert cm.corrupt(data) == data

    def test_freeze_heartbeats_blocks_beat_once(self):
        chaos_mod.set_chaos({"freeze_heartbeats": ["w-frozen"]})
        hb = cluster_mod.HeartbeatSender("http://127.0.0.1:1",
                                         "w-frozen", interval=999)
        assert hb.beat_once() is False       # no socket ever touched
        other = cluster_mod.HeartbeatSender("http://127.0.0.1:1",
                                            "w-live", interval=999,
                                            port=1)
        # not frozen -> really tries the (dead) master and fails there
        assert other.beat_once(timeout=0.2) is False

    def test_env_arming_and_programmatic_override(self, monkeypatch):
        monkeypatch.setenv(C.CHAOS_ENV, '{"drop_pct": 100}')
        assert chaos_mod.get_chaos().active
        monkeypatch.delenv(C.CHAOS_ENV)
        assert not chaos_mod.get_chaos().active
        chaos_mod.set_chaos({"delay_pct": 100})
        assert chaos_mod.get_chaos().active
        chaos_mod.set_chaos(None)
        assert not chaos_mod.get_chaos().active

    def test_middleware_injects_5xx_on_scoped_route(self, tmp_path):
        async def body():
            state = make_state(tmp_path, start_exec_thread=False)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                chaos_mod.set_chaos({"http_5xx_pct": 100,
                                     "routes": ["/history"]})
                r = await client.get("/history")
                assert r.status == 503
                body_json = await r.json()
                assert "chaos" in body_json["error"]
                # other routes unaffected
                r = await client.get("/distributed/queue_status")
                assert r.status == 200
                chaos_mod.set_chaos(None)
                assert (await client.get("/history")).status == 200
                m = await (await client.get(
                    "/distributed/metrics")).json()
                assert m["chaos"]["injected"].get("5xx", 0) >= 1
            finally:
                await client.close()
        asyncio.run(body())


# --- transport backoff + Retry-After ----------------------------------------

class TestTransportBackoff:
    def test_jittered_schedule_shape(self):
        import random
        rng = random.Random(3)
        delays = net_mod.backoff_delays(5, rng=rng)
        assert len(delays) == 4
        nominal = [0.5, 1.0, 2.0, 4.0]
        for d, n in zip(delays, nominal):
            assert n * (1 - C.SEND_JITTER_FRACTION) <= d <= n
        # jitter de-synchronizes: two retry storms don't share a cadence
        other = net_mod.backoff_delays(5, rng=random.Random(4))
        assert delays != other

    def test_retry_after_parse_and_cap(self):
        assert net_mod._retry_after_hint({"Retry-After": "3"}) == 3.0
        assert net_mod._retry_after_hint(
            {"Retry-After": "99999"}) == C.RETRY_AFTER_CAP_S
        assert net_mod._retry_after_hint({"Retry-After": "bogus"}) is None
        assert net_mod._retry_after_hint({}) is None

    def test_post_retry_honors_retry_after_and_recovers(self, tmp_path):
        from aiohttp import web
        hits = []
        sleeps = []

        async def handler(request):
            hits.append(1)
            if len(hits) < 3:
                return web.json_response({"error": "busy"}, status=429,
                                         headers={"Retry-After": "2"})
            return web.json_response({"status": "ok"})

        real_sleep = asyncio.sleep

        async def fake_sleep(s):
            sleeps.append(s)
            await real_sleep(0)

        async def body():
            app = web.Application()
            app.router.add_post("/up", handler)
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                url = (f"http://127.0.0.1:{client.server.port}/up")
                import aiohttp
                orig = asyncio.sleep
                asyncio.sleep = fake_sleep
                try:
                    await net_mod.post_form_with_retry(
                        url, lambda: aiohttp.FormData(), timeout=5,
                        what="test")
                finally:
                    asyncio.sleep = orig
            finally:
                await client.close()
        asyncio.run(body())
        assert len(hits) == 3
        # the server's Retry-After (2s) overrode the jittered backoff
        # (first nominal delay is <= 0.5s)
        assert sleeps and max(sleeps) >= 2.0

    def test_chaos_drop_is_retried(self, tmp_path):
        from aiohttp import web
        hits = []

        async def handler(request):
            hits.append(1)
            return web.json_response({"status": "ok"})

        real_sleep = asyncio.sleep

        async def fast_sleep(s):
            await real_sleep(0)

        async def body():
            app = web.Application()
            app.router.add_post("/up", handler)
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                import aiohttp
                url = f"http://127.0.0.1:{client.server.port}/up"
                # drop EVERY edge: the send must exhaust its retries
                chaos_mod.set_chaos({"drop_pct": 100})
                asyncio.sleep = fast_sleep
                try:
                    with pytest.raises(chaos_mod.ChaosDropError):
                        await net_mod.post_form_with_retry(
                            url, lambda: aiohttp.FormData(), timeout=5,
                            max_retries=3, what="test")
                    assert hits == []      # nothing reached the wire
                    chaos_mod.set_chaos(None)
                    await net_mod.post_form_with_retry(
                        url, lambda: aiohttp.FormData(), timeout=5,
                        what="test")
                    assert hits == [1]
                finally:
                    asyncio.sleep = real_sleep
            finally:
                await client.close()
        asyncio.run(body())


class TestServerRetryAfter:
    def test_429_carries_retry_after_header(self, tmp_path, monkeypatch):
        monkeypatch.setenv(C.MAX_QUEUE_ENV, "2")
        from tests.test_pipeline import make_prompt

        async def body():
            state = make_state(tmp_path, start_exec_thread=False)
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
                assert "Retry-After" in r.headers
                body_json = await r.json()
                assert int(r.headers["Retry-After"]) == \
                    body_json["retry_after_s"] >= 1
            finally:
                await client.close()
        asyncio.run(body())

    def test_batch_shed_before_paid_over_http(self, tmp_path,
                                              monkeypatch):
        monkeypatch.setenv(C.MAX_QUEUE_ENV, "4")
        from tests.test_pipeline import make_prompt

        async def body():
            state = make_state(tmp_path, start_exec_thread=False)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                # fill half the queue -> batch sheds (bar 0.5), paid ok
                for i in range(2):
                    r = await client.post("/prompt", json={
                        "prompt": make_prompt(i), "client_id": "c",
                        "priority": "paid"})
                    assert r.status == 200
                r = await client.post("/prompt", json={
                    "prompt": make_prompt(7), "client_id": "c",
                    "priority": "batch"})
                assert r.status == 429
                body_json = await r.json()
                assert body_json["tenant"] == "batch"
                assert body_json["reason"] == "overload"
                r = await client.post("/prompt", json={
                    "prompt": make_prompt(8), "client_id": "c",
                    "priority": "paid"})
                assert r.status == 200
                m = await (await client.get(
                    "/distributed/metrics")).json()
                assert m["admission"]["per_class"]["batch"][
                    "shed_overload"] == 1
                assert m["admission"]["queued_by_class"]["paid"] == 3
                fleet = await (await client.get(
                    "/distributed/fleet")).json()
                assert fleet["admission"]["per_class"]["paid"][
                    "admitted"] == 3
                assert fleet["autoscale"]["enabled"] is False
            finally:
                await client.close()
        asyncio.run(body())


    def test_dispatched_share_bypasses_worker_admission(self, tmp_path,
                                                        monkeypatch):
        """A share some master already orchestrated (hidden
        multi_job_id) is mandatory work for an ADMITTED job — the
        receiving worker must not re-shed it, even at an occupancy
        where fresh traffic of that class would 429."""
        monkeypatch.setenv(C.MAX_QUEUE_ENV, "4")
        from tests.test_pipeline import make_prompt

        def share(seed):
            p = make_prompt(seed)
            p["20"] = {"class_type": "DistributedCollector",
                       "inputs": {"images": ["1", 0]},
                       "hidden": {"multi_job_id": f"mj{seed}",
                                  "is_worker": True,
                                  "enabled_worker_ids": "[]"}}
            return p

        async def body():
            state = make_state(tmp_path, is_worker=True,
                               start_exec_thread=False)
            client = TestClient(TestServer(build_app(state)))
            await client.start_server()
            try:
                # occupy half the queue: fresh batch traffic sheds here
                for i in range(2):
                    r = await client.post("/prompt", json={
                        "prompt": make_prompt(i), "client_id": "c"})
                    assert r.status == 200
                r = await client.post("/prompt", json={
                    "prompt": make_prompt(7), "client_id": "c",
                    "priority": "batch"})
                assert r.status == 429
                # ...but the dispatched batch-class SHARE is admitted
                r = await client.post("/prompt", json={
                    "prompt": share(8), "client_id": "c",
                    "priority": "batch"})
                assert r.status == 200, await r.text()
                # the hard cap still applies to shares (queue now 3/4)
                r = await client.post("/prompt", json={
                    "prompt": share(9), "client_id": "c"})
                assert r.status == 200
                r = await client.post("/prompt", json={
                    "prompt": share(10), "client_id": "c"})
                assert r.status == 429
            finally:
                await client.close()
        asyncio.run(body())


# --- deadline-aware hedging --------------------------------------------------

class TestSloDeadlineHedging:
    def _job(self, ledger):
        ledger.create_job("j1", {0: "master", 1: "w0", 2: "w0"},
                          kind="tile")
        ledger.check_in("j1", 0, "master")   # EMA exists now

    def test_no_deadline_keeps_min_progress_gate(self):
        ledger = cluster_mod.WorkLedger()
        self._job(ledger)
        # 1/3 done < 50% gate -> no hedging regardless of silence
        assert ledger.overdue_units("j1", factor=0.0,
                                    min_wait_s=0.0) == {}

    def test_deadline_pressure_waives_gate_and_rekeys_threshold(self):
        ledger = cluster_mod.WorkLedger()
        self._job(ledger)
        # budget nearly blown: remaining ~0 -> threshold drops to the
        # SLO floor and the progress gate is waived
        ledger.set_deadline("j1", time.monotonic() + 0.01)
        time.sleep(C.SLO_MIN_WAIT_S + 0.05)
        overdue = ledger.overdue_units("j1", factor=1000.0,
                                       min_progress_pct=50.0,
                                       min_wait_s=1000.0)
        assert set(overdue) == {1, 2}
        assert all(o == "w0" for o in overdue.values())

    def test_comfortable_budget_does_not_loosen_policy(self):
        ledger = cluster_mod.WorkLedger()
        self._job(ledger)
        ledger.set_deadline("j1", time.monotonic() + 3600.0)
        # huge budget: the SLO threshold (0.25 x 3600) is LOOSER than
        # the global policy, so nothing changes
        assert ledger.overdue_units("j1", factor=1000.0,
                                    min_progress_pct=50.0,
                                    min_wait_s=1000.0) == {}

    def test_finish_job_clears_deadline(self):
        ledger = cluster_mod.WorkLedger()
        self._job(ledger)
        ledger.set_deadline("j1", time.monotonic() + 1.0)
        assert ledger.deadline("j1") is not None
        ledger.check_in("j1", 1, "w0")
        ledger.check_in("j1", 2, "w0")
        ledger.finish_job("j1")
        assert ledger.deadline("j1") is None

    def test_slo_rides_the_fanout_into_the_ledger(self, monkeypatch):
        """/prompt {"slo_s": N} -> orchestrate stamps every distributed
        job's deadline before dispatch (the plumbing half; the math is
        tested above)."""
        from comfyui_distributed_tpu.workflow import orchestrate

        worker = {"id": "w0", "host": "127.0.0.1", "port": 1,
                  "enabled": True}

        async def fake_preflight(workers, timeout=None, registry=None):
            return list(workers)

        async def fake_dispatch(w, graph, client_id=None,
                                extra_data=None):
            return {"prompt_id": "wp"}

        monkeypatch.setattr(orchestrate.dsp, "preflight_check",
                            fake_preflight)
        monkeypatch.setattr(orchestrate.dsp, "dispatch_to_worker",
                            fake_dispatch)
        monkeypatch.setattr(orchestrate.dsp, "make_job_id_map",
                            lambda graph, prefix=None: {"2": "job_slo"})

        class FakeJobs:
            async def prepare_job(self, mj):
                pass

            async def prepare_tile_job(self, mj):
                pass

        graph = {
            "1": {"class_type": "EmptyLatentImage",
                  "inputs": {"width": 8, "height": 8,
                             "batch_size": 1}},
            "2": {"class_type": "DistributedCollector",
                  "inputs": {"images": ["1", 0]}},
        }
        ledger = cluster_mod.WorkLedger()

        async def body():
            async def master_dispatch(g):
                return "pid"
            t0 = time.monotonic()
            out = await orchestrate.run_distributed(
                graph, "http://127.0.0.1:1", workers=[worker],
                master_dispatch=master_dispatch, job_store=FakeJobs(),
                extra_data={"slo_s": 30.0}, ledger=ledger)
            assert out["workers"] == ["w0"]
            dl = ledger.deadline("job_slo")
            assert dl is not None
            assert 25.0 < dl - t0 <= 30.5
        asyncio.run(body())


# --- rehome-heartbeat regression (satellite) ---------------------------------

class TestRehomeHeartbeat:
    def test_rehome_retries_through_a_racing_master(self, tmp_path):
        """The takeover race: the first rehomed beat fails (the dying
        master's socket), and the fix's retry burst re-registers on the
        next attempt — the worker must NOT stay unregistered for a full
        heartbeat interval.  The chaos freeze injector plays the dying
        master."""
        async def body():
            mstate = make_state(tmp_path, start_exec_thread=False)
            mclient = TestClient(TestServer(build_app(mstate)))
            await mclient.start_server()
            url = f"http://127.0.0.1:{mclient.server.port}"
            try:
                hb = cluster_mod.HeartbeatSender(
                    "http://127.0.0.1:1", "w-rehome", interval=999,
                    port=4242)
                # freeze = the beat that races the dying master fails
                chaos_mod.set_chaos(
                    {"freeze_heartbeats": ["w-rehome"]})
                unfreeze = threading.Timer(0.25, chaos_mod.set_chaos,
                                           args=(None,))
                unfreeze.start()
                loop = asyncio.get_running_loop()
                ok = await loop.run_in_executor(
                    None, lambda: hb.rehome(url, attempts=4))
                unfreeze.join()
                assert ok, "rehome retry burst never landed a beat"
                # the first landed beat re-registered IMMEDIATELY:
                # healthy in the new registry, no probe cycle needed
                assert mstate.cluster.state("w-rehome") \
                    == cluster_mod.HEALTHY
            finally:
                await mclient.close()
        asyncio.run(body())

    def test_rehome_route_registers_at_new_master(self, tmp_path):
        async def body():
            mstate = make_state(tmp_path / "m", start_exec_thread=False)
            mclient = TestClient(TestServer(build_app(mstate)))
            await mclient.start_server()
            wstate = make_state(tmp_path / "w", is_worker=True,
                                start_exec_thread=False)
            wclient = TestClient(TestServer(build_app(wstate)))
            await wclient.start_server()
            wstate.port = wclient.server.port
            url = f"http://127.0.0.1:{mclient.server.port}"
            try:
                r = await wclient.post("/distributed/rehome", json={
                    "master_url": url, "worker_id": "w-route"})
                assert r.status == 200
                body_json = await r.json()
                assert body_json["registered"] is True
                assert mstate.cluster.state("w-route") \
                    == cluster_mod.HEALTHY
            finally:
                if wstate.heartbeat is not None:
                    wstate.heartbeat.stop()
                await wclient.close()
                await mclient.close()
        asyncio.run(body())


# --- slow loopback acceptance ------------------------------------------------

def _p95(values):
    """Nearest-rank 95th percentile over a small latency sample."""
    if not values:
        return None
    xs = sorted(values)
    return xs[min(int(0.95 * (len(xs) - 1) + 0.5), len(xs) - 1)]


def run_overload(tmp_path, monkeypatch, duration_s, rates, seed=7,
                 wait_s=300.0):
    """One loopback topology — master + 2 config workers, all real
    aiohttp servers — under three Poisson tenant streams:

    * **overload** (chaos ON): the classes submit plain tiny prompts
      whose combined rate exceeds the master's (coalescing off — the
      mixed-traffic worst case) service rate, while chaos drops, delays
      and 5xx's the data-plane + heartbeat edges.  Admission sheds batch
      first; weighted fair dequeue orders the queue waits;
    * **churn**: the paid stream also carries tiled-upscale fan-out
      jobs; worker w1 is KILLED after the first one completes — the
      later jobs must recover through the ledger (reassign / redispatch)
      with the chaos still armed;
    * **convergence**: an armed FleetAutoscaler (injected spawner
      building REAL in-process loopback workers that register and
      heartbeat) must scale up under the backlog and scale back down
      after the drain, with zero direction-reversal flaps.

    Returns the counts the acceptance asserts on."""
    import json
    import random

    from comfyui_distributed_tpu.utils import trace as tr
    from tests.test_cluster import upscale_prompt
    from tests.test_pipeline import make_prompt

    # repeated seeded fan-out jobs in one process: result/tile cache
    # hits would settle later paid jobs without dispatching — this
    # harness exercises admission + recovery under load, pin reuse off
    monkeypatch.setenv(C.CACHE_ENV, "0")
    monkeypatch.setenv(C.FAULT_POLICY_ENV, "reassign")
    monkeypatch.setenv(C.HEDGE_ENV, "1")
    # single-process CPU proxy: jax compute starves the shared loop, so
    # leases must be generous enough that LIVE workers don't flap dead
    monkeypatch.setenv(C.LEASE_ENV, "4.0")
    monkeypatch.setenv(C.SUSPECT_PROBES_ENV, "3")
    # queue geometry for the shed ladder: batch sheds at 30% of 64,
    # free at 65%, paid only at a full queue the drain never lets
    # happen — "zero dropped paid" is enforced by the threshold gap
    monkeypatch.setenv(C.MAX_QUEUE_ENV, "64")
    monkeypatch.setenv(C.TENANT_SHED_ENV, "paid=1.0,free=0.65,batch=0.3")

    async def go():
        rng = random.Random(seed)
        workers, cfg_workers, heartbeats = [], [], []

        async def make_worker(wid):
            wdir = tmp_path / wid
            os.makedirs(wdir / "in", exist_ok=True)
            st = make_state(wdir, is_worker=True)
            client = TestClient(TestServer(build_app(st)))
            await client.start_server()
            return st, client

        for i in range(2):
            st, client = await make_worker(f"w{i}")
            workers.append((st, client))
            cfg_workers.append({"id": f"w{i}", "host": "127.0.0.1",
                                "port": client.server.port,
                                "enabled": True})
        mdir = tmp_path / "master"
        os.makedirs(mdir / "in")
        with open(mdir / "cfg.json", "w") as f:
            json.dump({"workers": cfg_workers,
                       "master": {"host": "127.0.0.1"}, "settings": {}},
                      f)

        # the overload master: coalescing OFF (mixed production traffic
        # degenerates to batch=1 — the worst case the fleet must absorb)
        mstate = make_state(mdir, is_worker=False, overlap=True,
                            coalesce=False)
        mclient = TestClient(TestServer(build_app(mstate)))
        await mclient.start_server()
        mstate.port = mclient.server.port
        master_url = f"http://127.0.0.1:{mstate.port}"
        mstate.health.interval = 0.5
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, mstate.health.poll_once)
        mstate.health.start()

        # config workers heartbeat their leases like spawned ones would
        for w in cfg_workers:
            hb = cluster_mod.HeartbeatSender(master_url, w["id"],
                                             interval=1.0,
                                             port=w["port"])
            hb.start()
            heartbeats.append(hb)

        # the autoscaler, spawning REAL loopback workers (register +
        # heartbeat) and retiring them by drain
        spawned: dict = {}

        async def spawn_async():
            wid = f"auto{len(spawned)}"
            st, client = await make_worker(wid)
            hb = cluster_mod.HeartbeatSender(master_url, wid,
                                             interval=1.0,
                                             port=client.server.port)
            hb.start()
            heartbeats.append(hb)
            spawned[wid] = (st, client, hb)
            mstate.cluster.register(wid, info={
                "host": "127.0.0.1", "port": client.server.port,
                "name": wid})
            return wid

        def spawner():
            return asyncio.run_coroutine_threadsafe(
                spawn_async(), loop).result(timeout=30)

        def retirer(wid):
            entry = spawned.get(wid)
            if entry is None:
                return False
            st, client, hb = entry
            hb.stop()
            asyncio.run_coroutine_threadsafe(
                client.close(), loop).result(timeout=10)
            st.drain(2)
            return True

        def worker_queue(wid):
            entry = spawned.get(wid)
            if entry is not None:
                return entry[0].queue_remaining()
            return None   # config workers: registry hint covers them

        scaler = autoscale_mod.FleetAutoscaler(
            registry=mstate.cluster,
            queue_depth_fn=mstate.queue_remaining,
            util_fn=None,
            spawner=spawner, retirer=retirer,
            worker_queue_fn=worker_queue,
            min_workers=2, max_workers=4,
            up_queue=2.0, down_queue=0.5,
            up_util=0.95, down_util=0.99,
            window=2, cooldown_s=3.0, interval_s=0.25, drain_s=10.0)
        mstate.autoscaler = scaler

        async def post(tenant, prompt, **extra):
            r = await mclient.post("/prompt", json={
                "prompt": prompt, "client_id": f"{tenant}-client",
                "priority": tenant, **extra})
            return r.status, await r.json()

        def post_plain(tenant, seq):
            return post(tenant, make_prompt(1000 + seq, steps=2,
                                            text="a lighthouse"))

        def post_fanout(tenant, seed_):
            # 96px -> 9 tiles of 32px over master + 2 workers
            return post(tenant, upscale_prompt(seed=seed_, size=96),
                        slo_s=60.0)

        async def wait_history(pids, bound_s):
            deadline = time.monotonic() + bound_s
            while time.monotonic() < deadline:
                hist = await (await mclient.get("/history")).json()
                if all(p in hist for p in pids):
                    return hist
                await asyncio.sleep(0.05)
            return await (await mclient.get("/history")).json()

        try:
            # warm every participant's compiled programs with chaos OFF:
            # one plain prompt and one fan-out job
            for status, body in (await post_plain("paid", 0),
                                 await post_fanout("paid", 5)):
                assert status == 200, body
                await wait_history([body["prompt_id"]], wait_s)

            # arm chaos for everything that follows: the data-plane +
            # heartbeat edges flake at ~5%, uploads corrupt at 2% — the
            # retry/idempotency machinery must absorb it all
            chaos_mod.set_chaos({
                "drop_pct": 5, "delay_pct": 5, "delay_s": 0.05,
                "http_5xx_pct": 5, "corrupt_pct": 2, "seed": seed,
                "routes": ["/distributed/tile_complete",
                           "/distributed/job_complete",
                           "/distributed/heartbeat"]})
            chaos_before = {
                k: v for k, v in tr.GLOBAL_COUNTERS.snapshot().items()
                if k.startswith("chaos_")}
            scaler.start()

            # the Poisson overload window with chaos armed.  Independent
            # exponential inter-arrival streams per class; the paid
            # stream additionally carries the fan-out jobs whose worker
            # gets killed mid-window.
            submissions = {cls: [] for cls in rates}   # (pid, t_submit)
            sheds = {cls: 0 for cls in rates}
            fanout_pids = []
            killed = {"done": False}

            async def tenant_stream(cls, rate):
                t_end = time.monotonic() + duration_s
                seq = 0
                while time.monotonic() < t_end:
                    await asyncio.sleep(rng.expovariate(rate))
                    t_sub = time.time()
                    status, body = await post_plain(cls, seq)
                    seq += 1
                    if status == 200:
                        submissions[cls].append(
                            (body["prompt_id"], t_sub))
                    elif status == 429:
                        sheds[cls] += 1
                    else:
                        raise AssertionError(
                            f"{cls} submit -> {status}: {body}")

            async def churn():
                # fan-out job 1 completes pre-kill; then w1 dies; jobs
                # 2 and 3 must complete through ledger recovery
                status, body = await post_fanout("paid", 101)
                assert status == 200, body
                fanout_pids.append(body["prompt_id"])
                await wait_history([body["prompt_id"]], wait_s)
                await asyncio.sleep(duration_s * 0.25)
                await workers[1][1].close()
                killed["done"] = True
                for s in (102, 103):
                    status, body = await post_fanout("paid", s)
                    assert status == 200, body
                    fanout_pids.append(body["prompt_id"])

            await asyncio.gather(
                churn(), *(tenant_stream(cls, r)
                           for cls, r in rates.items()))
            admitted_pids = [p for cls in submissions
                             for p, _ in submissions[cls]] + fanout_pids
            hist = await wait_history(admitted_pids, wait_s)

            # convergence: the drained fleet must scale back down
            # (retire the autoscaled workers) without flapping
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                snap = scaler.snapshot()
                if snap["scale_downs"] >= 1 and not snap["retiring"] \
                        and not snap["spawned"]:
                    break
                await asyncio.sleep(0.25)
            scaler.stop()
            chaos_mod.set_chaos(None)
            mstate.health.stop()

            def succeeded(pid):
                return (hist.get(pid) or {}).get("status") == "success"

            per_class = {}
            for cls in rates:
                lats = [hist[pid]["finished_at"] - t_sub
                        for pid, t_sub in submissions[cls]
                        if succeeded(pid)]
                per_class[cls] = {"admitted": len(submissions[cls]),
                                  "shed": sheds[cls],
                                  "completed": len(lats),
                                  "p95_s": _p95(lats)}
            chaos_injected = {
                k.split("chaos_", 1)[1]: v - chaos_before.get(k, 0)
                for k, v in tr.GLOBAL_COUNTERS.snapshot().items()
                if k.startswith("chaos_")}
            return {
                "per_class": per_class,
                "fanout_jobs": len(fanout_pids),
                "fanout_completed": sum(map(succeeded, fanout_pids)),
                "worker_killed": killed["done"],
                "autoscale": scaler.snapshot(),
                "chaos_injected": chaos_injected,
            }
        finally:
            chaos_mod.set_chaos(None)
            scaler.stop()
            mstate.health.stop()
            for hb in heartbeats:
                hb.stop()
            await mclient.close()
            for _st, client in list(workers) \
                    + [(s, c) for s, c, _h in spawned.values()]:
                try:
                    await client.close()
                except Exception:  # noqa: BLE001 - already closed
                    pass
            mstate.drain(5)
            for st, _ in workers:
                st.drain(5)
            for st, _c, _h in spawned.values():
                st.drain(2)

    return asyncio.run(go())


@pytest.mark.slow
class TestOverloadAcceptance:
    def test_three_tenants_killed_worker_chaos(self, tmp_path,
                                               monkeypatch):
        """ISSUE 9 acceptance, scaled down: 3 Poisson tenants + 1
        killed worker + injected 5xx/drops/delays -> every admitted job
        (paid ESPECIALLY) completes, shedding is batch-first with paid
        untouched, the p95 ordering holds, and the autoscaler scales
        up AND down without a flap."""
        m = run_overload(tmp_path, monkeypatch, duration_s=6.0,
                         rates={"paid": 2.0, "free": 2.5, "batch": 3.0})
        paid, free, batch = (m["per_class"][c]
                             for c in ("paid", "free", "batch"))
        assert m["worker_killed"]
        assert paid["shed"] == 0
        assert m["fanout_completed"] == m["fanout_jobs"]
        for cls in (paid, free, batch):       # every admitted job done
            assert cls["completed"] == cls["admitted"], m["per_class"]
        assert batch["shed"] >= 1
        assert batch["shed"] >= free["shed"]
        assert paid["p95_s"] is not None and batch["p95_s"] is not None
        assert paid["p95_s"] < batch["p95_s"]
        scale = m["autoscale"]
        assert scale["scale_ups"] >= 1 and scale["scale_downs"] >= 1
        assert scale["flaps"] == 0
        assert sum(m["chaos_injected"].values()) >= 1
