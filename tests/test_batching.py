"""Iteration-level continuous batching (ISSUE 12): step-granular denoise
executor with persistent shape-bucketed batches — non-contiguous
same-signature merging, per-slot (seed, fold-idx) bit-exactness vs the
serial run, tenant stride fairness through the CB pop, slot-exit-order
PNG/history provenance, and the metrics surfaces."""

import asyncio
import json
import os
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.models import samplers as smp
from comfyui_distributed_tpu.ops.base import OpContext
from comfyui_distributed_tpu.server.app import ServerState, build_app
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.workflow import batch_executor as cb_mod
from comfyui_distributed_tpu.workflow import scheduler as sched
from comfyui_distributed_tpu.workflow.executor import WorkflowExecutor


@pytest.fixture(autouse=True)
def tiny_family(monkeypatch):
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny")
    yield


def make_prompt(seed, steps=2, size=32, text="cat", batch=1,
                sampler="euler", save=False):
    p = {
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
                         "sampler_name": sampler, "scheduler": "normal",
                         "denoise": 1.0}},
        "1": {"class_type": "VAEDecode",
              "inputs": {"samples": ["8", 0], "vae": ["7", 2]}},
        "3": {"class_type": "PreviewImage", "inputs": {"images": ["1", 0]}},
    }
    if save:
        p["3"] = {"class_type": "SaveImage",
                  "inputs": {"images": ["1", 0],
                             "filename_prefix": f"cb_{seed}"}}
    return p


def make_state(tmp_path, **kw):
    kw.setdefault("cb", True)
    return ServerState(config_path=str(tmp_path / "cfg.json"),
                       input_dir=str(tmp_path / "in"),
                       output_dir=str(tmp_path / "out"), **kw)


def wait_history(state, pids, timeout=180):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(p in state._history for p in pids):
            return {p: state._history[p] for p in pids}
        time.sleep(0.01)
    raise AssertionError(f"prompts never finished: "
                         f"{[p for p in pids if p not in state._history]}")


def item(seed, cls="paid", steps=2, sampler="euler", cb=True):
    p = make_prompt(seed, steps=steps, sampler=sampler)
    return {"id": f"i{seed}", "prompt": p,
            "sig": sched.coalesce_signature(p),
            "cb": cb and cb_mod.quick_eligible(p),
            "tenant": cls, "t_enq": time.perf_counter()}


class TestEligibility:
    def test_safe_sampler_registry_matches_extracted_steps(self):
        """The declared product surface (constants.CB_SAFE_SAMPLERS) and
        the actual extracted step callables must never drift."""
        assert frozenset(C.CB_SAFE_SAMPLERS) \
            == frozenset(smp.SAMPLER_STEPS)

    def test_quick_eligible_plain_txt2img(self):
        assert cb_mod.quick_eligible(make_prompt(1))
        assert cb_mod.quick_eligible(make_prompt(1,
                                                 sampler="euler_ancestral"))

    def test_quick_rejects_non_step_sampler(self):
        assert not cb_mod.quick_eligible(make_prompt(1, sampler="heun"))

    def test_quick_rejects_multi_sampler_graphs(self):
        p = make_prompt(1)
        p["80"] = dict(p["8"])
        assert not cb_mod.quick_eligible(p)

    def test_quick_rejects_dispatched_shares(self):
        p = make_prompt(1)
        p["99"] = {"class_type": "DistributedCollector",
                   "inputs": {"images": ["1", 0],
                              "multi_job_id": "job"}}
        assert not cb_mod.quick_eligible(p)

    def test_quick_rejects_degenerate_steps(self):
        p = make_prompt(1)
        p["8"]["inputs"]["steps"] = 0
        assert not cb_mod.quick_eligible(p)


class TestCbPop:
    def test_non_contiguous_same_signature_merge(self):
        """A/B/A queue: the CB pop takes BOTH A prompts past the B in
        the middle — the head-run-only limitation is gone; B keeps its
        position for the next boundary."""
        adm = sched.AdmissionController()
        a1, b, a2 = item(1, steps=3), item(2, steps=1), item(3, steps=3)
        assert a1["sig"] == a2["sig"] != b["sig"]
        queue = [a1, b, a2]
        kind, items = sched.pop_cb_admit(queue, adm, lambda it: 4)
        assert kind == "cb"
        assert [it["id"] for it in items] == ["i1", "i3"]
        assert [it["id"] for it in queue] == ["i2"]

    def test_room_caps_the_sweep(self):
        adm = sched.AdmissionController()
        queue = [item(i) for i in range(5)]
        kind, items = sched.pop_cb_admit(queue, adm, lambda it: 2)
        assert kind == "cb" and len(items) == 2 and len(queue) == 3

    def test_ineligible_head_pops_legacy_group(self):
        adm = sched.AdmissionController()
        queue = [item(1, cb=False), item(2, cb=False), item(3, cb=False)]
        kind, group = sched.pop_cb_admit(queue, adm, lambda it: 0,
                                         legacy_max=8)
        assert kind == "fallback"
        # contiguous same-signature run merged, exactly like
        # pop_fair_group would
        assert [it["id"] for it in group] == ["i1", "i2", "i3"]

    def test_batchable_but_full_defers(self):
        """An eligible prompt whose bucket is full must WAIT for a slot
        exit (defer), never burn the mesh through the fallback path."""
        adm = sched.AdmissionController()
        queue = [item(1)]
        kind, items = sched.pop_cb_admit(queue, adm, lambda it: -1)
        assert kind == "defer" and not items and len(queue) == 1

    def test_fallback_busy_defers(self):
        adm = sched.AdmissionController()
        queue = [item(1, cb=False)]
        kind, items = sched.pop_cb_admit(queue, adm, lambda it: 0,
                                         fallback_ok=False)
        assert kind == "defer" and len(queue) == 1

    def test_tenant_stride_ratios_survive_cb_pop(self):
        """paid/free/batch dequeue ratios through pop_cb_admit match the
        6/3/1 stride weights — fairness survives the new dispatch
        model (the pop shares next_class with pop_fair_group)."""
        adm = sched.AdmissionController(
            weights={"paid": 6.0, "free": 3.0, "batch": 1.0},
            rate={}, burst={}, shed={})
        queue = []
        for i in range(40):
            for cls in ("paid", "free", "batch"):
                queue.append(item(1000 + i * 3, cls=cls))
        order = []
        for _ in range(60):
            kind, items = sched.pop_cb_admit(queue, adm,
                                             lambda it: 1)
            assert kind == "cb" and len(items) == 1
            order.append(items[0]["tenant"])
        counts = {cls: order.count(cls) for cls in
                  ("paid", "free", "batch")}
        assert counts["paid"] == 36 and counts["free"] == 18 \
            and counts["batch"] == 6


class TestBucketExactness:
    def test_late_join_bit_identical_to_serial(self):
        """THE exactness guarantee: a prompt that joins a RUNNING batch
        mid-flight produces a latent bit-identical to its own serial
        run — per-slot (seed, fold-idx) keys + the shared extracted
        step callable, for both a deterministic and an ancestral
        (per-step noise) sampler."""
        for sampler in ("euler", "euler_ancestral"):
            p1 = make_prompt(11, steps=3, sampler=sampler)
            p2 = make_prompt(22, steps=3, sampler=sampler)
            sig = sched.coalesce_signature(p1)
            serial = {}
            for s, p in ((11, p1), (22, p2)):
                res = WorkflowExecutor(OpContext()).execute(p)
                serial[s] = np.asarray(res.outputs["8"][0]["samples"]
                                       .data)
            i1 = {"id": "a", "prompt": p1, "sig": sig, "cb": True}
            i2 = {"id": "b", "prompt": p2, "sig": sig, "cb": True}
            bkt = cb_mod._Bucket(sig, i1, OpContext(), max_slots=4)
            bkt.admit(i1)
            bkt.step_once()          # a is mid-flight...
            bkt.admit(i2)            # ...when b joins at the boundary
            done = {}
            for _ in range(10):
                bkt.step_once()
                for its, rows, _t in bkt.take_finished():
                    arr = np.asarray(rows)
                    for j, it in enumerate(its):
                        done[it["id"]] = arr[j * bkt.b:(j + 1) * bkt.b]
                if len(done) == 2:
                    break
            assert (done["a"] == serial[11]).all(), sampler
            assert (done["b"] == serial[22]).all(), sampler

    def test_pad_grows_and_shrinks_along_the_set(self):
        p = make_prompt(1, steps=4)
        sig = sched.coalesce_signature(p)
        it0 = {"id": "x0", "prompt": p, "sig": sig, "cb": True}
        bkt = cb_mod._Bucket(sig, it0, OpContext(), max_slots=4)
        assert bkt.pads == [1, 2, 4]
        bkt.admit(it0)
        assert bkt.pad == 1
        for i in range(2):
            pi = make_prompt(10 + i, steps=4)
            bkt.admit({"id": f"x{i + 1}", "prompt": pi, "sig": sig,
                       "cb": True})
        assert bkt.pad == 4 and bkt.n_active == 3
        while bkt.n_active:
            bkt.step_once()
            bkt.take_finished()
        # all slots exited together -> pad falls back to the smallest
        assert bkt.pad == 1 and bkt.retires == 3

    def test_zero_steady_state_retraces_across_occupancy_churn(
            self, assert_nothing_compiled):
        """After one warm pass over a pad size, steps at that size and
        admit/retire churn within it must not retrace — the per-bucket
        jitted step + slot plumbing all come from caches keyed on the
        declared shape set."""
        from comfyui_distributed_tpu.utils import trace as trace_mod
        p = make_prompt(5, steps=2)
        sig = sched.coalesce_signature(p)
        it0 = {"id": "w", "prompt": p, "sig": sig, "cb": True}
        bkt = cb_mod._Bucket(sig, it0, OpContext(), max_slots=2)
        # warm: one full admit->step->retire cycle at EACH pad size —
        # steady state is defined over the declared shape set, so every
        # pad must have compiled once (exactly what a serving warmup
        # does)
        bkt.admit(it0)
        while bkt.n_active:
            bkt.step_once()
            bkt.take_finished()
        bkt.admit({"id": "w1", "prompt": make_prompt(4, steps=2),
                   "sig": sig, "cb": True})
        bkt.admit({"id": "w2", "prompt": make_prompt(6, steps=2),
                   "sig": sig, "cb": True})
        while bkt.n_active:
            bkt.step_once()
            bkt.take_finished()
        mark = trace_mod.GLOBAL_RETRACES.mark()
        for i in range(3):
            bkt.admit({"id": f"s{i}", "prompt":
                       make_prompt(100 + i, steps=2), "sig": sig,
                       "cb": True})
            bkt.step_once()
            bkt.take_finished()
        while bkt.n_active:
            bkt.step_once()
            bkt.take_finished()
        assert_nothing_compiled(trace_mod.GLOBAL_RETRACES.since(mark))


class TestBucketTensorParallel:
    """ISSUE 16 composition: with a 2-D data×tensor mesh live, the
    bucket's persistent padded batch is 2-D-sharded (rows over ``data``,
    UNet internals over ``tensor``) and every ISSUE-12 invariant must
    survive: per-image bit-exactness vs the serial run, the canonical
    per-pad buffer layout, and zero steady-state retraces."""

    @pytest.fixture()
    def tp_mesh(self, monkeypatch):
        import jax
        from comfyui_distributed_tpu.parallel import mesh as mesh_mod
        monkeypatch.setenv("DTPU_TP_MIN_SHARD_ELEMENTS", "2")
        registry.clear_pipeline_cache()
        mesh = mesh_mod.build_mesh(
            axes={C.DATA_AXIS: 2, C.TENSOR_AXIS: 2, C.SEQ_AXIS: 1},
            devices=jax.devices()[:4])
        mesh_mod.set_runtime(mesh_mod.MeshRuntime(mesh=mesh))
        yield mesh
        mesh_mod.set_runtime(None)
        registry.clear_pipeline_cache()

    def _drain(self, bkt, done, rounds=12):
        for _ in range(rounds):
            bkt.step_once()
            for its, rows, _t in bkt.take_finished():
                arr = np.asarray(rows)
                for j, it in enumerate(its):
                    done[it["id"]] = arr[j * bkt.b:(j + 1) * bkt.b]
            if not bkt.n_active:
                return done
        raise AssertionError("bucket never drained")

    def test_late_join_bit_identical_to_solo_under_tp(self, tp_mesh,
                                                      monkeypatch):
        """CB per-image bit-exactness on the 2-D-sharded bucket: a slot's
        math depends only on its own (seed, fold-idx) and schedule
        position, never on co-tenants — a row that late-joins a running
        batch is BIT-identical to the same prompt run solo through the
        same sharded step kernel.  The pad set is pinned to one size
        because XLA CPU's SPMD matmuls are not row-wise bit-stable
        ACROSS batch sizes (a B=2 and a B=4 lowering round differently
        at ~1e-6) — within one padded shape, rows are bit-independent;
        vs the full-loop serial graph the match is tolerance-tight, not
        bitwise (asserted separately)."""
        monkeypatch.setenv(C.CB_PAD_BUCKETS_ENV, "2")
        p1 = make_prompt(11, steps=3, sampler="euler_ancestral")
        p2 = make_prompt(22, steps=3, sampler="euler_ancestral")
        sig = sched.coalesce_signature(p1)
        serial = {}
        for s, p in ((11, p1), (22, p2)):
            res = WorkflowExecutor(OpContext()).execute(p)
            serial[s] = np.asarray(res.outputs["8"][0]["samples"].data)
        pipe = registry.load_pipeline("tiny.safetensors")
        assert pipe._tp_mesh is tp_mesh     # serving layout engaged
        # solo reference: each prompt alone in its own bucket (padded
        # to the same rows=2 shape the shared run uses)
        solo = {}
        for pid, p in (("a", p1), ("b", p2)):
            it = {"id": pid, "prompt": p, "sig": sig, "cb": True}
            bkt = cb_mod._Bucket(sig, it, OpContext(), max_slots=2)
            assert bkt.pads == [2] and bkt._tp_mesh is tp_mesh
            bkt.admit(it)
            self._drain(bkt, solo)
        # shared run: a is mid-flight when b joins at a step boundary
        i1 = {"id": "a2", "prompt": p1, "sig": sig, "cb": True}
        i2 = {"id": "b2", "prompt": p2, "sig": sig, "cb": True}
        bkt = cb_mod._Bucket(sig, i1, OpContext(), max_slots=2)
        bkt.admit(i1)
        bkt.step_once()
        bkt.admit(i2)
        done = {}
        self._drain(bkt, done)
        assert (done["a2"] == solo["a"]).all()
        assert (done["b2"] == solo["b"]).all()
        # and the sharded CB rows track the (differently-lowered)
        # serial full-loop graph tightly
        assert np.allclose(done["a2"], serial[11], atol=5e-4)
        assert np.allclose(done["b2"], serial[22], atol=5e-4)

    def test_bucket_buffers_carry_canonical_rows_layout(self, tp_mesh):
        """Every rows-leading persistent buffer sits on ONE layout per
        pad: rows over ``data`` when divisible, replicated otherwise —
        the invariant that keeps the donated step executable from
        re-lowering (parallel/sharding.put_rows)."""
        from comfyui_distributed_tpu.parallel import sharding as shd
        p = make_prompt(7, steps=2)
        sig = sched.coalesce_signature(p)
        it0 = {"id": "r0", "prompt": p, "sig": sig, "cb": True}
        bkt = cb_mod._Bucket(sig, it0, OpContext(), max_slots=4)
        bkt.admit(it0)
        # pad 1: one row cannot split over data=2 -> replicated
        assert bkt.pad == 1
        assert shd.spec_of(bkt.x) == shd.mesh_spec()
        assert shd.spec_of(bkt.keys) == shd.mesh_spec()
        bkt.admit({"id": "r1", "prompt": make_prompt(8, steps=2),
                   "sig": sig, "cb": True})
        # pad 2: rows ride the data axis
        assert bkt.pad == 2
        assert shd.spec_of(bkt.x) == shd.batch_axis_spec(bkt.x.ndim)
        assert shd.spec_of(bkt.keys) == \
            shd.batch_axis_spec(bkt.keys.ndim)
        bkt.step_once()
        # the donated step hands back the SAME canonical layout
        assert bkt.x.sharding.is_equivalent_to(
            shd.named(tp_mesh, shd.batch_axis_spec(bkt.x.ndim)),
            bkt.x.ndim)

    def test_zero_steady_state_retraces_under_tp(self, tp_mesh,
                                                 assert_nothing_compiled):
        """Warm pads stay warm on the 2-D mesh: admit/retire churn after
        one pass over each pad size must not retrace — the sharded
        buffers are re-pinned to the canonical layout after every
        write/repad, so each executable only ever sees one input
        sharding."""
        from comfyui_distributed_tpu.utils import trace as trace_mod
        p = make_prompt(5, steps=2)
        sig = sched.coalesce_signature(p)
        it0 = {"id": "w", "prompt": p, "sig": sig, "cb": True}
        bkt = cb_mod._Bucket(sig, it0, OpContext(), max_slots=2)
        bkt.admit(it0)
        while bkt.n_active:
            bkt.step_once()
            bkt.take_finished()
        bkt.admit({"id": "w1", "prompt": make_prompt(4, steps=2),
                   "sig": sig, "cb": True})
        bkt.admit({"id": "w2", "prompt": make_prompt(6, steps=2),
                   "sig": sig, "cb": True})
        while bkt.n_active:
            bkt.step_once()
            bkt.take_finished()
        mark = trace_mod.GLOBAL_RETRACES.mark()
        for i in range(3):
            bkt.admit({"id": f"s{i}", "prompt":
                       make_prompt(100 + i, steps=2), "sig": sig,
                       "cb": True})
            bkt.step_once()
            bkt.take_finished()
        while bkt.n_active:
            bkt.step_once()
            bkt.take_finished()
        assert_nothing_compiled(trace_mod.GLOBAL_RETRACES.since(mark))


class TestServerContinuousBatching:
    def test_interleaved_signatures_all_complete_and_merge(self,
                                                           tmp_path):
        """A/B/A interleaved queue through a real CB ServerState: all
        succeed, the two A prompts share ONE bucket (non-contiguous
        merge), and the 1-step B exits without waiting for the 3-step
        A batch to drain (slot-exit order != queue order)."""
        st = make_state(tmp_path)
        st._exec_gate.clear()
        pids = [st.enqueue_prompt(make_prompt(1, steps=3), "c"),
                st.enqueue_prompt(make_prompt(2, steps=1), "c"),
                st.enqueue_prompt(make_prompt(3, steps=3), "c")]
        st._exec_gate.set()
        hist = wait_history(st, pids)
        assert all(h["status"] == "success" for h in hist.values())
        snap = st.cb.snapshot()
        assert snap["admits"] == 3 and snap["retires"] == 3
        assert snap["fallbacks"] == 0
        by_admits = sorted(b["admits"] for b in snap["buckets"])
        assert by_admits == [1, 2]
        assert st.drain(20) is True

    def test_slot_exit_order_keeps_png_and_history_provenance(
            self, tmp_path):
        """Satellite: images may now finish out of queue order — each
        saved PNG must still embed ITS OWN prompt's seed and land in
        its own history entry."""
        from PIL import Image
        st = make_state(tmp_path)
        st._exec_gate.clear()
        # enqueue the slow prompt FIRST so the fast one overtakes it
        pids = [st.enqueue_prompt(make_prompt(71, steps=4, save=True),
                                  "c"),
                st.enqueue_prompt(make_prompt(72, steps=1, save=True),
                                  "c")]
        st._exec_gate.set()
        hist = wait_history(st, pids)
        assert all(h["status"] == "success" for h in hist.values())
        assert all(h["images"] == 1 for h in hist.values())
        out = tmp_path / "out"
        embedded = {}
        for n in os.listdir(out):
            meta = json.loads(Image.open(out / n).info["prompt"])
            embedded[meta["8"]["inputs"]["seed"]] = n
        assert set(embedded) == {71, 72}
        assert st.drain(20) is True

    def test_ineligible_prompts_ride_the_fallback(self, tmp_path):
        st = make_state(tmp_path)
        st._exec_gate.clear()
        pids = [st.enqueue_prompt(make_prompt(7, steps=1), "c"),
                st.enqueue_prompt(make_prompt(8, steps=1,
                                              sampler="heun"), "c")]
        st._exec_gate.set()
        hist = wait_history(st, pids)
        assert all(h["status"] == "success" for h in hist.values())
        assert st.cb.snapshot()["fallbacks"] >= 1
        assert st.drain(20) is True

    def test_metrics_surfaces_expose_batching(self, tmp_path):
        async def body():
            st = make_state(tmp_path)
            client = TestClient(TestServer(build_app(st)))
            await client.start_server()
            try:
                m = await (await client.get(
                    "/distributed/metrics")).json()
                b = m["batching"]
                assert b["enabled"] is True
                assert {"max_slots", "pad_buckets", "slots_active",
                        "slots_free", "admits", "retires", "steps",
                        "fallbacks", "buckets"} <= set(b)
                text = await (await client.get(
                    "/distributed/metrics.prom")).text()
                assert 'dtpu_batch_slots{state="active"}' in text
                assert 'dtpu_batch_slots{state="free"}' in text
                assert "dtpu_cb_admits_total" in text
                assert "dtpu_cb_retires_total" in text
                assert "dtpu_cb_steps_total" in text
            finally:
                await client.close()
                st.drain(5)
        asyncio.run(body())

    def test_cb_off_keeps_legacy_dispatch(self, tmp_path):
        """DTPU_CB unset: no executor is constructed and the classic
        exec loop serves the queue — the default path is untouched."""
        st = make_state(tmp_path, cb=False)
        assert st.cb is None
        pid = st.enqueue_prompt(make_prompt(9, steps=1), "c")
        hist = wait_history(st, [pid])
        assert hist[pid]["status"] == "success"
        assert st.drain(20) is True


class TestParkedStore:
    """runtime.jobs.ParkedStore: the host-side beyond-HBM working set
    (ISSUE 17) — capacity backstop, double-park guard, and the
    residency scheduler's resume ordering."""

    @staticmethod
    def rec(pid, sig="A", rank=0, t_park=0.0):
        class R:
            pass
        r = R()
        r.pid, r.sig, r.rank, r.t_park = pid, sig, rank, t_park
        return r

    def test_overflow_raises_and_room_tracks(self):
        from comfyui_distributed_tpu.runtime.jobs import ParkedStore
        st = ParkedStore(2)
        st.park([self.rec("a"), self.rec("b")])
        assert st.room() == 0 and st.count() == 2
        with pytest.raises(ValueError, match="overflow"):
            st.park([self.rec("c")])

    def test_double_park_of_same_prompt_rejected(self):
        from comfyui_distributed_tpu.runtime.jobs import ParkedStore
        st = ParkedStore(4)
        st.park([self.rec("a")])
        with pytest.raises(ValueError, match="double-park"):
            st.park([self.rec("a")])
        # the failed batch must not partially register
        assert st.count() == 1 and st.has("a")

    def test_pop_for_orders_rank_desc_then_fifo(self):
        from comfyui_distributed_tpu.runtime.jobs import ParkedStore
        st = ParkedStore(8)
        st.park([self.rec("b1", rank=0, t_park=1.0),
                 self.rec("f1", rank=1, t_park=3.0),
                 self.rec("b2", rank=0, t_park=2.0),
                 self.rec("f2", rank=1, t_park=4.0)])
        got = st.pop_for("A", 3)
        assert [r.pid for r in got] == ["f1", "f2", "b1"]
        assert st.count() == 1 and st.has("b2")

    def test_pop_for_filters_by_signature(self):
        from comfyui_distributed_tpu.runtime.jobs import ParkedStore
        st = ParkedStore(8)
        st.park([self.rec("a", sig="A"), self.rec("b", sig="B")])
        assert [r.pid for r in st.pop_for("B", 8)] == ["b"]
        assert st.sigs() == ["A"]

    def test_pop_abandoned_frees_only_gone_clients(self):
        from comfyui_distributed_tpu.runtime.jobs import ParkedStore
        st = ParkedStore(8)
        st.park([self.rec("keep"), self.rec("gone")])
        out = st.pop_abandoned(lambda pid: pid == "gone")
        assert [r.pid for r in out] == ["gone"]
        assert st.count() == 1 and not st.has("gone")

    def test_zero_capacity_store_is_inert(self):
        """DTPU_CB_PARK unset -> ParkedStore(0): every park path is
        structurally unreachable (room 0)."""
        from comfyui_distributed_tpu.runtime.jobs import ParkedStore
        st = ParkedStore(0)
        assert st.room() == 0
        with pytest.raises(ValueError, match="overflow"):
            st.park([self.rec("a")])


class TestLatentPagingExactness:
    """Bucket-level park/resume (ISSUE 17 tentpole): a parked row's
    remaining steps are bit-identical to its never-parked serial run —
    the host round trip + recomputed keys change nothing."""

    def _serial(self, p):
        res = WorkflowExecutor(OpContext()).execute(p)
        return np.asarray(res.outputs["8"][0]["samples"].data)

    def _drain(self, bkt, done):
        for _ in range(16):
            if not bkt.n_active:
                return done
            bkt.step_once()
            for its, rows, _t in bkt.take_finished():
                arr = np.asarray(rows)
                for j, it in enumerate(its):
                    done[it["id"]] = arr[j * bkt.b:(j + 1) * bkt.b]
        raise AssertionError("bucket never drained")

    def _park_to_rec(self, bkt, idx, rank=0):
        recs = []
        for item, step, t_admit, x_rows in bkt.park_slots(idx):
            recs.append(cb_mod._ParkedRow(item, bkt.sig, rank, step,
                                          t_admit, x_rows,
                                          time.perf_counter()))
        return recs

    def test_park_resume_bit_identical_to_serial(self):
        """THE paging exactness guarantee, for a deterministic and an
        ancestral sampler: park a mid-schedule row to host while its
        co-tenant keeps stepping, resume it later, and the final latent
        is bit-equal to the serial run."""
        for sampler in ("euler", "euler_ancestral"):
            p1 = make_prompt(31, steps=3, sampler=sampler)
            p2 = make_prompt(32, steps=3, sampler=sampler)
            sig = sched.coalesce_signature(p1)
            serial = {s: self._serial(p)
                      for s, p in ((31, p1), (32, p2))}
            i1 = {"id": "a", "prompt": p1, "sig": sig, "cb": True}
            i2 = {"id": "b", "prompt": p2, "sig": sig, "cb": True}
            bkt = cb_mod._Bucket(sig, i1, OpContext(), max_slots=4)
            bkt.admit_many([i1, i2])
            bkt.step_once()                   # both at sigma index 1
            recs = self._park_to_rec(bkt, [0])   # a pages out...
            assert bkt.n_active == 1
            assert recs[0].step == 1
            done = {}
            self._drain(bkt, done)            # ...b runs to completion
            bkt.resume_parked(recs)           # a pages back in
            self._drain(bkt, done)
            assert (done["a"] == serial[31]).all(), sampler
            assert (done["b"] == serial[32]).all(), sampler

    def test_park_on_final_step_is_noop_resume(self):
        """Edge case: a row parked AT its final boundary has no steps
        left — resume must hand it straight to retirement (no extra
        step) and the latent is still the serial run's."""
        p = make_prompt(41, steps=2)
        sig = sched.coalesce_signature(p)
        it = {"id": "z", "prompt": p, "sig": sig, "cb": True}
        serial = self._serial(p)
        bkt = cb_mod._Bucket(sig, it, OpContext(), max_slots=2)
        bkt.admit(it)
        bkt.step_once()
        bkt.step_once()                       # schedule exhausted...
        recs = self._park_to_rec(bkt, [0])    # ...parked anyway
        assert recs[0].step == bkt.n_steps and bkt.n_active == 0
        bkt.resume_parked(recs)
        cohorts = bkt.take_finished()         # no step_once needed
        assert len(cohorts) == 1
        (its, rows, _t0), = cohorts
        assert its[0]["id"] == "z"
        assert (np.asarray(rows) == serial).all()

    def test_double_park_of_same_slot_rejected(self):
        p = make_prompt(42, steps=3)
        sig = sched.coalesce_signature(p)
        it = {"id": "d", "prompt": p, "sig": sig, "cb": True}
        bkt = cb_mod._Bucket(sig, it, OpContext(), max_slots=2)
        bkt.admit(it)
        with pytest.raises(ValueError, match="double-park"):
            bkt.park_slots([0, 0])
        with pytest.raises(ValueError, match="unknown slot"):
            bkt.park_slots([3])
        # the rejected calls left the slot intact and steppable
        assert bkt.n_active == 1
        bkt.step_once()

    @pytest.mark.xfail(strict=False, reason=(
        "depends on the process's history (ROADMAP C2): `write` is one "
        "process-wide jit, and the steady pass can meet an argument "
        "sharding the warm pass did not, which compiles one more variant "
        "of it unless an earlier test already had; passes alone"))
    def test_park_resume_stays_inside_warmed_shape_set(
            self, assert_nothing_compiled):
        """Zero steady-state retraces survive paging (the ISSUE 12
        guarantee): park's gather is a retire-cohort shape pair, resume
        is an admit write pair, keys are recomputed not gathered — after
        one warm pass that exercises park/resume cohort sizes, paging
        churn compiles nothing."""
        from comfyui_distributed_tpu.utils import trace as trace_mod
        p = make_prompt(51, steps=3)
        sig = sched.coalesce_signature(p)
        it0 = {"id": "w0", "prompt": p, "sig": sig, "cb": True}
        bkt = cb_mod._Bucket(sig, it0, OpContext(), max_slots=2)
        # warm: one pass of the exact steady-state sequence, so every
        # shape pair (park gather, compaction, pad-1 step, resume
        # write, both retire cohorts) compiles here
        bkt.admit_many([it0, {"id": "w1",
                              "prompt": make_prompt(52, steps=3),
                              "sig": sig, "cb": True}])
        bkt.step_once()
        warm_recs = self._park_to_rec(bkt, [1])
        bkt.step_once()
        bkt.resume_parked(warm_recs)
        self._drain(bkt, {})
        mark = trace_mod.GLOBAL_RETRACES.mark()
        bkt.admit_many([{"id": "s0", "prompt":
                         make_prompt(53, steps=3), "sig": sig,
                         "cb": True},
                        {"id": "s1", "prompt":
                         make_prompt(54, steps=3), "sig": sig,
                         "cb": True}])
        bkt.step_once()
        recs = self._park_to_rec(bkt, [1])
        bkt.step_once()
        bkt.resume_parked(recs)
        self._drain(bkt, {})
        assert_nothing_compiled(trace_mod.GLOBAL_RETRACES.since(mark))


class TestLatentPagingTensorParallel:
    """ISSUE 17 × ISSUE 16: parked rows must round-trip the 2-D
    data×tensor mesh layout — park gathers a sharded buffer to host,
    resume's ``_pin`` restores the canonical layout, and the remaining
    steps are bit-identical to the never-parked run."""

    @pytest.fixture()
    def tp_mesh(self, monkeypatch):
        import jax
        from comfyui_distributed_tpu.parallel import mesh as mesh_mod
        monkeypatch.setenv("DTPU_TP_MIN_SHARD_ELEMENTS", "2")
        registry.clear_pipeline_cache()
        mesh = mesh_mod.build_mesh(
            axes={C.DATA_AXIS: 2, C.TENSOR_AXIS: 2, C.SEQ_AXIS: 1},
            devices=jax.devices()[:4])
        mesh_mod.set_runtime(mesh_mod.MeshRuntime(mesh=mesh))
        yield mesh
        mesh_mod.set_runtime(None)
        registry.clear_pipeline_cache()

    def _drain(self, bkt, done):
        for _ in range(16):
            if not bkt.n_active:
                return done
            bkt.step_once()
            for its, rows, _t in bkt.take_finished():
                arr = np.asarray(rows)
                for j, it in enumerate(its):
                    done[it["id"]] = arr[j * bkt.b:(j + 1) * bkt.b]
        raise AssertionError("bucket never drained")

    def test_park_resume_bit_identical_under_tp(self, tp_mesh,
                                                monkeypatch):
        """Park one of two rows out of a 2-D-sharded bucket mid-flight,
        resume it after its co-tenant finishes, and both final latents
        are BIT-identical to the same prompts run without any parking
        through the same sharded step kernel.  The pad set is pinned to
        one size (the ISSUE 16 caveat: XLA CPU SPMD matmuls are not
        row-wise bit-stable across batch sizes)."""
        monkeypatch.setenv(C.CB_PAD_BUCKETS_ENV, "2")
        p1 = make_prompt(61, steps=3, sampler="euler_ancestral")
        p2 = make_prompt(62, steps=3, sampler="euler_ancestral")
        sig = sched.coalesce_signature(p1)
        # reference: the same two prompts, same bucket geometry, no
        # parking
        ref = {}
        i1 = {"id": "a", "prompt": p1, "sig": sig, "cb": True}
        i2 = {"id": "b", "prompt": p2, "sig": sig, "cb": True}
        bkt = cb_mod._Bucket(sig, i1, OpContext(), max_slots=2)
        assert bkt.pads == [2] and bkt._tp_mesh is tp_mesh
        bkt.admit_many([i1, i2])
        self._drain(bkt, ref)
        # paged run: a parks at sigma index 1, b drains, a resumes
        j1 = {"id": "a2", "prompt": p1, "sig": sig, "cb": True}
        j2 = {"id": "b2", "prompt": p2, "sig": sig, "cb": True}
        bkt = cb_mod._Bucket(sig, j1, OpContext(), max_slots=2)
        bkt.admit_many([j1, j2])
        bkt.step_once()
        recs = [cb_mod._ParkedRow(item, sig, 0, step, t_admit, x_rows,
                                  time.perf_counter())
                for item, step, t_admit, x_rows
                in bkt.park_slots([0])]
        from comfyui_distributed_tpu.parallel import sharding as shd
        # host copy detached from the mesh; the live buffer stays
        # canonically sharded
        assert isinstance(recs[0].x_rows, np.ndarray)
        done = {}
        self._drain(bkt, done)
        bkt.resume_parked(recs)
        # resume restored the canonical rows layout for this pad
        assert bkt.x.sharding.is_equivalent_to(
            shd.named(tp_mesh, shd.spec_of(bkt.x)), bkt.x.ndim)
        self._drain(bkt, done)
        assert (done["a2"] == ref["a"]).all()
        assert (done["b2"] == ref["b"]).all()


def make_harness(tmp_path, monkeypatch, slots=2, park="1",
                 park_max=None):
    """A ContinuousBatchExecutor driven BY THE TEST (never started):
    deterministic single-threaded park/resume scheduling, backed by a
    real ServerState for capture contexts and finalize plumbing."""
    monkeypatch.setenv(C.CB_PARK_ENV, park)
    monkeypatch.setenv(C.CB_SLOTS_ENV, str(slots))
    if park_max is not None:
        monkeypatch.setenv(C.CB_PARK_MAX_ENV, str(park_max))
    st = make_state(tmp_path, cb=False)
    return st, cb_mod.ContinuousBatchExecutor(st)


class TestSloPreemption:
    """Executor-level residency scheduling (ISSUE 17): preempt order
    batch < free < paid-never, victim/resume ordering, the PR 5 HBM
    gate, and the PR 13 client-gone composition."""

    def test_room_for_counts_preemptible_lower_class(
            self, tmp_path, monkeypatch):
        st, ex = make_harness(tmp_path, monkeypatch)
        ex._admit_cb([item(201, cls="batch", steps=4)])
        ex._admit_cb([item(202, cls="free", steps=4)])
        bkt = next(iter(ex._buckets.values()))
        assert bkt.n_active == 2            # full
        assert ex.room_for(item(203, cls="paid", steps=4)) == 2
        assert ex.room_for(item(204, cls="free", steps=4)) == 1
        assert ex.room_for(item(205, cls="batch", steps=4)) == -1

    def test_park_disabled_keeps_hard_full_semantics(
            self, tmp_path, monkeypatch):
        st, ex = make_harness(tmp_path, monkeypatch, park="0")
        assert ex.parked.room() == 0
        ex._admit_cb([item(211, cls="batch", steps=4),
                      item(212, cls="batch", steps=4)])
        assert ex.room_for(item(213, cls="paid", steps=4)) == -1

    def test_paid_admit_parks_youngest_lowest_class(
            self, tmp_path, monkeypatch):
        """A paid arrival into a full bucket parks the YOUNGEST
        batch-tier row (oldest started work keeps its slot), admits the
        paid prompt at the same boundary, and books the park on every
        surface: stats, counters, gauge, store."""
        from comfyui_distributed_tpu.utils import trace as trace_mod
        st, ex = make_harness(tmp_path, monkeypatch)
        ex._admit_cb([item(221, cls="batch", steps=6)])
        ex._admit_cb([item(222, cls="batch", steps=6)])
        ex._admit_cb([item(223, cls="paid", steps=6)])
        bkt = next(iter(ex._buckets.values()))
        assert {s.item["id"] for s in bkt.slots} == {"i221", "i223"}
        assert ex.parked.has("i222") and ex.parked.count() == 1
        snap = ex.snapshot()
        assert snap["parks"] == 1 and snap["preemptions"] == 1
        assert snap["parked"] == 1 and snap["park_enabled"] is True
        assert trace_mod.GLOBAL_GAUGES.get("cb_parked") == 1.0
        assert not ex.idle()                # parked rows pin liveness

    def test_preempted_row_resumes_and_matches_serial(
            self, tmp_path, monkeypatch):
        """End-to-end through the executor's own park/resume methods: a
        batch row preempted mid-schedule by a paid arrival resumes once
        the slot frees and its latent is bit-equal to the serial run."""
        st, ex = make_harness(tmp_path, monkeypatch, slots=1)
        victim = item(231, cls="batch", steps=4)
        serial = np.asarray(WorkflowExecutor(OpContext()).execute(
            victim["prompt"]).outputs["8"][0]["samples"].data)
        ex._admit_cb([victim])
        bkt = next(iter(ex._buckets.values()))
        bkt.step_once()                     # victim is mid-flight...
        ex._admit_cb([item(232, cls="paid", steps=4)])
        assert ex.parked.has("i231")
        done = {}
        for _ in range(8):                  # ...paid runs to completion
            if not bkt.n_active:
                break
            bkt.step_once()
            for its, rows, _t in bkt.take_finished():
                arr = np.asarray(rows)
                for j, it in enumerate(its):
                    done[it["id"]] = arr[j * bkt.b:(j + 1) * bkt.b]
        assert "i232" in done
        assert ex._resume_boundary() is True
        assert not ex.parked.has("i231")
        for _ in range(8):
            if not bkt.n_active:
                break
            bkt.step_once()
            for its, rows, _t in bkt.take_finished():
                arr = np.asarray(rows)
                for j, it in enumerate(its):
                    done[it["id"]] = arr[j * bkt.b:(j + 1) * bkt.b]
        assert (done["i231"] == serial).all()
        snap = ex.snapshot()
        assert snap["resumes"] == 1 and snap["parked"] == 0

    def test_resume_order_free_before_batch(self, tmp_path,
                                            monkeypatch):
        st, ex = make_harness(tmp_path, monkeypatch)
        ex._admit_cb([item(241, cls="batch", steps=4)])
        ex._admit_cb([item(242, cls="free", steps=4)])
        bkt = next(iter(ex._buckets.values()))
        ex._park_out(bkt, [0, 1])
        assert bkt.n_active == 0 and ex.parked.count() == 2
        assert ex._resume_boundary() is True
        # both fit, and the higher class landed first
        assert [s.item["id"] for s in bkt.slots] == ["i242", "i241"]

    def test_resume_gated_on_hbm_fraction(self, tmp_path, monkeypatch):
        """PR 5 telemetry drives residency: above the fraction nothing
        resumes (re-admitting under pressure would undo the shed), and
        _pressure_park sheds exactly one lowest-class slot per
        boundary."""
        st, ex = make_harness(tmp_path, monkeypatch)
        ex._admit_cb([item(251, cls="batch", steps=4)])
        ex._admit_cb([item(252, cls="free", steps=4)])
        bkt = next(iter(ex._buckets.values()))
        ex._mem_probe = lambda: {"bytes_in_use": 95, "bytes_limit": 100}
        ex._pressure_park()                 # sheds the batch row only
        assert ex.parked.count() == 1 and ex.parked.has("i251")
        assert ex._resume_boundary() is False   # gate holds it out
        assert ex.parked.count() == 1
        ex._mem_probe = lambda: {"bytes_in_use": 10, "bytes_limit": 100}
        assert ex._resume_boundary() is True
        assert ex.parked.count() == 0 and bkt.n_active == 2

    def test_paid_rows_never_pressure_parked(self, tmp_path,
                                             monkeypatch):
        st, ex = make_harness(tmp_path, monkeypatch)
        ex._admit_cb([item(261, cls="paid", steps=4),
                      item(262, cls="paid", steps=4)])
        ex._mem_probe = lambda: {"bytes_in_use": 99, "bytes_limit": 100}
        ex._pressure_park()
        assert ex.parked.count() == 0       # nothing preemptible

    def test_abandoned_parked_row_freed_without_resume(
            self, tmp_path, monkeypatch):
        """PR 13 composition (satellite): the client of a PARKED row
        disconnects — the row is finalized as abandoned and freed, its
        slot claim evaporates, and no denoise steps are spent on it."""
        from comfyui_distributed_tpu.runtime import reuse as reuse_mod
        st, ex = make_harness(tmp_path, monkeypatch)
        ex._admit_cb([item(271, cls="batch", steps=4)])
        bkt = next(iter(ex._buckets.values()))
        ex._park_out(bkt, [0])
        assert ex.parked.count() == 1
        reuse_mod.PREVIEWS.abandon("i271")
        steps_before = bkt.steps_done
        assert ex._resume_boundary() is False   # freed, not resumed
        assert ex.parked.count() == 0 and bkt.n_active == 0
        assert bkt.steps_done == steps_before
        hist = wait_history(st, ["i271"], timeout=30)
        assert hist["i271"]["status"] == "abandoned"
        assert ex.snapshot()["abandoned"] == 1

    def test_all_parked_bucket_survives_eviction(self, tmp_path,
                                                 monkeypatch):
        """A bucket whose every row is parked is idle-by-count but must
        NOT be evicted: its captured conditioning is the only thing the
        rows can resume into."""
        st, ex = make_harness(tmp_path, monkeypatch)
        ex._admit_cb([item(281, cls="batch", steps=4)])
        bkt = next(iter(ex._buckets.values()))
        ex._park_out(bkt, [0])
        assert bkt.n_active == 0
        ex._evict_idle_bucket()
        assert bkt.sig in ex._buckets
        assert ex._resume_boundary() is True
        assert bkt.n_active == 1

    def test_validate_cb_env_rejects_malformed_knobs(self):
        cb_mod.validate_cb_env({})           # absent -> fine
        cb_mod.validate_cb_env({
            C.CB_SLOTS_ENV: "8", C.CB_PARK_ENV: "1",
            C.CB_PARK_MAX_ENV: "0",
            C.CB_PARK_HBM_FRACTION_ENV: "0.85"})
        for env, frag in (
                ({C.CB_SLOTS_ENV: "0"}, C.CB_SLOTS_ENV),
                ({C.CB_SLOTS_ENV: "two"}, C.CB_SLOTS_ENV),
                ({C.CB_PARK_MAX_ENV: "-1"}, C.CB_PARK_MAX_ENV),
                ({C.CB_PARK_ENV: "maybe"}, C.CB_PARK_ENV),
                ({C.CB_PARK_HBM_FRACTION_ENV: "1.5"},
                 C.CB_PARK_HBM_FRACTION_ENV),
                ({C.CB_PARK_HBM_FRACTION_ENV: "lots"},
                 C.CB_PARK_HBM_FRACTION_ENV)):
            with pytest.raises(ValueError, match=frag):
                cb_mod.validate_cb_env(env)


class TestCbPopPreemption:
    """pop_cb_admit's blocked-class re-peek (ISSUE 17): a class whose
    bucket is full no longer blinds the pop to admissible work behind
    it in stride order."""

    def test_blocked_class_repeeks_next_class(self):
        adm = sched.AdmissionController(
            weights={"batch": 6.0, "paid": 1.0, "free": 1.0},
            rate={}, burst={}, shed={})
        b, p = item(301, cls="batch"), item(302, cls="paid")
        queue = [b, p]
        # batch wins the stride peek but its bucket is full; paid has
        # preemption room
        kind, items = sched.pop_cb_admit(
            queue, adm,
            lambda it: -1 if it["tenant"] == "batch" else 1)
        assert kind == "cb"
        assert [it["id"] for it in items] == ["i302"]
        assert [it["id"] for it in queue] == ["i301"]

    def test_all_classes_blocked_defers_without_stride_charge(self):
        adm = sched.AdmissionController(
            weights={"paid": 6.0, "free": 3.0, "batch": 1.0},
            rate={}, burst={}, shed={})
        queue = [item(311, cls="paid"), item(312, cls="batch")]
        before = dict(adm._pass)
        kind, items = sched.pop_cb_admit(queue, adm, lambda it: -1)
        assert kind == "defer" and not items and len(queue) == 2
        # a deferred boundary must not advance any class's pass
        assert dict(adm._pass) == before


class TestServerPreemptionE2E:
    def test_paid_preempts_running_batch_end_to_end(self, tmp_path,
                                                    monkeypatch):
        """The tentpole scenario through a real CB ServerState: a
        saturated one-slot bucket running a batch-tier prompt gets a
        paid arrival — the batch row PARKS mid-schedule, the paid
        prompt takes the slot, and the parked row resumes and completes
        after it.  Both succeed; every park surface moved."""
        monkeypatch.setenv(C.CB_PARK_ENV, "1")
        monkeypatch.setenv(C.CB_SLOTS_ENV, "1")
        st = make_state(tmp_path)
        # same structural signature (preemption is within-bucket), so
        # the paid arrival lands on the saturated batch bucket
        pid_b = st.enqueue_prompt(make_prompt(91, steps=8), "c",
                                  tenant="batch")
        deadline = time.monotonic() + 60
        while st.cb.snapshot()["admits"] < 1:
            assert time.monotonic() < deadline, "batch never admitted"
            time.sleep(0.002)
        pid_p = st.enqueue_prompt(make_prompt(92, steps=8), "c",
                                  tenant="paid")
        hist = wait_history(st, [pid_b, pid_p])
        assert all(h["status"] == "success" for h in hist.values())
        snap = st.cb.snapshot()
        assert snap["parks"] >= 1 and snap["preemptions"] >= 1
        assert snap["resumes"] >= 1
        assert snap["parked"] == 0 and snap["retires"] == 2
        assert snap["fallbacks"] == 0
        assert st.drain(20) is True

    def test_metrics_surfaces_expose_paging(self, tmp_path,
                                            monkeypatch):
        monkeypatch.setenv(C.CB_PARK_ENV, "1")

        async def body():
            st = make_state(tmp_path)
            client = TestClient(TestServer(build_app(st)))
            await client.start_server()
            try:
                m = await (await client.get(
                    "/distributed/metrics")).json()
                b = m["batching"]
                assert b["park_enabled"] is True
                assert {"parked", "park_room", "parks", "resumes",
                        "preemptions"} <= set(b)
                text = await (await client.get(
                    "/distributed/metrics.prom")).text()
                assert "dtpu_cb_parked" in text
                assert "dtpu_cb_parks_total" in text
                assert "dtpu_cb_resumes_total" in text
                assert "dtpu_cb_preemptions_total" in text
            finally:
                await client.close()
                st.drain(5)
        asyncio.run(body())
