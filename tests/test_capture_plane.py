"""Continuous capture plane (ISSUE 18): durable trace export with
rotation + retention, the SLO burn-rate engine, exemplar-linked
histograms, the CB flight deck, and the server surfaces that tie them
together (`/distributed/slo`, extended metrics/reset, Perfetto export).

CPU-only, tier-1-eligible: exporter/engine units run against local
instances; the server tests use in-process ServerStates over aiohttp
TestServer sockets like test_observability.py.
"""

import json
import os
import time
from types import SimpleNamespace

import pytest

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils import slo as slo_mod
from comfyui_distributed_tpu.utils import trace as tr
from comfyui_distributed_tpu.utils import trace_export as te
from tests.test_observability import (make_prompt, run_with_client,
                                      validate_prometheus,
                                      wait_remote_history)


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


@pytest.fixture(autouse=True)
def export_off(monkeypatch):
    """Each test opts into export with its own dir; never inherit one."""
    monkeypatch.delenv(C.TRACE_EXPORT_DIR_ENV, raising=False)
    yield
    # drop the module singleton so the next test re-reads the env
    te.current()


def commit_trace(pid, n_children=2, status="ok", worker=None):
    """One committed multi-span trace through the REAL span model."""
    root = tr.start_span("job", attrs={"prompt_id": pid})
    children = []
    for i in range(n_children):
        attrs = {"node": f"n{i}"}
        if worker:
            attrs["worker"] = worker
        c = tr.start_span(f"compute_{i}", parent=root, attrs=attrs)
        c.end(status="error" if (status != "ok" and i == 0) else "ok")
        children.append(c)
    tr.event_span("queue_wait", root.start_s, root.start_s + 0.01,
                  parent=root)
    root.end()
    tr.GLOBAL_TRACES.commit(pid, root.trace_id, status=status,
                            root_span_id=root.span_id, duration_s=1.25)
    return root


class TestExportRoundTrip:
    def test_roundtrip_field_for_field(self, tmp_path, monkeypatch):
        d = str(tmp_path / "cap")
        monkeypatch.setenv(C.TRACE_EXPORT_DIR_ENV, d)
        root = commit_trace("rt1", status="error")
        mem = tr.GLOBAL_TRACES.get("rt1")
        disk = te.load_trace(d, prompt_id="rt1")
        assert disk is not None
        key = lambda s: s["span_id"]  # noqa: E731
        assert sorted(disk["spans"], key=key) \
            == sorted(mem["spans"], key=key)
        for k in ("prompt_id", "trace_id", "status", "root_span_id",
                  "duration_s", "finished_at"):
            assert disk[k] == mem[k], k
        assert disk["schema"] == te.SCHEMA_VERSION
        assert disk["trace_id"] == root.trace_id
        # the reconstructed forest nests exactly like the in-memory one
        forest = te.load_forest(disk)
        assert [n["name"] for n in forest] == ["job"]
        assert sorted(c["name"] for c in forest[0]["children"]) \
            == ["compute_0", "compute_1", "queue_wait"]

    def test_load_by_trace_id_newest_wins(self, tmp_path, monkeypatch):
        d = str(tmp_path / "cap")
        monkeypatch.setenv(C.TRACE_EXPORT_DIR_ENV, d)
        commit_trace("tw1")
        root2 = commit_trace("tw2")
        assert te.load_trace(d, trace_id=root2.trace_id)[
            "prompt_id"] == "tw2"
        assert te.load_trace(d)["prompt_id"] == "tw2"  # newest record
        assert te.load_trace(d, prompt_id="nope") is None

    def test_unset_dir_writes_nothing(self, tmp_path):
        # export_off fixture guarantees the env is unset
        commit_trace("off1")
        assert te.current() is None
        assert te.stats() == {"enabled": False}
        assert not list((tmp_path).glob("capture-*"))

    def test_torn_and_foreign_lines_skipped(self, tmp_path, monkeypatch):
        d = str(tmp_path / "cap")
        monkeypatch.setenv(C.TRACE_EXPORT_DIR_ENV, d)
        commit_trace("ok1")
        seg = te.segment_paths(d)[-1]
        with open(seg, "ab") as fh:
            fh.write(b'{"schema": 999, "prompt_id": "future"}\n')
            fh.write(b'not json at all\n')
            fh.write(b'{"schema": 1, "prompt_id": "torn"')  # no newline
        recs = list(te.iter_records(d))
        assert [r["prompt_id"] for r in recs] == ["ok1"]


class TestRotationRetention:
    def _rec(self, i, pad=80):
        return {"prompt_id": f"p{i:04d}", "trace_id": f"{i:032x}",
                "status": "ok", "root_span_id": None, "duration_s": 0.1,
                "finished_at": 1.0, "spans": [{"pad": "x" * pad}]}

    def test_rotation_respects_byte_budget(self, tmp_path):
        exp = te.TraceExporter(str(tmp_path), segment_bytes=400,
                               retain_bytes=100000)
        for i in range(20):
            assert exp.export(self._rec(i))
        exp.close()
        segs = te.segment_paths(str(tmp_path))
        assert len(segs) > 1 and exp.rotations >= len(segs) - 1
        for p in segs:
            assert os.path.getsize(p) <= 400, p
        assert len(list(te.iter_records(str(tmp_path)))) == 20

    def test_oversized_record_lands_alone(self, tmp_path):
        exp = te.TraceExporter(str(tmp_path), segment_bytes=200,
                               retain_bytes=100000)
        exp.export(self._rec(0, pad=16))
        exp.export(self._rec(1, pad=600))   # single record > budget
        exp.export(self._rec(2, pad=16))
        exp.close()
        assert exp.dropped == 0
        sizes = [os.path.getsize(p)
                 for p in te.segment_paths(str(tmp_path))]
        assert any(s > 200 for s in sizes)  # it landed...
        got = [r["prompt_id"] for r in te.iter_records(str(tmp_path))]
        assert got == ["p0000", "p0001", "p0002"]  # ...and nothing lost

    def test_retention_deletes_oldest_segments(self, tmp_path):
        exp = te.TraceExporter(str(tmp_path), segment_bytes=300,
                               retain_bytes=1200)
        for i in range(40):
            exp.export(self._rec(i))
        exp.close()
        segs = te.segment_paths(str(tmp_path))
        assert exp.retired_segments > 0
        total = sum(os.path.getsize(p) for p in segs)
        assert total <= 1200
        recs = [r["prompt_id"] for r in te.iter_records(str(tmp_path))]
        # survivors are a contiguous NEWEST suffix — retention only
        # ever eats from the oldest end
        assert recs and recs[-1] == "p0039"
        assert recs == [f"p{i:04d}"
                        for i in range(40 - len(recs), 40)]

    def test_capture_dir_under_budget_across_200_traces(self, tmp_path):
        exp = te.TraceExporter(str(tmp_path), segment_bytes=1000,
                               retain_bytes=5000)
        for i in range(200):
            exp.export(self._rec(i))
        exp.close()
        assert exp.exported == 200 and exp.dropped == 0
        total = sum(os.path.getsize(p)
                    for p in te.segment_paths(str(tmp_path)))
        assert total <= 5000

    def test_resume_numbering_after_restart(self, tmp_path):
        exp = te.TraceExporter(str(tmp_path), segment_bytes=60,
                               retain_bytes=100000)
        exp.export(self._rec(0, pad=16))
        exp.export(self._rec(1, pad=16))
        exp.close()
        before = te.segment_paths(str(tmp_path))
        exp2 = te.TraceExporter(str(tmp_path), segment_bytes=60,
                                retain_bytes=100000)
        exp2.export(self._rec(2, pad=16))
        exp2.close()
        after = te.segment_paths(str(tmp_path))
        assert before == after[:len(before)]  # nothing overwritten
        assert len(after) == len(before) + 1


class TestSloSpec:
    def test_parse_grammar(self):
        spec = slo_mod.parse_slo_spec(
            "paid:p95<2s,completion>0.999;free:p99<500ms")
        assert set(spec) == {"paid", "free"}
        lat, comp = spec["paid"]
        assert lat.kind == "latency" and lat.quantile == 0.95
        assert lat.threshold_s == 2.0
        assert abs(lat.budget_frac - 0.05) < 1e-9
        assert comp.kind == "completion" and comp.min_ratio == 0.999
        assert abs(comp.budget_frac - 0.001) < 1e-9
        assert spec["free"][0].threshold_s == 0.5

    def test_malformed_clauses_skipped_not_fatal(self):
        spec = slo_mod.parse_slo_spec(
            "paid:p95<2s;bogus;free:pXX<1s,completion>0.99;:p95<1s")
        assert set(spec) == {"paid", "free"}
        assert [o.raw for o in spec["free"]] == ["completion>0.99"]
        assert slo_mod.parse_slo_spec(None) == {}
        assert slo_mod.parse_slo_spec("") == {}

    def test_out_of_range_objectives_rejected(self):
        assert slo_mod.parse_slo_spec("a:p0<1s") == {}
        assert slo_mod.parse_slo_spec("a:completion>1.0") == {}
        assert slo_mod.parse_slo_spec("a:p95<0s") == {}


class TestSloEngine:
    def _engine(self, spec="paid:p95<1s,completion>0.99"):
        return slo_mod.SLOEngine(slo_mod.parse_slo_spec(spec),
                                 fast_s=10.0, slow_s=100.0)

    def test_burn_rate_math_latency(self):
        eng = self._engine()
        now = 1000.0
        for i in range(20):         # 2/20 slow = 10% bad vs 5% budget
            eng.record("paid", 2.0 if i < 2 else 0.1, True, now=now)
        assert abs(eng.burn_rate("paid", "fast", now=now) - 2.0) < 1e-9

    def test_burn_rate_math_completion(self):
        eng = self._engine("paid:completion>0.9")
        now = 1000.0
        for i in range(10):         # 2/10 failed = 20% bad vs 10% budget
            eng.record("paid", 0.1, i >= 2, now=now)
        assert abs(eng.burn_rate("paid", "fast", now=now) - 2.0) < 1e-9

    def test_window_pruning_decays_burn(self):
        eng = self._engine()
        now = 1000.0
        for _ in range(10):
            eng.record("paid", 5.0, True, now=now)   # all violate
        assert eng.burn_rate("paid", "fast", now=now) > 1.0
        # fast window (10s) ages out; slow window (100s) still burning
        later = now + 11.0
        assert eng.burn_rate("paid", "fast", now=later) == 0.0
        assert eng.burn_rate("paid", "slow", now=later) > 1.0

    def test_evaluate_shape_and_budget(self):
        eng = self._engine()
        now = 1000.0
        for _ in range(4):
            eng.record("paid", 5.0, True, now=now)
        snap = eng.evaluate(now=now)
        assert snap["enabled"] is True
        t = snap["tenants"]["paid"]
        assert [o["raw"] for o in t["objectives"]] \
            == ["p95<1s", "completion>0.99"]
        fast = t["windows"]["fast"]
        assert fast["count"] == 4 and fast["ok_ratio"] == 1.0
        assert fast["burn_rate"] == fast["burn_rates"]["p95<1s"]
        assert fast["burn_rate"] > 1.0
        assert t["budget_remaining"] == 0.0     # slow window burning too
        # unknown-tenant traffic still shows up (objective-less)
        eng.record("mystery", 0.1, True, now=now)
        snap = eng.evaluate(now=now)
        assert snap["tenants"]["mystery"]["objectives"] == []

    def test_latency_threshold_is_tightest(self):
        eng = self._engine("paid:p95<2s,p99<5s,completion>0.9")
        assert eng.latency_threshold("paid") == 2.0
        assert eng.latency_threshold("free") is None

    def test_disarmed_engine_is_noop(self):
        eng = slo_mod.SLOEngine({})
        assert not eng.enabled
        eng.record("paid", 9.0, False)
        assert eng.evaluate()["tenants"] == {}
        assert eng.burn_rate("paid") == 0.0
        assert eng.prom_families() == []

    def test_prom_families_and_reset(self):
        eng = self._engine()
        now = 1000.0
        eng.record("paid", 5.0, True, now=now)
        fams = eng.prom_families()
        names = [f[0] for f in fams]
        assert names == ["dtpu_slo_burn_rate",
                         "dtpu_slo_budget_remaining"]
        burn = fams[0][3]
        assert {tuple(sorted(lbl.items())) for lbl, _ in burn} \
            == {(("tenant", "paid"), ("window", "fast")),
                (("tenant", "paid"), ("window", "slow"))}
        eng.reset()
        snap = eng.evaluate(now=now)
        assert snap["tenants"]["paid"]["windows"]["fast"]["count"] == 0

    def test_from_env(self, monkeypatch):
        monkeypatch.setenv(C.SLO_SPEC_ENV, "paid:p95<2s")
        monkeypatch.setenv(C.SLO_FAST_WINDOW_ENV, "7")
        monkeypatch.setenv(C.SLO_SLOW_WINDOW_ENV, "70")
        eng = slo_mod.SLOEngine.from_env()
        assert eng.enabled and eng.fast_s == 7.0 and eng.slow_s == 70.0

    def test_autoscale_arming(self, monkeypatch):
        monkeypatch.delenv(C.AUTOSCALE_SLO_ENV, raising=False)
        assert not slo_mod.autoscale_slo_armed()
        monkeypatch.setenv(C.AUTOSCALE_SLO_ENV, "1")
        assert slo_mod.autoscale_slo_armed()


class TestExemplars:
    def test_histogram_records_bucket_exemplar(self):
        h = tr.LatencyHistogram(bounds=(0.01, 0.1, 1.0))
        h.record(0.05, trace_id="aa" * 16)
        h.record(5.0, trace_id="bb" * 16)
        h.record(0.06)                      # no trace -> no overwrite
        ex = h.exemplars_snapshot()
        assert ex[1][0] == "aa" * 16 and ex[1][1] == 0.05
        assert ex[3][0] == "bb" * 16        # overflow bucket
        assert set(ex) == {1, 3}

    def test_prometheus_renders_exemplar_and_validator_accepts(self):
        tr.GLOBAL_STAGES.record("exem_stage", 0.015,
                                trace_id="cd" * 16)
        text = tr.prometheus_text()
        lines = [l for l in text.splitlines()
                 if l.startswith("dtpu_stage_seconds_bucket")
                 and 'stage="exem_stage"' in l and " # {" in l]
        assert len(lines) == 1
        assert f'# {{trace_id="{"cd" * 16}"}} 0.015' in lines[0]
        validate_prometheus(text)           # exemplar-aware grammar

    def test_reset_clears_exemplars(self):
        tr.GLOBAL_STAGES.record("exem_gone", 0.01, trace_id="ee" * 16)
        tr.reset_aggregate_metrics()
        assert "exem_gone" not in tr.prometheus_text()


class TestEvictionAccounting:
    def test_ring_eviction_counted(self):
        rec = tr.FlightRecorder(max_traces=2)
        before = tr.GLOBAL_COUNTERS.snapshot().get("trace_evictions", 0)
        for i in range(5):
            sp = tr.Span(f"j{i}")
            rec.add(sp.trace_id, sp.to_dict())
            rec.commit(f"ev{i}", sp.trace_id, status="ok")
        assert rec.eviction_count() == 3
        assert tr.GLOBAL_COUNTERS.snapshot()["trace_evictions"] \
            == before + 3
        rec.reset()
        assert rec.eviction_count() == 0

    def test_evictions_total_in_prom(self):
        text = tr.prometheus_text()
        assert "# TYPE dtpu_trace_evictions_total counter" in text


class TestFlightDeck:
    def _executor(self, monkeypatch, ring=4):
        monkeypatch.setenv(C.CB_DECK_RING_ENV, str(ring))
        from comfyui_distributed_tpu.workflow import batch_executor \
            as cb_mod
        return cb_mod.ContinuousBatchExecutor(SimpleNamespace())

    def test_deck_ring_rows_and_cap(self, monkeypatch):
        ex = self._executor(monkeypatch, ring=4)
        bkt = SimpleNamespace(sig="cafebabe1234", n_active=3, capacity=4)
        with ex._lock:
            ex._stats["admits"] = 5
        for i in range(6):
            ex._deck_record(bkt)
        snap = ex.snapshot()
        assert snap["deck_ring"] == 4 and len(snap["deck"]) == 4
        rows = snap["deck"]
        assert [r["seq"] for r in rows] == [2, 3, 4, 5]
        assert rows[-1]["bucket"] == "cafebabe"
        assert rows[-1]["busy"] == 3 and rows[-1]["free"] == 1
        # counter deltas: all 5 admits land on the FIRST boundary only
        assert rows[0]["admits"] == 0 if rows[0]["seq"] else 5
        assert sum(r["admits"] for r in rows) == 0  # later rows: no new

    def test_deck_counts_deltas_between_boundaries(self, monkeypatch):
        ex = self._executor(monkeypatch, ring=8)
        bkt = SimpleNamespace(sig="deadbeef0000", n_active=1, capacity=2)
        ex._deck_record(bkt)
        with ex._lock:
            ex._stats["admits"] += 2
            ex._stats["retires"] += 1
            ex._stats["preemptions"] += 1
        ex._deck_record(bkt)
        rows = ex.snapshot()["deck"]
        assert rows[-1]["admits"] == 2 and rows[-1]["retires"] == 1
        assert rows[-1]["preemptions"] == 1

    def test_admit_to_first_step_histogram_end_to_end(
            self, tmp_path, monkeypatch):
        """A real bucket stepped by the driver path records the
        admit-to-first-step wait exactly once per row."""
        from tests.test_batching import item, make_state
        from comfyui_distributed_tpu.workflow import batch_executor \
            as cb_mod
        monkeypatch.setenv(C.CB_SLOTS_ENV, "2")
        st = make_state(tmp_path, cb=False)
        ex = cb_mod.ContinuousBatchExecutor(st)
        ex._admit_cb([item(401, steps=2), item(402, steps=2)])
        bkt = next(iter(ex._buckets.values()))
        for _ in range(6):
            if not bkt.n_active:
                break
            ex._step_and_retire(bkt)
        snap = ex.snapshot()
        assert snap["admit_to_first_step"]["count"] == 2
        assert snap["deck"], "step boundaries recorded deck rows"
        assert snap["deck"][0]["bucket"] == bkt.sig[:8]
        stages = tr.GLOBAL_STAGES.snapshot()
        assert stages.get("cb_admit_to_first_step",
                          {}).get("count", 0) >= 2


class TestPerfetto:
    def test_conversion_lanes_and_events(self, tmp_path, monkeypatch):
        d = str(tmp_path / "cap")
        monkeypatch.setenv(C.TRACE_EXPORT_DIR_ENV, d)
        commit_trace("pf1", worker="worker_a")
        rec = te.load_trace(d, prompt_id="pf1")
        doc = te.to_perfetto(rec)
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        xs = [e for e in evs if e["ph"] == "X"]
        metas = [e for e in evs if e["ph"] == "M"]
        assert len(xs) == len(rec["spans"])
        lane_names = {m["args"]["name"] for m in metas
                      if m["name"] == "thread_name"}
        assert lane_names == {"master", "worker_a"}
        job = [e for e in xs if e["name"] == "job"][0]
        span = [s for s in rec["spans"] if s["name"] == "job"][0]
        assert job["ts"] == round(span["start_s"] * 1e6, 3)
        assert job["args"]["trace_id"] == rec["trace_id"]
        # events are start-ordered for the viewer
        assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)

    def test_cli_offline_listing_and_perfetto(self, tmp_path,
                                              monkeypatch, capsys):
        d = str(tmp_path / "cap")
        monkeypatch.setenv(C.TRACE_EXPORT_DIR_ENV, d)
        commit_trace("cli1")
        from comfyui_distributed_tpu import cli
        assert cli.main(["trace", "--export-dir", d]) == 0
        assert "cli1" in capsys.readouterr().out
        assert cli.main(["trace", "cli1", "--export-dir", d]) == 0
        assert "job" in capsys.readouterr().out
        out = str(tmp_path / "pf.json")
        assert cli.main(["trace", "cli1", "--export-dir", d,
                         "--perfetto", "--out", out]) == 0
        doc = json.load(open(out))
        assert doc["traceEvents"]
        assert cli.main(["trace", "missing", "--export-dir", d]) == 1


class TestServerSurfaces:
    def test_slo_route_metrics_and_total_reset(self, tmp_path, monkeypatch,
                                               assert_nothing_compiled):
        d = str(tmp_path / "cap")
        monkeypatch.setenv(C.TRACE_EXPORT_DIR_ENV, d)
        monkeypatch.setenv(C.SLO_SPEC_ENV,
                           "paid:p95<0.001s,completion>0.999")

        async def body(client, state):
            r = await client.post("/prompt", json={
                "prompt": make_prompt(11), "client_id": "cp"})
            pid = (await r.json())["prompt_id"]
            await wait_remote_history(client, pid)

            # /distributed/slo: the tight objective is burning
            slo = await (await client.get("/distributed/slo")).json()
            assert slo["enabled"] is True
            paid = slo["tenants"]["paid"]
            assert paid["windows"]["fast"]["count"] >= 1
            assert paid["windows"]["fast"]["burn_rate"] > 1.0

            # breach event span landed in the committed trace
            rec = tr.GLOBAL_TRACES.get(pid)
            names = {s["name"] for s in rec["spans"]}
            assert "slo_breach" in names
            breach = [s for s in rec["spans"]
                      if s["name"] == "slo_breach"][0]
            assert breach["attrs"]["tenant"] == "paid"

            # JSON metrics: slo block + export stats + evictions
            m = await (await client.get("/distributed/metrics")).json()
            assert m["slo"]["enabled"] is True
            assert m["tracing"]["export"]["enabled"] is True
            assert m["tracing"]["export"]["exported"] >= 1
            assert m["tracing"]["export"]["dropped"] == 0
            assert "evictions" in m["tracing"]

            # prom text: new families + exemplar-aware grammar
            text = await (await client.get(
                "/distributed/metrics.prom")).text()
            types = validate_prometheus(text)
            assert types.get("dtpu_slo_burn_rate") == "gauge"
            assert types.get("dtpu_slo_budget_remaining") == "gauge"
            assert types.get("dtpu_trace_export_traces_total") \
                == "counter"
            assert types.get("dtpu_trace_evictions_total") == "counter"
            assert 'dtpu_slo_burn_rate{tenant="paid",window="fast"}' \
                in text
            # the e2e histogram carries the committed trace's exemplar
            ex_lines = [l for l in text.splitlines()
                        if l.startswith("dtpu_stage_seconds_bucket")
                        and 'stage="job_e2e"' in l and " # {" in l]
            assert ex_lines, "job_e2e bucket exemplar missing"
            assert rec["trace_id"] in ex_lines[0]

            # capture file round-trips the job
            disk = te.load_trace(d, prompt_id=pid)
            assert disk is not None and disk["status"] == "ok"

            # the armed plane (tracing + export + SLO engine + exemplars)
            # never touches compiled code: a warm request compiles nothing
            mark = tr.GLOBAL_RETRACES.mark()
            r = await client.post("/prompt", json={
                "prompt": make_prompt(13), "client_id": "cp"})
            await wait_remote_history(client, (await r.json())["prompt_id"])
            assert_nothing_compiled(tr.GLOBAL_RETRACES.since(mark))

            # total reset: SLO windows + exporter counters clear too
            r = await client.post("/distributed/metrics/reset", json={})
            cleared = (await r.json())["cleared"]
            assert cleared["slo_windows"] and cleared["export_counters"]
            slo = await (await client.get("/distributed/slo")).json()
            assert slo["tenants"]["paid"]["windows"]["fast"][
                "count"] == 0
            m = await (await client.get("/distributed/metrics")).json()
            assert m["tracing"]["export"]["exported"] == 0

        run_with_client(body, tmp_path)

    def test_tracing_off_writes_no_capture_files(self, tmp_path,
                                                 monkeypatch):
        d = str(tmp_path / "cap")
        monkeypatch.setenv(C.TRACE_EXPORT_DIR_ENV, d)
        tr.set_tracing(False)

        async def body(client, state):
            r = await client.post("/prompt", json={
                "prompt": make_prompt(12), "client_id": "cp"})
            pid = (await r.json())["prompt_id"]
            await wait_remote_history(client, pid)
            assert te.segment_paths(d) == []

        run_with_client(body, tmp_path)

    def test_autoscaler_reads_paid_fast_burn(self, monkeypatch):
        """The DTPU_AUTOSCALE_SLO hook: burn > 1 alone trips scale-up
        pressure with a dedicated reason."""
        from comfyui_distributed_tpu.runtime import autoscale as aus
        eng = slo_mod.SLOEngine(
            slo_mod.parse_slo_spec("paid:p95<0.001s"),
            fast_s=1e9, slow_s=1e9)
        for _ in range(10):
            eng.record("paid", 1.0, True)
        a = aus.FleetAutoscaler(
            registry=None,
            queue_depth_fn=lambda: 0,   # queue looks IDLE — burn alone
            spawner=lambda: "w_new",    # must trip the scale-up
            slo_burn_fn=lambda: eng.burn_rate("paid", "fast"),
            window=1, cooldown_s=0.0, min_workers=0, max_workers=3,
            up_queue=100.0, down_queue=-1.0)
        sig = a.fleet_signal()
        assert sig["slo_burn"] > 1.0
        a.sample_once(now=0.0)
        assert a.scale_ups == 1
        assert "SLO burn rate" in a.decisions[-1]["reason"]
