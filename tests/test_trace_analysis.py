"""Critical-path analytics plane (ISSUE 20): blame decomposition on
hand-built span forests, the NTP-style clock-skew estimator, cross-trace
aggregation + the straggler scorecard, regression diffing with the
permutation significance test, the sim capture exporter round-tripped
through the REAL segment loader, and the server surfaces
(`/distributed/analysis`, extended metrics/reset, live anomaly plane).

CPU-only, tier-1-eligible: the analytics are pure stdlib; the one
ServerState e2e test follows test_capture_plane.py's socket idiom and
the sim round-trips run on the virtual clock (<1s each).
"""

import json
import random

import pytest

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.runtime import cluster
from comfyui_distributed_tpu.sim import fleet
from comfyui_distributed_tpu.sim import scenario as sc_mod
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils import trace as tr
from comfyui_distributed_tpu.utils import trace_analysis as ta
from comfyui_distributed_tpu.utils import trace_export as te
from tests.test_observability import (make_prompt, run_with_client,
                                      validate_prometheus,
                                      wait_remote_history, wait_trace)


@pytest.fixture(autouse=True)
def tiny_family(monkeypatch):
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny")
    yield


@pytest.fixture(autouse=True)
def analysis_disarmed(monkeypatch):
    """Each test opts into the live plane with its own baseline; the
    process-global LIVE singleton never leaks state across tests."""
    monkeypatch.delenv(C.ANALYSIS_BASELINE_ENV, raising=False)
    ta.reset_live()
    yield
    ta.reset_live()


@pytest.fixture(autouse=True)
def tracing_on():
    was = tr.tracing_enabled()
    tr.set_tracing(True)
    yield
    tr.set_tracing(was)


@pytest.fixture(autouse=True)
def export_off(monkeypatch):
    monkeypatch.delenv(C.TRACE_EXPORT_DIR_ENV, raising=False)
    yield
    te.current()


def span(name, start, end, sid, parent=None, attrs=None):
    """One raw span dict in the committed-record shape."""
    d = {"trace_id": "ab" * 16, "span_id": sid, "parent_id": parent,
         "name": name, "start_s": float(start), "end_s": float(end),
         "duration_s": round(float(end) - float(start), 6),
         "status": "ok"}
    if attrs:
        d["attrs"] = dict(attrs)
    return d


def record(spans, pid="p1", root_id="r"):
    return {"prompt_id": pid, "trace_id": "ab" * 16, "status": "ok",
            "root_span_id": root_id, "duration_s": 1.0,
            "finished_at": 1.0, "spans": spans}


class TestCriticalPath:
    def test_blame_cover_sums_exactly_to_e2e(self):
        # binary-exact boundaries so the reconstruction is EXACT, not
        # approximately-equal
        rec = record([
            span("job", 0.0, 8.0, "r", attrs={"tenant": "paid"}),
            span("queue_wait", 0.0, 2.0, "q", parent="r"),
            span("dispatch", 2.0, 3.0, "d", parent="r"),
            span("execute", 3.0, 6.0, "x", parent="r",
                 attrs={"worker": "w1"}),
            span("d2h", 6.0, 6.5, "h", parent="x"),
            span("finalize", 6.5, 7.5, "f", parent="r"),
        ])
        bd = ta.critical_path(rec)
        assert bd["e2e_s"] == 8.0
        assert bd["categories"] == {"queue_wait": 2.0, "dispatch": 1.0,
                                    "compute": 3.0, "d2h": 0.5,
                                    "blend": 1.0}
        assert bd["unattributed_s"] == 0.5          # 7.5..8.0 uncovered
        assert sum(bd["categories"].values()) + bd["unattributed_s"] \
            == bd["e2e_s"]
        assert bd["unattributed_pct"] == pytest.approx(6.25)
        assert bd["negative_edges"] == 0
        # the compute segment carries its worker
        seg = [s for s in bd["path"] if s["category"] == "compute"][0]
        assert seg["worker"] == "w1"

    def test_deepest_covering_span_wins(self):
        # d2h nested INSIDE execute claims its sub-interval: compute
        # must not double-count the child's time
        rec = record([
            span("job", 0.0, 10.0, "r"),
            span("execute", 1.0, 9.0, "x", parent="r"),
            span("d2h", 4.0, 5.0, "h", parent="x"),
        ])
        bd = ta.critical_path(rec)
        assert bd["categories"] == {"compute": 7.0, "d2h": 1.0}
        assert bd["unattributed_s"] == 2.0

    def test_fanout_overlapping_workers_no_double_count(self):
        # two tiles on two workers overlap; the cover blames each
        # instant ONCE (ties at equal depth: latest start wins)
        rec = record([
            span("job", 0.0, 8.0, "r"),
            span("execute", 2.0, 5.0, "a", parent="r",
                 attrs={"worker": "w1"}),
            span("execute", 3.0, 7.0, "b", parent="r",
                 attrs={"worker": "w2"}),
        ])
        bd = ta.critical_path(rec)
        assert bd["categories"] == {"compute": 5.0}     # 2..7, not 7s
        workers = [s.get("worker") for s in bd["path"]
                   if s["category"] == "compute"]
        assert workers == ["w1", "w2"]

    def test_cb_park_resume_timeline(self):
        # a preempted row: compute, park, compute again — park time is
        # its own category, never blamed on compute
        rec = record([
            span("job", 0.0, 9.0, "r"),
            span("queue_wait", 0.0, 1.0, "q", parent="r"),
            span("execute", 1.0, 3.0, "x1", parent="r"),
            span("cb_park", 3.0, 6.0, "pk", parent="r"),
            span("execute", 6.0, 9.0, "x2", parent="r"),
        ])
        bd = ta.critical_path(rec)
        assert bd["categories"] == {"queue_wait": 1.0, "compute": 5.0,
                                    "park": 3.0}
        assert bd["unattributed_s"] == 0.0

    def test_missing_spans_surface_as_gap_not_inflation(self):
        rec = record([
            span("job", 0.0, 10.0, "r"),
            span("queue_wait", 0.0, 1.0, "q", parent="r"),
        ])
        bd = ta.critical_path(rec)
        assert bd["categories"] == {"queue_wait": 1.0}
        assert bd["unattributed_s"] == 9.0
        assert bd["unattributed_pct"] == 90.0
        gap_segs = [s for s in bd["path"]
                    if s["category"] == "unattributed"]
        assert len(gap_segs) == 1 and gap_segs[0]["dur_s"] == 9.0

    def test_unknown_span_name_degrades_to_other(self):
        rec = record([
            span("job", 0.0, 4.0, "r"),
            span("brand_new_stage", 1.0, 3.0, "n", parent="r"),
        ])
        bd = ta.critical_path(rec)
        assert bd["categories"] == {"other": 2.0}

    def test_negative_parent_child_edge_counted(self):
        # a worker span starting before its master-side parent is the
        # clock-skew signature the corrected ingest must eliminate
        rec = record([
            span("job", 0.0, 5.0, "r"),
            span("dispatch", 2.0, 3.0, "d", parent="r"),
            span("execute", 1.5, 2.8, "x", parent="d"),
        ])
        assert ta.critical_path(rec)["negative_edges"] == 1

    def test_empty_and_rootless_records(self):
        bd = ta.critical_path({"prompt_id": "e", "spans": []})
        assert bd["e2e_s"] == 0.0 and bd["path"] == []
        # no root_span_id: the longest parentless span is the root
        rec = record([
            span("queue_wait", 0.0, 1.0, "q"),
            span("job", 0.0, 6.0, "j"),
        ], root_id=None)
        bd = ta.critical_path(rec)
        assert bd["e2e_s"] == 6.0
        assert bd["categories"] == {"queue_wait": 1.0}


class TestSkewEstimator:
    def test_min_filter_converges_and_error_is_bounded(self):
        reg = cluster.ClusterRegistry(lease_s=30.0)
        reg.register("w1", {})
        rng = random.Random(7)
        true_offset = -3.2          # worker clock 3.2s AHEAD of master
        errors = []
        delays = []
        for _ in range(C.SKEW_SAMPLES_KEPT):
            d = rng.uniform(0.005, 0.25)    # non-negative uplink delay
            delays.append(d)
            reg.update_skew("w1", true_offset + d)
            errors.append(abs(reg.skew("w1") - true_offset))
        # the estimate only improves as samples arrive, never overshoots
        # below the true offset, and lands exactly on the least-delayed
        # sample seen
        assert errors == sorted(errors, reverse=True)
        assert reg.skew("w1") == pytest.approx(true_offset + min(delays))
        assert errors[-1] <= 0.25

    def test_window_slides_past_stale_minimum(self):
        reg = cluster.ClusterRegistry(lease_s=30.0)
        reg.register("w1", {})
        reg.update_skew("w1", 1.001)        # one near-perfect sample
        for _ in range(C.SKEW_SAMPLES_KEPT):
            reg.update_skew("w1", 1.5)      # then only congested ones
        # the deque forgot the old minimum: the estimate tracks the
        # CURRENT network, it does not pin to an ancient best
        assert reg.skew("w1") == pytest.approx(1.5)

    def test_unknown_worker_and_garbage_samples(self):
        reg = cluster.ClusterRegistry(lease_s=30.0)
        assert reg.skew("ghost") == 0.0
        reg.update_skew("ghost", 5.0)       # unknown id: dropped
        assert reg.skew_snapshot() == {}
        reg.register("w1", {})
        reg.update_skew("w1", "not-a-number")
        assert reg.skew("w1") == 0.0

    def test_snapshot_and_reset(self):
        reg = cluster.ClusterRegistry(lease_s=30.0)
        reg.register("w1", {})
        reg.register("w2", {})
        reg.update_skew("w1", 0.25)
        reg.update_skew("w1", 0.125)
        snap = reg.skew_snapshot()
        assert set(snap) == {"w1"}          # w2 has no estimate
        assert snap["w1"]["offset_s"] == 0.125
        assert snap["w1"]["samples"] == 2
        assert snap["w1"]["age_s"] is not None
        assert reg.reset_skew() == 1
        assert reg.skew("w1") == 0.0 and reg.skew_snapshot() == {}


def _tenant_rec(pid, tenant, compute_s, worker, bucket=None):
    spans = [
        span("job", 0.0, compute_s + 1.0, "r",
             attrs={"tenant": tenant}),
        span("queue_wait", 0.0, 1.0, "q", parent="r"),
        span("execute", 1.0, 1.0 + compute_s, "x", parent="r",
             attrs={"worker": worker}),
    ]
    if bucket:
        spans[2]["attrs"]["bucket"] = bucket
    return record(spans, pid=pid)


class TestAggregation:
    def test_group_bys_tenant_worker_signature(self):
        recs = [
            _tenant_rec("a1", "paid", 2.0, "w1", bucket="cafe0001"),
            _tenant_rec("a2", "paid", 4.0, "w1", bucket="cafe0001"),
            _tenant_rec("a3", "free", 1.0, "w2"),
        ]
        bds = ta.collect_breakdowns(recs)
        by_tenant = ta.aggregate(bds, group_by="tenant")
        assert set(by_tenant) == {"paid", "free"}
        paid = by_tenant["paid"]
        assert paid["n"] == 2
        assert paid["e2e_mean_s"] == pytest.approx(4.0)   # (3+5)/2
        assert paid["categories"]["compute"]["mean_s"] \
            == pytest.approx(3.0)
        assert paid["categories"]["compute"]["share_pct"] \
            == pytest.approx(75.0)
        assert paid["unattributed_pct"] == 0.0
        by_worker = ta.aggregate(bds, group_by="worker")
        assert set(by_worker) == {"w1", "w2"}
        by_sig = ta.aggregate(bds, group_by="signature")
        assert set(by_sig) == {"cafe0001", "unknown"}
        assert by_sig["cafe0001"]["n"] == 2

    def test_collect_breakdowns_limit_and_zero_e2e_skip(self):
        recs = [_tenant_rec(f"p{i}", "paid", 1.0, "w1")
                for i in range(10)]
        recs.insert(0, record([span("job", 1.0, 1.0, "r")], pid="z"))
        bds = ta.collect_breakdowns(recs, limit=3)
        assert [bd["prompt_id"] for bd in bds] == ["p0", "p1", "p2"]

    def test_straggler_scorecard_flags_slow_worker(self):
        recs = []
        for i in range(8):
            recs.append(_tenant_rec(f"f{i}", "paid", 0.5,
                                    f"w{i % 2}"))        # healthy pair
        for i in range(8):
            recs.append(_tenant_rec(f"s{i}", "paid", 2.5, "w_slow"))
        sc = ta.straggler_scorecard(ta.collect_breakdowns(recs))
        assert sc["fleet_median_p95_s"] == pytest.approx(0.5)
        cards = sc["workers"]
        assert cards["w_slow"]["straggler"] is True
        assert cards["w_slow"]["vs_fleet_median_x"] \
            == pytest.approx(5.0)
        assert not cards["w0"]["straggler"]
        assert not cards["w1"]["straggler"]


def _fake_bd(v):
    return {"e2e_s": v, "categories": {"compute": v},
            "unattributed_s": 0.0, "unattributed_pct": 0.0,
            "negative_edges": 0}


class TestRegressionDiff:
    A = [_fake_bd(0.2 + 0.002 * (i % 5)) for i in range(40)]

    def test_seeded_regression_flagged_null_clean(self):
        reg = [_fake_bd(0.26 + 0.002 * (i % 5)) for i in range(40)]
        null = [_fake_bd(0.2 + 0.002 * ((i + 3) % 5))
                for i in range(40)]
        d = ta.diff_breakdowns(self.A, reg, seed=0)
        assert "compute" in d["flagged"] and d["regressed"]
        row = d["categories"]["compute"]
        assert row["delta_pct"] == pytest.approx(29.7, abs=0.5)
        assert row["p_value"] < 0.05 and row["flagged"]
        dn = ta.diff_breakdowns(self.A, null, seed=0)
        assert not dn["regressed"] and dn["flagged"] == []

    def test_significant_but_small_delta_not_flagged(self):
        # +5% with tiny spread: p ~ 0 yet below the 10% materiality bar
        b = [_fake_bd(0.21 + 0.002 * (i % 5)) for i in range(40)]
        d = ta.diff_breakdowns(self.A, b, seed=0)
        row = d["categories"]["compute"]
        assert row["significant"] and not row["flagged"]
        assert not d["regressed"]

    def test_diff_is_deterministic_under_seed(self):
        b = [_fake_bd(0.23 + 0.002 * (i % 5)) for i in range(40)]
        d1 = ta.diff_breakdowns(self.A, b, seed=42)
        d2 = ta.diff_breakdowns(self.A, b, seed=42)
        assert d1 == d2


class TestBaselineAndLivePlane:
    def test_profile_save_load_roundtrip(self, tmp_path):
        bds = [_fake_bd(0.25), _fake_bd(0.75)]
        prof = ta.profile_from_breakdowns(bds)
        assert prof["n"] == 2 and prof["e2e_mean_s"] == 0.5
        assert prof["categories"] == {"compute": 0.5}
        path = str(tmp_path / "base.json")
        ta.save_baseline(prof, path)
        loaded = ta.load_baseline(path)
        assert loaded["kind"] == "dtpu_analysis_baseline"
        assert loaded["categories"] == {"compute": 0.5}

    def test_unreadable_baselines_rejected(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text("not json")
        assert ta.load_baseline(str(bad)) is None
        empty = tmp_path / "empty.json"
        empty.write_text(json.dumps({"n": 3, "categories": {}}))
        assert ta.load_baseline(str(empty)) is None
        assert ta.load_baseline(str(tmp_path / "missing.json")) is None

    def test_detect_anomalies_thresholds(self):
        baseline = {"e2e_mean_s": 0.2,
                    "categories": {"compute": 0.1}}
        # +100% compute: anomalous at the default 50% bar
        out = ta.detect_anomalies(_fake_bd(0.2), baseline)
        assert [a["category"] for a in out] == ["compute"]
        assert out[0]["change_pct"] == pytest.approx(100.0)
        # +20%: clean
        assert ta.detect_anomalies(_fake_bd(0.12), baseline) == []
        # a category the baseline never saw flags against e2e headroom
        bd = {"e2e_s": 0.3, "categories": {"compute": 0.1,
                                           "upload": 0.15}}
        out = ta.detect_anomalies(bd, baseline)
        assert [a["category"] for a in out] == ["upload"]
        assert out[0]["change_pct"] is None

    def _commit_job(self, pid, compute_s=0.5):
        # explicit past intervals via event_span: the blame cover must
        # see the exact compute duration, not a wall-clock-clipped one
        import hashlib
        import time
        tid = hashlib.md5(pid.encode()).hexdigest()
        t0 = time.time() - 60.0
        root = tr.event_span("job", t0, t0 + 0.1 + compute_s,
                             trace_id=tid,
                             attrs={"prompt_id": pid, "tenant": "paid"})
        tr.event_span("queue_wait", t0, t0 + 0.1, trace_id=tid,
                      parent_id=root["span_id"])
        tr.event_span("execute", t0 + 0.1, t0 + 0.1 + compute_s,
                      trace_id=tid, parent_id=root["span_id"],
                      attrs={"worker": "w1"})
        tr.GLOBAL_TRACES.commit(pid, tid, status="ok",
                                root_span_id=root["span_id"],
                                duration_s=compute_s + 0.1)

    def test_commit_tap_scores_against_armed_baseline(
            self, tmp_path, monkeypatch):
        path = str(tmp_path / "base.json")
        ta.save_baseline({"n": 4, "e2e_mean_s": 0.61,
                          "categories": {"compute": 0.5,
                                         "queue_wait": 0.1}}, path)
        monkeypatch.setenv(C.ANALYSIS_BASELINE_ENV, path)
        assert ta.LIVE.armed()
        self._commit_job("ok1", compute_s=0.5)      # on-profile: clean
        self._commit_job("bad1", compute_s=1.5)     # 3x compute
        snap = ta.LIVE.snapshot()
        assert snap["armed"] and snap["baseline"] == path
        assert snap["traces_analyzed"] == 2
        assert snap["anomalies_total"] == 1
        assert snap["anomalies_by_category"] == {"compute": 1}
        assert snap["last_anomalies"][0]["category"] == "compute"
        assert snap["live_profile"]["categories"]["compute"] \
            == pytest.approx(1.0)
        ta.reset_live()
        assert ta.LIVE.snapshot()["traces_analyzed"] == 0

    def test_disarmed_commit_tap_is_noop(self):
        assert not ta.LIVE.armed()
        self._commit_job("quiet1")
        snap = ta.LIVE.snapshot()
        assert snap["traces_analyzed"] == 0
        assert snap["anomalies_total"] == 0


def _cap_spec(name, seed, mean_s, cap_dir):
    """A tiny fixed-service scenario: ~45 completions in <1s of wall
    time, jitter far inside the differ's 10% materiality bar."""
    return {
        "name": name, "seed": seed, "duration_s": 15.0,
        "traffic": [{"cls": "paid", "rate": 3.0, "clients": 2}],
        "service": {"model": "fixed", "mean_s": mean_s,
                    "jitter_pct": 5.0},
        "workers": 4, "drain_limit_s": 60.0,
        "capture_dir": cap_dir,
    }


class TestSimCaptureRoundTrip:
    def test_exporter_roundtrip_through_real_loader(self, tmp_path):
        cap = str(tmp_path / "cap")
        s = fleet.run_scenario(sc_mod.from_dict(
            _cap_spec("rt", 11, 0.2, cap)))
        assert s["capture"]["exported"] == s["completed_total"] > 20
        assert s["capture"]["dropped"] == 0
        stats: dict = {}
        recs = list(te.iter_records(cap, stats=stats))
        assert stats["records"] == s["capture"]["exported"]
        assert stats["torn_lines"] == 0 and stats["io_errors"] == 0
        assert all(r["schema"] == te.SCHEMA_VERSION for r in recs)
        report = ta.analyze_records(recs)
        assert report["n_traces"] == len(recs)
        assert report["unattributed_pct_mean"] == 0.0
        assert report["negative_edges"] == 0
        assert set(report["profiles"]["tenant"]) == {"paid"}
        prof = report["profiles"]["tenant"]["paid"]
        assert prof["categories"]["compute"]["mean_s"] \
            == pytest.approx(0.2, rel=0.1)

    def test_capture_ids_are_deterministic(self, tmp_path):
        d1, d2 = str(tmp_path / "c1"), str(tmp_path / "c2")
        fleet.run_scenario(sc_mod.from_dict(_cap_spec("det", 5, 0.1,
                                                      d1)))
        fleet.run_scenario(sc_mod.from_dict(_cap_spec("det", 5, 0.1,
                                                      d2)))
        ids1 = sorted((r["prompt_id"], r["trace_id"])
                      for r in te.iter_records(d1))
        ids2 = sorted((r["prompt_id"], r["trace_id"])
                      for r in te.iter_records(d2))
        assert ids1 == ids2 and ids1

    def test_cli_why_and_analyze_offline(self, tmp_path, capsys):
        cap = str(tmp_path / "cap")
        fleet.run_scenario(sc_mod.from_dict(
            _cap_spec("cli", 11, 0.2, cap)))
        pid = next(te.iter_records(cap))["prompt_id"]
        from comfyui_distributed_tpu import cli
        assert cli.main(["why", pid, "--export-dir", cap]) == 0
        out = capsys.readouterr().out
        assert "critical path:" in out and "compute" in out
        assert "(unattributed)" in out
        assert cli.main(["analyze", "--export-dir", cap]) == 0
        out = capsys.readouterr().out
        assert "by tenant:" in out and "paid" in out
        assert "straggler scorecard" in out
        assert cli.main(["why", "ghost", "--export-dir", cap]) == 1

    def test_cli_diff_exit_codes_seeded_vs_null(self, tmp_path,
                                                capsys):
        a, b, c = (str(tmp_path / x) for x in "abc")
        fleet.run_scenario(sc_mod.from_dict(_cap_spec("a", 11, 0.2, a)))
        fleet.run_scenario(sc_mod.from_dict(_cap_spec("b", 12, 0.26,
                                                      b)))
        fleet.run_scenario(sc_mod.from_dict(_cap_spec("c", 13, 0.2, c)))
        from comfyui_distributed_tpu import cli
        assert cli.main(["analyze", "--diff", a, b]) == 3
        out = capsys.readouterr().out
        assert "REGRESSED in" in out and "compute" in out
        assert cli.main(["analyze", "--diff", a, c]) == 0
        assert "verdict: clean" in capsys.readouterr().out

    def test_cli_baseline_out_from_capture(self, tmp_path, capsys):
        cap = str(tmp_path / "cap")
        fleet.run_scenario(sc_mod.from_dict(
            _cap_spec("base", 11, 0.2, cap)))
        out_path = str(tmp_path / "baseline.json")
        from comfyui_distributed_tpu import cli
        assert cli.main(["analyze", "--export-dir", cap,
                         "--baseline-out", out_path]) == 0
        capsys.readouterr()
        prof = ta.load_baseline(out_path)
        assert prof is not None and prof["n"] > 20
        assert prof["categories"]["compute"] > 0


class TestServerSurfaces:
    def test_analysis_route_metrics_and_reset(self, tmp_path, monkeypatch,
                                              assert_nothing_compiled):
        # a deliberately-stale baseline: any real prompt's compute
        # blows past it, so the live plane must flag anomalies
        path = str(tmp_path / "base.json")
        ta.save_baseline({"n": 1, "e2e_mean_s": 1e-6,
                          "categories": {"compute": 1e-9}}, path)
        monkeypatch.setenv(C.ANALYSIS_BASELINE_ENV, path)

        async def body(client, state):
            r = await client.post("/prompt", json={
                "prompt": make_prompt(21), "client_id": "an"})
            pid = (await r.json())["prompt_id"]
            await wait_remote_history(client, pid)

            # the analysis route: profiles + scorecard + armed plane
            rep = await (await client.get(
                "/distributed/analysis")).json()
            assert rep["n_traces"] >= 1
            assert set(rep["profiles"]) \
                == {"tenant", "signature", "worker"}
            assert rep["unattributed_pct_mean"] < 100.0
            assert rep["negative_edges"] == 0
            assert rep["live"]["armed"] is True
            assert rep["live"]["traces_analyzed"] >= 1
            assert rep["live"]["anomalies_total"] >= 1
            assert isinstance(rep["skew"], dict)
            assert "hedging_latency_ema_s" in rep

            # JSON metrics block mirrors the live snapshot
            m = await (await client.get("/distributed/metrics")).json()
            assert m["analysis"]["armed"] is True
            assert m["analysis"]["anomalies_total"] >= 1
            assert "skew" in m["analysis"]

            # prom: the counter family is always present and valid
            text = await (await client.get(
                "/distributed/metrics.prom")).text()
            types = validate_prometheus(text)
            assert types.get("dtpu_analysis_anomalies_total") \
                == "counter"
            val = [l for l in text.splitlines()
                   if l.startswith("dtpu_analysis_anomalies_total ")]
            assert val and float(val[0].split()[-1]) >= 1

            # a served job's blame cover reconstructs its e2e exactly
            await wait_trace(client, pid)
            bd = ta.critical_path(tr.GLOBAL_TRACES.get(pid))
            assert bd["e2e_s"] > 0
            assert abs(sum(bd["categories"].values())
                       + bd["unattributed_s"] - bd["e2e_s"]) \
                <= 1e-3 * bd["e2e_s"]

            # the armed plane never touches compiled code: a warm
            # request compiles nothing
            mark = tr.GLOBAL_RETRACES.mark()
            r = await client.post("/prompt", json={
                "prompt": make_prompt(22), "client_id": "an"})
            await wait_remote_history(client, (await r.json())["prompt_id"])
            assert_nothing_compiled(tr.GLOBAL_RETRACES.since(mark))

            # total reset clears the analytics plane too
            r = await client.post("/distributed/metrics/reset", json={})
            cleared = (await r.json())["cleared"]
            assert cleared["analysis"] is True
            assert isinstance(cleared["skew_estimates"], int)
            m = await (await client.get("/distributed/metrics")).json()
            assert m["analysis"]["traces_analyzed"] == 0
            assert m["analysis"]["anomalies_total"] == 0

        run_with_client(body, tmp_path)
