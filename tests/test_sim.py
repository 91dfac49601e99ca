"""Traffic twin (ISSUE 19): determinism, replay robustness,
calibration and the policy-sweep surface.

The simulator's value proposition is falsifiable three ways and each
gets a test class here: a (seed, scenario) pair must fully determine
the event log (byte-identical digests across runs), the capture-replay
adapter must survive torn segment tails without drifting the virtual
clock, and the committed scenario fixtures must keep reproducing the
measured records beside them (``*.measured.json``: loopback runs of
``tiny`` on the CPU, kept as the calibration target), so a policy change
that un-calibrates the twin fails fast.
"""

import copy
import json
import os

import pytest

from comfyui_distributed_tpu.sim import calibrate
from comfyui_distributed_tpu.sim import fleet
from comfyui_distributed_tpu.sim import replay as replay_mod
from comfyui_distributed_tpu.sim import scenario as sc_mod
from comfyui_distributed_tpu.sim import sweep as sweep_mod
from comfyui_distributed_tpu.sim.engine import Engine, VirtualClock
from comfyui_distributed_tpu.utils import constants as C

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCEN = os.path.join(ROOT, "benchmarks", "scenarios")


def _spec(**over):
    """A small but policy-dense scenario: 3 classes, chaos on the
    completion edge, an autoscaler, a mid-window worker kill and one
    fan-out job — every subsystem on, still <1s to run."""
    spec = {
        "name": "unit",
        "seed": 1234,
        "duration_s": 6.0,
        "traffic": [
            {"cls": "paid", "rate": 3.0, "clients": 2, "slo_s": 30.0},
            {"cls": "free", "rate": 2.0, "clients": 2},
            {"cls": "batch", "rate": 2.0, "clients": 1},
        ],
        "jobs": [{"t": 1.5, "cls": "paid", "units": 4, "slo_s": 30.0,
                  "service_s": 2.0}],
        "service": {"model": "lognormal", "mean_s": 0.3,
                    "sigma": 0.4, "min_s": 0.05},
        "workers": 2,
        "admission": {"max_queue": 32,
                      "shed": {"paid": 1.0, "free": 0.65,
                               "batch": 0.3},
                      "rate": 1000.0, "burst": 1000.0},
        "cluster": {"lease_s": 2.0, "suspect_probes": 2},
        "hedge": {"enabled": True, "min_wait_s": 1.0, "sweep_s": 0.5},
        "autoscale": {"min_workers": 2, "max_workers": 4,
                      "up_queue": 2.0, "down_queue": 0.5,
                      "window": 2, "cooldown_s": 1.0,
                      "interval_s": 0.25, "drain_s": 5.0},
        "chaos": {"drop_pct": 10, "delay_pct": 10, "delay_s": 0.05,
                  "seed": 5,
                  "routes": ["/distributed/job_complete"]},
        "faults": [{"t": 2.0, "kind": "kill_worker", "id": "w1"}],
        "drain_limit_s": 60.0,
    }
    spec.update(over)
    return spec


class TestVirtualClock:
    def test_sleep_is_banned(self):
        clk = VirtualClock()
        with pytest.raises(RuntimeError):
            clk.sleep(0.1)

    def test_engine_orders_ties_by_schedule_sequence(self):
        eng = Engine()
        seen = []
        eng.at(1.0, lambda: seen.append("a"))
        eng.at(1.0, lambda: seen.append("b"))
        eng.at(0.5, lambda: seen.append("c"))
        eng.run(until=2.0)
        assert seen == ["c", "a", "b"]
        assert eng.clock.now == pytest.approx(1.0)


class TestDeterminism:
    def test_same_seed_same_log_and_summary(self):
        s1 = fleet.run_scenario(sc_mod.from_dict(_spec()))
        s2 = fleet.run_scenario(sc_mod.from_dict(_spec()))
        assert s1["log_digest"] == s2["log_digest"]
        assert s1 == s2

    def test_different_seed_different_world(self):
        s1 = fleet.run_scenario(sc_mod.from_dict(_spec()))
        s2 = fleet.run_scenario(sc_mod.from_dict(_spec(seed=99)))
        assert s1["log_digest"] != s2["log_digest"]

    def test_env_seed_override(self, monkeypatch):
        monkeypatch.setenv(C.SIM_SEED_ENV, "99")
        s_env = fleet.run_scenario(sc_mod.from_dict(_spec()))
        monkeypatch.delenv(C.SIM_SEED_ENV)
        s99 = fleet.run_scenario(sc_mod.from_dict(_spec(seed=99)))
        assert s_env["log_digest"] == s99["log_digest"]

    def test_committed_fixtures_are_deterministic(self):
        for name in ("overload_r09.json", "multimaster_r14.json"):
            path = os.path.join(SCEN, name)
            s1 = fleet.run_scenario(sc_mod.load_scenario(path))
            s2 = fleet.run_scenario(sc_mod.load_scenario(path))
            assert s1["log_digest"] == s2["log_digest"], name

    def test_fleet_drains_and_books_balance(self):
        s = fleet.run_scenario(sc_mod.from_dict(_spec()))
        assert s["drained"]
        assert s["completed_total"] == s["admitted_total"]
        assert s["completion_rate"] == 1.0
        # the fan-out job rides outside the per-class books
        assert s["fanout"]["jobs"] == 1
        assert s["fanout"]["completed"] == 1
        per_cls_done = sum(v["completed"]
                          for v in s["per_class"].values())
        assert per_cls_done == s["completed_total"]


def _write_segment(dir_path, name, lines):
    os.makedirs(dir_path, exist_ok=True)
    with open(os.path.join(dir_path, name), "w",
              encoding="utf-8") as f:
        for line in lines:
            f.write(line + "\n")


def _rec(pid, fin, dur, tenant="paid", client="c1", worker_s=None):
    spans = [{"span_id": "root", "name": "job_e2e",
              "duration_s": dur,
              "attrs": {"tenant": tenant, "client_id": client}}]
    if worker_s is not None:
        spans.append({"span_id": "s2", "name": "denoise",
                      "duration_s": worker_s,
                      "attrs": {"worker": "w0"}})
    return json.dumps({"schema": 1, "prompt_id": pid,
                       "trace_id": f"t{pid}", "status": "done",
                       "root_span_id": "root", "duration_s": dur,
                       "finished_at": fin, "spans": spans})


class TestReplayAdapter:
    def test_arrivals_normalized_and_classed(self, tmp_path):
        d = str(tmp_path / "cap")
        _write_segment(d, "capture-000001.jsonl", [
            _rec("p1", fin=100.0, dur=2.0, tenant="free",
                 worker_s=0.5),
            _rec("p2", fin=99.0, dur=1.0, tenant="batch"),
        ])
        arrivals, stats = replay_mod.load_arrivals(d)
        assert stats == {"records": 2, "skipped_lines": 0,
                         "skipped_records": 0, "window_s": 0.0}
        # both arrive at t=98 -> normalized to 0; sorted & stable
        assert [a["t"] for a in arrivals] == [0.0, 0.0]
        assert {a["cls"] for a in arrivals} == {"free", "batch"}
        free = next(a for a in arrivals if a["cls"] == "free")
        assert free["service_s"] == pytest.approx(0.5)
        batch = next(a for a in arrivals if a["cls"] == "batch")
        assert "service_s" not in batch   # no worker span -> model

    def test_torn_lines_skipped_without_clock_drift(self, tmp_path):
        clean = str(tmp_path / "clean")
        torn = str(tmp_path / "torn")
        recs = [_rec("p1", 10.0, 1.0), _rec("p2", 12.0, 1.5),
                _rec("p3", 15.0, 2.0)]
        _write_segment(clean, "capture-000001.jsonl", recs)
        _write_segment(torn, "capture-000001.jsonl", [
            recs[0],
            recs[1][:37],                       # torn mid-record
            json.dumps({"schema": 999, "finished_at": 1.0,
                        "duration_s": 1.0}),    # future schema
            recs[1],
            json.dumps({"schema": 1, "spans": []}),  # no timestamps
            recs[2],
        ])
        a_clean, s_clean = replay_mod.load_arrivals(clean)
        a_torn, s_torn = replay_mod.load_arrivals(torn)
        assert a_torn == a_clean          # same origin, same spacing
        assert s_torn["records"] == 3
        assert s_torn["skipped_lines"] == 2
        assert s_torn["skipped_records"] == 1
        assert s_clean["skipped_lines"] == 0

    def test_replay_spec_runs_deterministically(self, tmp_path):
        d = str(tmp_path / "cap")
        _write_segment(d, "capture-000001.jsonl", [
            _rec(f"p{i}", fin=10.0 + 0.4 * i, dur=0.3,
                 tenant=("paid", "free")[i % 2], worker_s=0.1)
            for i in range(20)
        ])
        spec, stats = replay_mod.build_replay_spec(
            d, base=_spec(duration_s=0.0, jobs=[], faults=[]))
        assert stats["records"] == 20
        assert "traffic" not in spec
        s1 = fleet.run_scenario(sc_mod.from_dict(spec))
        s2 = fleet.run_scenario(sc_mod.from_dict(copy.deepcopy(spec)))
        assert s1["log_digest"] == s2["log_digest"]
        assert s1["drained"]
        assert s1["completed_total"] == 20

    def test_empty_capture_dir(self, tmp_path):
        arrivals, stats = replay_mod.load_arrivals(
            str(tmp_path / "nope"))
        assert arrivals == []
        assert stats["records"] == 0


class TestCalibration:
    """The calibration gate: the committed fixtures must keep
    reproducing the measured records.  A change to scheduler/cluster/
    autoscale policy code that breaks this is a real behavior change —
    recalibrate deliberately (see benchmarks/README) or fix the
    regression."""

    def _score(self, kind, scn, art):
        with open(os.path.join(SCEN, art)) as f:
            artifact = json.load(f)
        summary = fleet.run_scenario(
            sc_mod.load_scenario(os.path.join(SCEN, scn)))
        return calibrate.SCORERS[kind](summary, artifact)

    def test_overload_fixture_within_gate(self):
        score = self._score("overload", "overload_r09.json",
                            "overload_r09.measured.json")
        assert score["bars_failed"] == []
        assert score["mean_rel_err"] <= C.SIM_CALIBRATION_MAX_ERR

    def test_multimaster_fixture_within_gate(self):
        score = self._score("multimaster", "multimaster_r14.json",
                            "multimaster_r14.measured.json")
        assert score["bars_failed"] == []
        assert score["mean_rel_err"] <= C.SIM_CALIBRATION_MAX_ERR

    def test_combine_matches_committed_artifact(self):
        scores = {
            "overload": self._score("overload", "overload_r09.json",
                                    "overload_r09.measured.json"),
            "multimaster": self._score("multimaster",
                                       "multimaster_r14.json",
                                       "multimaster_r14.measured.json"),
        }
        comb = calibrate.combine(scores)
        assert comb["ok"]
        with open(os.path.join(SCEN, "sim_r19.calibration.json")) as f:
            committed = json.load(f)
        assert comb["calibration_error"] == committed["value"]

    def test_failed_bar_inflates_error(self):
        score = calibrate._score(
            [("x", 1.0, 1.0)], [("bar_a", False), ("bar_b", True)])
        assert score["bars_failed"] == ["bar_a"]
        assert score["calibration_error"] >= 1.0


class TestSweep:
    def test_shed_sweep_moves_batch_first(self):
        with open(os.path.join(SCEN, "overload_r09.json")) as f:
            base = json.load(f)
        results = sweep_mod.run_sweep(base, "admission.shed.batch",
                                      [0.1, 0.8])
        sheds = [r["summary"]["per_class"]["batch"]["shed_overload"]
                 for r in results]
        # a LOWER shed bar sheds batch earlier/harder — causal, same
        # seed everywhere
        assert sheds[0] > sheds[1]
        # the base spec must not bleed across runs
        assert base["admission"]["shed"]["batch"] == 0.3
        table = sweep_mod.format_table(results)
        assert "admission.shed.batch" in table
        assert "batch_shed" in table

    def test_parse_values(self):
        assert sweep_mod.parse_values("0.1,2,true,exp") == \
            [0.1, 2, True, "exp"]


class TestScaleSmoke:
    def test_midsize_fleet_drains_quickly(self):
        """A 100-worker diurnal slice: the same shape as the
        1000-worker day below, instant."""
        spec = {
            "name": "scale_smoke", "seed": 7, "duration_s": 120.0,
            "traffic": [
                {"cls": "paid", "rate": 8.0, "pattern": "diurnal",
                 "period_s": 120.0, "amplitude": 0.5, "clients": 16},
                {"cls": "batch", "rate": 4.0, "pattern": "burst",
                 "burst_at": 60.0, "burst_x": 2.0,
                 "burst_dur_s": 20.0, "clients": 8},
            ],
            "service": {"model": "lognormal", "mean_s": 6.0,
                        "sigma": 0.5, "min_s": 0.2},
            "workers": 100,
            "admission": {"max_queue": 512, "rate": 1000.0,
                          "burst": 1000.0},
            "cluster": {"lease_s": 10.0, "heartbeat_s": 3.0,
                        "sweep_s": 2.0},
            "hedge": {"enabled": True, "min_wait_s": 20.0,
                      "sweep_s": 10.0},
            "chaos": {},
            "faults": [{"t": 30.0, "kind": "kill_worker",
                        "id": "w5"}],
            "drain_limit_s": 120.0,
        }
        s = fleet.run_scenario(sc_mod.from_dict(spec))
        assert s["drained"]
        assert s["completion_rate"] == 1.0
        assert s["admitted_total"] > 1000
        assert s["counters"].get("worker_kills") == 1

    def test_thousand_worker_day_drains(self):
        """The committed 1000-worker diurnal day: at least 100k virtual
        prompts, every one completed, the fleet drained (about 20 s of
        pure Python on one core)."""
        s = fleet.run_scenario(sc_mod.load_scenario(
            os.path.join(SCEN, "diurnal_1k.json")))
        assert s["admitted_total"] >= 100_000
        assert s["drained"]
        assert s["completion_rate"] == 1.0


class TestCliSim:
    def test_run_and_sweep_and_replay(self, tmp_path, capsys):
        from comfyui_distributed_tpu import cli
        rc = cli.main(["sim", "run",
                       os.path.join(SCEN, "multimaster_r14.json"),
                       "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert out["drained"]
        assert out["takeover"]["successor"] == "m0"

        rc = cli.main(["sim", "sweep",
                       os.path.join(SCEN, "multimaster_r14.json"),
                       "--param", "traffic.0.rate",
                       "--values", "1.0,2.0"])
        assert rc == 0
        assert "completion" in capsys.readouterr().out

        d = str(tmp_path / "cap")
        _write_segment(d, "capture-000001.jsonl", [
            _rec("p1", 5.0, 0.5, worker_s=0.2),
            "not json at all",
        ])
        rc = cli.main(["sim", "replay", d, "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert out["replay"]["records"] == 1
        assert out["replay"]["skipped_lines"] == 1

    def test_replay_empty_dir_fails_loudly(self, tmp_path, capsys):
        from comfyui_distributed_tpu import cli
        rc = cli.main(["sim", "replay", str(tmp_path / "none")])
        assert rc == 1
        assert "no replayable records" in capsys.readouterr().err
