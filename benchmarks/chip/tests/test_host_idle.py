"""Tests of the nine readers PR 51 added (``lib/host_idle.py``: the owner
table of the idle time between programs, the collector's pauses, the
``POST /prompt`` handler), on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

``tests/test_chip_benchmark.py`` collects these under ``pytest tests/``.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

from lib import host_idle                       # noqa: E402
from lib.server import BenchFailure             # noqa: E402

OWNER_READERS = {"idle_exec_host_pct": "host", "idle_exec_wake_pct": "wake",
                 "idle_wait_device_pct": "wait_device",
                 "idle_wait_request_pct": "wait_request",
                 "idle_unowned_pct": "unowned"}
TRACE_READERS = ["idle_between_programs_pct"] + list(OWNER_READERS)
SPAN_READERS = ["gc_pause_ms_per_request", "gc_pause_max_ms",
                "http_prompt_ms_per_request"]
ELEVEN = ["sdxl_1024_sat", "sd15_512_sat", "sdxl_1024_fanout4",
          "ouro_expand_sd15_512_sat", "ouro_expand_sd15_512_sat4",
          "pangu_expand_sd15_512_sat4", "exaone_expand_sd15_512_sat4",
          "granite_expand_sd15_512_sat4", "keye_expand_sd15_512_sat4",
          "phi4flash_expand_sd15_512_sat4", "longcat_expand_sd15_512_sat4"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"host_idle_metric_{name}",
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def profile():
    """A summary as ``trace_summary.summarize`` writes it since PR 51: a
    3 s window, 0.15 s idle between programs."""
    by_class = {"host": 0.060, "wake": 0.015, "wait_device": 0.030,
                "wait_request": 0.036, "gc": 0.006, "unowned": 0.003}
    return {"chips": [{"chip": 0}], "names_found": True,
            "window_s": 3.0, "busy_s": 2.83, "gaps_in_programs_s": 0.02,
            "host_spans": ["device_wait"], "programs": {},
            "idle": {"none": 0.15}, "idle_under": {},
            "clock_drift_ns": 1200, "idle_between_s": sum(by_class.values()),
            "idle_by_executor": {"dispatch": 0.060, "wake_drain": 0.015,
                                 "device_wait": 0.030, "exec_idle": 0.036,
                                 "gc_pause": 0.006, "unowned": 0.003},
            "idle_by_executor_class": by_class,
            "top_idle_between": [{"s": 0.02, "n": 3, "owner": "dispatch",
                                  "before": "jit_lm_generate",
                                  "after": "jit__unknown"}]}


def stage(total, count=10, max_s=0.0):
    return {"count": count, "total_s": total, "max_s": max_s}


class FakeContext:
    def __init__(self, traced=True, prof=None, stages=None, completed=10):
        self.trace = {"window_s": 3.0} if traced else None
        self.metrics_window = {"pipeline": {"stages": stages or {}}}
        if prof is not None:
            self.metrics_window["profile"] = prof
        self._completed = [{}] * completed

    def completed(self):
        return self._completed

    def stage(self, name):
        return self.metrics_window["pipeline"]["stages"].get(name)


@pytest.fixture(autouse=True)
def say_again(monkeypatch):
    monkeypatch.setattr(host_idle, "_said", False)


def test_each_owner_reader_is_its_row_over_the_window_and_they_add_up(
        capsys):
    ctx = FakeContext(prof=profile())
    got = {name: reader(name)(ctx) for name in TRACE_READERS}
    assert got["idle_exec_host_pct"] == pytest.approx(2.0)
    assert got["idle_exec_wake_pct"] == pytest.approx(0.5)
    assert got["idle_wait_device_pct"] == pytest.approx(1.0)
    assert got["idle_wait_request_pct"] == pytest.approx(1.2)
    assert got["idle_unowned_pct"] == pytest.approx(0.1)
    assert got["idle_between_programs_pct"] == pytest.approx(5.0)
    # the five rows and the collector's add up to the whole
    assert sum(got[n] for n in OWNER_READERS) + 100 * 0.006 / 3.0 == \
        pytest.approx(got["idle_between_programs_pct"])
    # the identity is printed once a run, whichever reader comes first
    said = capsys.readouterr().out
    assert said.count("idle between programs:") == 1
    assert "= 0.150000 s against the summary's between-program idle " \
           "0.150000 s" in said
    assert "less gaps_in_programs 0.020000 = 0.150000" in said
    assert "clock_drift_ns 1200" in said
    assert "dispatch between jit_lm_generate and jit__unknown" in said


@pytest.mark.parametrize("name", TRACE_READERS)
def test_owner_readers_report_nothing_without_their_source(name):
    # the CPU rehearsal has no device trace
    assert reader(name)(FakeContext(traced=False, prof=profile())) is None
    # the parent commit under this benchmark: a summary without the rows
    parent = {k: v for k, v in profile().items()
              if not k.startswith("idle_b") and k != "top_idle_between"}
    assert reader(name)(FakeContext(prof=parent)) is None
    # a program from before the summary, a trace with no device plane
    assert reader(name)(FakeContext()) is None
    assert reader(name)(FakeContext(prof={**profile(), "chips": []})) is None
    # clock markers that disagree: an error in the summary, no rows
    drifted = {**parent, "timeline_error": "the clock markers disagree"}
    assert reader(name)(FakeContext(prof=drifted)) is None


@pytest.mark.parametrize("name", TRACE_READERS)
def test_a_row_of_zero_seconds_is_zero_and_not_nothing(name):
    prof = profile()
    prof["idle_by_executor_class"] = dict.fromkeys(
        prof["idle_by_executor_class"], 0.0)
    prof["idle_between_s"] = 0.0
    assert reader(name)(FakeContext(prof=prof)) == 0.0


def test_a_trace_without_names_fails_the_run():
    with pytest.raises(BenchFailure, match="no op_name path"):
        reader("idle_unowned_pct")(FakeContext(
            prof={**profile(), "names_found": False}))


def test_the_span_readers_take_the_windows_stages():
    stages = {"gc_pause": stage(0.042, 300, max_s=0.0213),
              "http_prompt": stage(0.125, 12)}
    for traced in (True, False):            # no device trace is needed
        ctx = FakeContext(traced=traced, stages=stages)
        assert reader("gc_pause_ms_per_request")(ctx) == pytest.approx(4.2)
        assert reader("gc_pause_max_ms")(ctx) == pytest.approx(21.3)
        assert reader("http_prompt_ms_per_request")(ctx) == \
            pytest.approx(12.5)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_report_nothing_from_a_program_without_the_stage(name):
    # the parent commit: no such stage
    assert reader(name)(FakeContext(stages={"dispatch": stage(1.0)})) is None
    if name != "gc_pause_max_ms":
        ctx = FakeContext(stages={"gc_pause": stage(1.0),
                                  "http_prompt": stage(1.0)}, completed=0)
        assert reader(name)(ctx) is None


def test_the_nine_entries_are_appended_with_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    tail = manifest["per_layer"][-9:]
    assert [m["name"] for m in tail] == [
        "idle_between_programs_pct", "idle_exec_host_pct",
        "idle_exec_wake_pct", "idle_wait_device_pct",
        "idle_wait_request_pct", "idle_unowned_pct",
        "gc_pause_ms_per_request", "gc_pause_max_ms",
        "http_prompt_ms_per_request"]
    idle = [m for m in manifest["per_layer"]
            if m["name"] == "device_idle_pct"][0]
    assert idle["workloads"] == ELEVEN
    reports = {e["name"]: e.get("workloads") for e in manifest["end_to_end"]}
    for m in tail:
        assert m["better"] == "lower"
        assert m["source"] == ("device_trace" if m["name"].startswith("idle_")
                               else "program_span")
        want = ["sd15_512_steady"] if m["name"] == "gc_pause_max_ms" \
            else ELEVEN
        assert m["workloads"] == want, m["name"]
        # a cell that reports the metric reports what it should move
        assert set(want) <= set(reports[m["moves"]]), m["name"]
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
    layers = {m["name"]: m["layer"] for m in tail}
    assert layers["idle_wait_request_pct"] == \
        layers["http_prompt_ms_per_request"] == "HTTP, admission, queue"
    assert set(layers.values()) == {"Dispatch", "HTTP, admission, queue"}


def test_the_rehearsal_reads_the_spans_and_none_of_the_owner_rows(tmp_path):
    """``--trace 1 --rehearse``: the profiler runs on the CPU (the program
    writes its timeline and its summary), the window's stages hold every
    hand-over the graph has, and no device metric is reported."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "sd15_512_sat", "--seed", str(2 ** 31 + 51), "--seconds", "4",
         "--trace", "1", "--rehearse", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert {"gc_pause_ms_per_request", "http_prompt_ms_per_request"} \
        <= set(out["metrics"])
    assert not set(TRACE_READERS) & set(out["metrics"])
    assert "gc_pause_max_ms" not in out["metrics"]      # the steady cell's
    with open(tmp_path / "run.json") as f:
        run = json.load(f)
    stages, counters = run["window_stages"], run["window_counters"]
    # every request posted is one handler, one pool task, one finalize
    posted = len(run["records"])
    assert stages["http_prompt"]["count"] == posted > 0
    assert stages["wake_pool"]["count"] >= run["requests_completed"]
    assert 0 < stages["wake_finalize"]["count"] <= posted
    assert 0 < stages["wake_queue"]["count"] <= posted
    # (folded from the callback's pauses by whichever thread comes by:
    # one may land between the two reads of a snapshot)
    assert stages["gc_pause"]["count"] > 0
    assert abs(stages["gc_pause"]["count"] - counters["gc.collections"]) <= 2
    assert "wake_drain" not in stages       # no language model in the graph
    assert "idle between programs" not in p.stdout
