"""Tests of the chip benchmark's own code, on the CPU.

Kept beside the benchmark (``benchmarks/chip`` is the one directory a
benchmark PR may write to); run them with

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

No topology call and no JAX import happens while this file is imported.
"""

import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

from lib import flops, stats, xplane      # noqa: E402
from lib.traffic import Traffic           # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def run_cell(root, *argv, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "chip", "run.py"),
         *argv], cwd=root, env=e, capture_output=True, text=True,
        timeout=600)


# --- the manifest ----------------------------------------------------------

def test_manifest_names_units_and_files():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    configs = {c["name"]: c for c in m["configs"]}
    cells = {w["name"]: w for w in m["workloads"]}
    for c in configs.values():
        assert NAME.match(c["name"])
        assert c["file"].startswith(m["paths"][0] + "/")
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            assert json.load(f)["name"] == c["name"]
    pairs = set()
    for w in cells.values():
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(cells)
    assert sum(w["chips"] == 4 for w in cells.values()) \
        <= max(len(cells) // 4, 1)
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for group, folder in (("end_to_end", "end_to_end"),
                          ("per_layer", "layer_metrics")):
        for x in m[group]:
            assert NAME.match(x["name"]) and UNIT.match(x["unit"]), x
            assert x["better"] in ("lower", "higher")
            assert set(x.get("workloads", [])) <= set(cells)
            assert os.path.isfile(os.path.join(
                BENCH, folder, x["name"] + ".py")), x["name"]
    for x in m["end_to_end"]:
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        # a per-layer metric is reported only where the metric it moves is
        moved = e2e[x["moves"]]
        assert set(x.get("workloads", cells)) \
            <= set(moved.get("workloads", cells)), x["name"]
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for group, want in keys.items():
        for x in m[group]:
            assert set(x) - {"workloads"} == want, x
    for base, _, files in os.walk(BENCH):
        if "__pycache__" not in base:
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel
    for name in cells:
        reported = [x for x in m["end_to_end"]
                    if name in x.get("workloads", cells)]
        assert len(reported) >= 2, name
        assert any(name in x.get("workloads", cells)
                   for x in m["per_layer"]), name


def test_run_py_names_no_cell_config_mix_or_metric():
    m = manifest()
    with open(os.path.join(BENCH, "run.py"), encoding="utf-8") as f:
        src = f.read()
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[g]]
    names += [w["traffic"] for w in m["workloads"]]
    # as a string: nothing is looked up or branched on by such a name
    assert [n for n in names if f'"{n}"' in src or f"'{n}'" in src] == []


# --- arithmetic kept with the benchmark ------------------------------------

def test_percentiles_and_spread():
    xs = [4.0, 1.0, 3.0, 2.0, 5.0]
    assert stats.median(xs) == 3.0
    assert stats.percentile(xs, 90) == pytest.approx(4.6)
    assert stats.spread(xs) == pytest.approx((4.0 - 2.0) / 3.0)


def test_flops_from_shapes_against_hand_counts():
    # a two-level UNet small enough to count by hand: 8x8 latent,
    # 4 -> 32 channels, mult (1, 2), one res block, depth (1, 1), ctx 16x5
    unet = {"in_channels": 4, "out_channels": 4, "model_channels": 32,
            "channel_mult": [1, 2], "num_res_blocks": 1,
            "transformer_depth": [1, 1], "context_dim": 16}
    conv = lambda h, cin, cout, k=3: 2 * h * h * cin * cout * k * k  # noqa: E731
    res = lambda h, cin, cout: (conv(h, cin, cout) + conv(h, cout, cout)  # noqa: E731
                                + 2 * 128 * cout
                                + (conv(h, cin, cout, 1) if cin != cout
                                   else 0))

    def tr(h, c):
        n = h * h
        self_attn = 8 * n * c * c + 4 * n * n * c
        cross = 4 * n * c * c + 4 * 5 * 16 * c + 4 * n * 5 * c
        ff = 16 * n * c * c + 8 * n * c * c
        return self_attn + cross + ff + 4 * n * c * c
    want = (conv(8, 4, 32)
            + res(8, 32, 32) + tr(8, 32) + conv(4, 32, 32)      # down 0
            + res(4, 32, 64) + tr(4, 64)                        # down 1
            + 2 * res(4, 64, 64) + tr(4, 64)                    # middle
            + res(4, 128, 64) + tr(4, 64)                       # up 1
            + res(4, 96, 64) + tr(4, 64) + conv(8, 64, 64)
            + res(8, 96, 32) + tr(8, 32)                        # up 0
            + res(8, 64, 32) + tr(8, 32)
            + conv(8, 32, 4))
    assert flops.unet_forward_flops(unet, 8, 8, ctx_len=5) == want


@pytest.mark.parametrize("name, latent, tflop", [
    # 2 x the multiply-accumulates usually quoted for these UNets
    # (SD1.5 ~0.40 T at 64x64, SDXL ~3.4 T at 128x128 with 77 tokens)
    ("sd15-512", 64, 0.803), ("sdxl-base-1024", 128, 6.761)])
def test_flops_of_the_two_configurations(name, latent, tflop):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)
    fwd = flops.unet_forward_flops(config["unet"], latent, latent)
    assert fwd / 1e12 == pytest.approx(tflop, abs=0.001)
    assert flops.denoise_flops_per_image(config) == fwd * 2 * 20


def test_traffic_is_a_function_of_the_seed():
    with open(os.path.join(BENCH, "traffic", "poisson_unique_r80.json")) as f:
        mix = json.load(f)
    a = Traffic(mix, "sd15-512", 7).schedule(51.0)
    b = Traffic(mix, "sd15-512", 7).schedule(51.0)
    c = Traffic(mix, "sd15-512", 8).schedule(51.0)
    assert a == b and len(a) == 68       # the count the mix's why states
    # the arrival instants are the mix's, texts and seeds the run's
    assert [r["due"] for r in a] == [r["due"] for r in c]
    assert [r["text"] for r in a] != [r["text"] for r in c]
    assert len({r["text"] for r in a}) == len(a)
    with pytest.raises(ValueError, match="no rate for configuration"):
        Traffic(mix, "some-other-config", 7)


def load_run_py():
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_slice_follows_request_length_and_chips():
    run = load_run_py()
    # the four cells as measured (warm request seconds, chips): two SDXL
    # requests; 3 s for SD1.5; 8 chip-seconds over four chips
    assert run.slice_seconds(3.58, 1, 51.0) == pytest.approx(7.16)
    assert run.slice_seconds(0.70, 1, 51.0) == 3.0
    assert run.slice_seconds(3.38, 4, 51.0) == 2.0
    assert run.slice_seconds(0.70, 1, 3.0) == pytest.approx(1.8)


class FakeContext:
    """What the two host-side readers below take from a Context."""

    def __init__(self, records=(), stages=None, mix=None, seconds=51.0,
                 images_per_request=1):
        self.records = [{"due": d, "done": e} for d, e in records]
        self.stages = stages or {}
        self.mix = mix or {}
        self.seconds = seconds
        self.images_per_request = images_per_request

    def completed(self):
        return self.records

    def latencies(self):
        return [r["done"] - r["due"] for r in self.records]

    def stage(self, name):
        return self.stages.get(name)


def test_images_per_s_is_the_median_cycle_and_a_pause_does_not_move_it():
    read = load_run_py().load_reader("end_to_end", "images_per_s")
    # two callers, four images a request, a completion every 0.5 s: a
    # caller's cycle is 1.0 s, so 2 x 4 images a second
    done = [0.5 * k for k in range(1, 21)]
    mix = {"clients": 2}
    ctx = FakeContext([(0.0, d) for d in done], mix=mix, seconds=9.2,
                      images_per_request=4)
    assert read(ctx) == pytest.approx(8.0)
    # one pause of 0.3 s after the sixth completion: 18 completions in
    # 9.2 s became 17 (the count over the window loses 6%), two of the
    # cycles are 1.3 s, and the median cycle is what it was
    paused = [d + (0.3 if d > 3.0 else 0.0) for d in done]
    ctx = FakeContext([(0.0, d) for d in paused], mix=mix, seconds=9.2,
                      images_per_request=4)
    assert len([d for d in paused if d <= 9.2]) == 17
    assert read(ctx) == pytest.approx(8.0)
    # an open loop has no callers: its step is one completion
    ctx = FakeContext([(0.0, d) for d in done], seconds=9.2)
    assert read(ctx) == pytest.approx(2.0)
    # fewer completions inside the window than one cycle spans: no reading
    assert read(FakeContext([(0.0, 0.5), (0.0, 1.0)], mix=mix)) is None


def test_tail_is_the_mean_beyond_the_mix_percentile():
    read = load_run_py().load_reader("end_to_end", "tti_tail_s")
    # ten latencies 1..10 s: beyond p80 lie the slowest two, 9 and 10
    ctx = FakeContext([(0.0, float(k)) for k in range(1, 11)],
                      mix={"tail_percentile": 80})
    assert read(ctx) == pytest.approx(9.5)
    # 68 requests: 13 beyond p80, as the mix file says
    ctx = FakeContext([(0.0, float(k)) for k in range(1, 69)],
                      mix={"tail_percentile": 80})
    assert read(ctx) == pytest.approx(sum(range(56, 69)) / 13)
    # never no sample: one request is its own tail
    assert read(FakeContext([(0.0, 2.0)], mix={"tail_percentile": 95})) == 2.0
    assert read(FakeContext(mix={"tail_percentile": 80})) is None


def test_queue_delay_is_mean_latency_less_the_unloaded_latency():
    read = load_run_py().load_reader("layer_metrics", "queue_delay_mean_ms")
    # service 1 s; the first and the fourth request find the server empty
    ctx = FakeContext([(0.0, 1.0), (0.5, 2.0), (1.0, 3.0), (4.0, 5.1)])
    # latencies 1.0, 1.5, 2.0, 1.1: mean 1.4; alone 1.0 and 1.1: median 1.05
    assert read(ctx) == pytest.approx(350.0)
    assert read(FakeContext()) is None


def test_finalize_host_leaves_out_every_span_that_waits_for_the_device():
    read = load_run_py().load_reader("layer_metrics",
                                     "finalize_host_ms_per_request")
    st = lambda total, n=10: {"count": n, "total_s": total}  # noqa: E731
    ctx = FakeContext(stages={"job_e2e": st(12.0), "queue_wait": st(0.5),
                              "compute": st(5.0), "d2h": st(6.0),
                              "encode": st(0.4)})
    assert read(ctx) == pytest.approx(50.0)
    del ctx.stages["d2h"]
    assert read(ctx) is None


# --- the trace reduction, on a recorded slice ------------------------------

def test_reducer_nesting_edges_and_gaps_on_a_made_up_trace():
    k = 1000
    ev = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops",
             "names": ["%pad.0 = f32[8]{0:T(128)} pad(f32[4]{0} %p)",
                       "while.1", "fusion.1", "conv.2"],
             "name_idx": [0, 1, 2, 3, 2, 0],
             "start_ns": [0, 100 * k, 110 * k, 130 * k, 220 * k, 300 * k],
             "dur_ns": [20 * k, 100 * k, 10 * k, 20 * k, 10 * k, 20 * k]},
            {"name": "XLA Modules",
             "names": ["jit_pad(1)", "jit_core(123)", "jit_core(77)",
                       "jit__unknown(5)"],
             "name_idx": [0, 1, 2, 3, 0],
             "start_ns": [0, 100 * k, 205 * k, 220 * k, 300 * k],
             "dur_ns": [20 * k, 100 * k, 5 * k, 10 * k, 20 * k]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3/1", "names": ["wait"], "name_idx": [0],
             "start_ns": [0], "dur_ns": [900 * k]}]}]}
    r = xplane.reduce(ev, {"denoise": "^jit_core$",
                           "text_encode": "^jit__unknown$",
                           "pad": "^jit_pad$"})
    chip = r["chips"][0]
    # the window is the device lines' range, not the host's
    assert r["window_s"] == pytest.approx(320e-6)
    # the while holds two operations and is not counted itself
    assert chip["ops"] == 5 and chip["container_ops"] == 1
    assert chip["busy_s"] == pytest.approx(80e-6)
    assert chip["programs"]["denoise"] == {"count": 2,
                                           "total_s": pytest.approx(105e-6)}
    # an execution cut by the window's edge is left out
    assert chip["programs"]["pad"]["count"] == 0
    assert r["idle_gaps"] == [["python3: wait", pytest.approx(240e-6)]]
    assert dict(r["device_ops"]) == {
        "pad.0 = f32[8] pad(f32[4] %p)": pytest.approx(40e-6),
        "fusion.1": pytest.approx(20e-6), "conv.2": pytest.approx(20e-6)}


def test_reducer_uses_each_chips_own_edges():
    """Chips start tracing a little apart: an execution cut at the start
    of chip 1's trace begins after chip 0's first event and still is no
    whole execution."""
    k = 1000

    def chip(n, shift):
        return {"name": f"/device:TPU:{n}", "lines": [
            {"name": "XLA Ops", "names": ["fusion.1"], "name_idx": [0, 0],
             "start_ns": [shift, shift + 500 * k],
             "dur_ns": [400 * k, 400 * k]},
            {"name": "XLA Modules", "names": ["jit_core(1)"],
             "name_idx": [0, 0], "start_ns": [shift, shift + 500 * k],
             "dur_ns": [400 * k, 300 * k]}]}
    r = xplane.reduce({"planes": [chip(0, 0), chip(1, 50 * k)]},
                      {"denoise": "^jit_core$"})
    assert [c["programs"]["denoise"]["count"] for c in r["chips"]] == [1, 1]
    assert r["window_s"] == pytest.approx(950e-6)
    assert r["busy_s"] == pytest.approx(800e-6)


def test_window_is_no_shorter_than_the_profiler_was_on():
    """A slice that starts or ends in an idle gap holds no device event
    there; the time the profiler was on still counts as idle."""
    k = 1000
    ev = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "names": ["fusion.1"], "name_idx": [0, 0],
         "start_ns": [0, 600 * k], "dur_ns": [400 * k, 200 * k]}]}]}
    short = xplane.reduce(ev, {}, traced_s=500e-6)
    assert short["window_s"] == pytest.approx(800e-6)
    assert [g[0] for g in short["idle_gaps"]] == ["(no host event)"]
    r = xplane.reduce(ev, {}, traced_s=1000e-6)
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["busy_s"] == pytest.approx(600e-6)
    assert dict(r["idle_gaps"]) == {
        "(no host event)": pytest.approx(200e-6),
        xplane.EDGE_GAP: pytest.approx(200e-6)}


def test_reducer_on_the_recorded_slice():
    """One SD1.5 512x512 request out of this benchmark's first trace of
    the chip (sd15_512_sat, TPU v5 lite, PR 22): VAE decode and text
    encode of the neighbours at either end, one whole denoise between."""
    ev = xplane.load_events(os.path.join(
        BENCH, "testdata", "sd15_512_one_request.events.json.gz"))
    with open(os.path.join(BENCH, "configs", "sd15-512.json")) as f:
        config = json.load(f)
    r = xplane.reduce(ev, config["programs"])
    assert len(r["chips"]) == 1
    chip = r["chips"][0]
    assert r["window_s"] == pytest.approx(0.630936752, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.609590622, abs=1e-9)
    assert chip["ops"] == 88041 and chip["container_ops"] == 461
    assert chip["longest_gap_s"] == pytest.approx(0.001650839, abs=1e-9)
    prog = chip["programs"]
    assert prog["denoise"]["count"] == 1
    assert prog["denoise"]["total_s"] == pytest.approx(0.592966838, abs=1e-9)
    assert prog["vae_decode"]["count"] == 2
    assert prog["vae_decode"]["total_s"] == pytest.approx(0.036689713,
                                                          abs=1e-9)
    assert prog["text_encode"]["count"] == 2
    # the costliest operation is the fp32 score chunk of the 4096-token
    # self-attention, as the trace prints it
    name, seconds = r["device_ops"][0]
    assert "f32[2,8,2048,4096]" in name and name.startswith("fusion.")
    assert seconds == pytest.approx(0.032763149, abs=1e-9)
    # 20 steps x 2 CFG rows of 0.803 TFLOP in 0.593 s of a 197 TFLOP/s chip
    util = flops.denoise_flops_per_image(config) \
        / prog["denoise"]["total_s"] / 197e12
    assert util == pytest.approx(0.275, abs=0.001)


# --- the command, end to end on the CPU ------------------------------------

def last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def test_rehearsal_end_to_end_holds_the_last_line_to_the_contract(tmp_path):
    cell = manifest()["workloads"][1]["name"]
    p = run_cell(ROOT, "--workload", cell, "--seed", "5", "--seconds", "4",
                 "--trace", "0", "--rehearse", "--out", str(tmp_path))
    assert p.returncode == 0, p.stderr[-3000:]
    out = last_json(p.stdout)
    assert set(out) == RESULT_KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    m = manifest()
    want = {x["name"] for x in m["end_to_end"]
            if cell in x.get("workloads", [cell])}
    assert set(out["metrics"]) == want
    for name, row in out["metrics"].items():
        assert set(row) == {"value", "unit"} and row["value"] > 0, name
    with open(tmp_path / "run.json") as f:
        run = json.load(f)
    assert run["probe"]["sha256"] and (tmp_path / "probe.png").is_file()
    assert run["result"] == out
    # no device metric from a CPU run, under any name
    device_metrics = {x["name"] for x in m["per_layer"]
                      if x["source"] == "device_trace"}
    assert not device_metrics & set(run["all_metrics"])


def test_default_mode_refuses_the_cpu(tmp_path):
    cell = manifest()["workloads"][0]["name"]
    p = run_cell(ROOT, "--workload", cell, "--seed", "1", "--seconds", "2",
                 "--trace", "0", "--out", str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_a_cell_is_added_with_files_and_entries_alone(tmp_path):
    """A configuration, a mix, a per-layer metric and their manifest
    entries dropped into a copy of the benchmark run with no edit to any
    file that was there."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "comfyui_distributed_tpu"),
               root / "comfyui_distributed_tpu")
    bench = root / "benchmarks" / "chip"
    with open(bench / "configs" / "sd15-512.json") as f:
        config = json.load(f)
    config["name"] = "extra-config"
    (bench / "configs" / "extra-config.json").write_text(json.dumps(config))
    (bench / "traffic" / "closed1_extra.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1, "text_words": 5}))
    (bench / "layer_metrics" / "extra_requests.py").write_text(
        "def read(ctx):\n    return float(len(ctx.completed()))\n")
    m = manifest()
    m["configs"].append({"name": "extra-config", "source": "a test",
                         "file": "benchmarks/chip/configs/extra-config.json",
                         "reduced": [], "why": "a test"})
    m["workloads"].append({"name": "extra_cell", "config": "extra-config",
                           "traffic": "closed1_extra", "chips": 1,
                           "why": "a test"})
    m["per_layer"].append({"name": "extra_requests", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "Dispatch", "moves": "tti_p50_s",
                           "workloads": ["extra_cell"]})
    for x in m["end_to_end"]:
        if x["name"] == "tti_p50_s":
            x["workloads"].append("extra_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    p = run_cell(str(root), "--workload", "extra_cell", "--seed", "3",
                 "--seconds", "3", "--trace", "1", "--rehearse",
                 "--out", str(tmp_path / "out"))
    assert p.returncode == 0, p.stderr[-3000:]
    out = last_json(p.stdout)
    assert out["correct"] is True
    assert out["metrics"]["extra_requests"]["value"] == out["attempted"]
    # a CPU trace has no device plane: nothing is reported from it
    assert "busy_s" not in out["device"] and "breakdown" not in out


def test_a_checkout_without_the_program_gives_no_result(tmp_path):
    root = tmp_path / "bare"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    p = run_cell(str(root), "--workload", manifest()["workloads"][0]["name"],
                 "--seed", "1", "--seconds", "2", "--trace", "0",
                 "--rehearse")
    assert p.returncode != 0 and p.stdout.strip() == ""
