"""Tests of the readers PR 23 added (the program's trace summary, its
spans and counters) and of ``lib/kernels.py``'s counts, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

``tests/test_chip_benchmark.py`` collects these under ``pytest tests/``.
"""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

from lib import flops, kernels                  # noqa: E402
from lib.server import BenchFailure             # noqa: E402

SCOPE_READERS = {
    "attn_device_s_per_image": 0.30 + 0.01,
    "proj_ff_device_s_per_image": 0.05 + 0.06,
    "conv_device_s_per_image": 0.07 + 0.02,
    "norm_device_s_per_image": 0.04,
}
SPAN_READERS = ["dispatch_host_ms_per_request", "exec_idle_pct",
                "queue_to_device_mean_ms", "gather_ms_per_request",
                "setup_weights_s", "setup_trace_compile_s"]
TRACE_READERS = list(SCOPE_READERS) + ["attn_roofline_pct",
                                       "idle_under_dispatch_pct"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"span_metric_{name}",
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def config(name="sd15-512"):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def peaks():
    with open(os.path.join(BENCH, "lib", "peaks.json")) as f:
        return json.load(f)["by_device_kind"]["TPU v5 lite"]


def profile():
    """A summary as ``trace_summary.summarize`` writes it: one chip, two
    whole denoise executions of 0.6 s."""
    classes = {"attn_self": 0.30, "attn_cross": 0.01, "attn_proj": 0.05,
              "ff": 0.06, "resblock": 0.07, "resample": 0.02, "norm": 0.04,
              "embed": 0.001, "sampler": 0.004, "other": 0.03, "gaps": 0.015}
    return {"chips": [{"chip": 0}], "names_found": True,
            "window_s": 3.0, "busy_s": 2.88,
            "host_spans": ["dispatch", "device_wait", "exec_idle"],
            "op_stat_names": ["tf_op"],
            "programs": {
                "jit_core": {"count": 2.0, "mean_s": 0.6, "classes": classes},
                "jit__lambda": {"count": 2.0, "mean_s": 0.018,
                                "classes": {"vae_conv": 0.018}}},
            "idle": {"dispatch": 0.03, "KSampler": 0.06, "none": 0.03},
            "idle_under": {"dispatch": 0.09, "exec_idle": 0.0}}


def stage(total, count=10):
    return {"count": count, "total_s": total}


class FakeContext:
    def __init__(self, traced=True, with_profile=True, stages=None,
                 setup_stages=None, retraces=None, completed=10,
                 seconds=51.0, cfg=None):
        self.config = cfg or config()
        self.peaks = peaks()
        self.seconds = seconds
        self.trace = {"window_s": 3.0} if traced else None
        self.metrics_window = {"pipeline": {"stages": stages or {}}}
        if with_profile:
            self.metrics_window["profile"] = profile()
        self.metrics_setup = {
            "pipeline": {"stages": setup_stages or {}},
            "retraces": retraces if retraces is not None
            else {"traces": 3, "compiles": 3}}
        self._completed = [{}] * completed

    def completed(self):
        return self._completed

    def stage(self, name):
        return self.metrics_window["pipeline"]["stages"].get(name)


# --- operations and bytes from shapes --------------------------------------

def test_attention_calls_of_a_unet_small_enough_to_count_by_hand():
    # 8x8 latent, 32 channels x (1, 2), one res block, depth (1, 1), heads
    # of 16 channels, 5 text tokens: transformers sit at down 0, down 1,
    # middle, up 1 (x2) and up 0 (x2)
    unet = {"model_channels": 32, "channel_mult": [1, 2],
            "num_res_blocks": 1, "transformer_depth": [1, 1],
            "num_head_channels": 16}
    calls = kernels.attention_calls(unet, 8, 8, ctx_len=5)
    shapes = [(c["kind"], c["q"], c["kv"], c["heads"], c["head_dim"])
              for c in calls]
    hi = [("self", 64, 64, 2, 16), ("cross", 64, 5, 2, 16)]
    lo = [("self", 16, 16, 4, 16), ("cross", 16, 5, 4, 16)]
    assert shapes == hi + lo + lo + lo + lo + hi + hi
    # one call: two matmuls of 2 x B x H x N x M x D and five operations
    # a score; q and the output are N x H x D values, k and v M x H x D
    ops, nbytes = kernels.attention_cost(calls[0], rows=2)
    assert ops == 4 * 2 * 2 * 64 * 64 * 16 + 5 * 2 * 2 * 64 * 64
    assert nbytes == 2 * (2 * 2 * 16 * (2 * 64 + 2 * 64))
    ops, nbytes = kernels.attention_cost(calls[1], rows=1)
    assert ops == 4 * 2 * 64 * 5 * 16 + 5 * 2 * 64 * 5
    assert nbytes == 2 * (2 * 16 * (2 * 64 + 2 * 5))


def _sd15_by_hand():
    # 8 heads at every level; per level five transformers of depth 1 over
    # 4096 / 1024 / 256 tokens with head dims 40 / 80 / 160, the middle
    # one over 64 tokens; each a self call and a cross call on 77 tokens
    matmul = 4 * 8 * (5 * (4096**2 * 40 + 1024**2 * 80 + 256**2 * 160)
                      + 64**2 * 160) \
        + 4 * 8 * 77 * (5 * (4096 * 40 + 1024 * 80 + 256 * 160) + 64 * 160)
    softmax = 5 * 8 * (5 * (4096**2 + 1024**2 + 256**2) + 64**2) \
        + 5 * 8 * 77 * (5 * (4096 + 1024 + 256) + 64)
    return matmul + softmax


def _sdxl_by_hand():
    # heads of 64 channels: 10 heads over 4096 tokens at level 1 (five
    # transformers of depth 2: 10 calls), 20 heads over 1024 tokens at
    # level 2 (five of depth 10 and the middle one: 60 calls)
    self_scores = 10 * 10 * 4096**2 + 60 * 20 * 1024**2
    cross_scores = 77 * (10 * 10 * 4096 + 60 * 20 * 1024)
    return (4 * 64 + 5) * (self_scores + cross_scores)


@pytest.mark.parametrize("name, level0, by_hand, total_tflop", [
    ("sd15-512", (5, 4096, 8, 40), _sd15_by_hand, 5.1885),
    ("sdxl-base-1024", (10, 4096, 10, 64), _sdxl_by_hand, 31.969)])
def test_attention_of_the_two_configurations(name, level0, by_hand,
                                             total_tflop):
    cfg = config(name)
    shape = flops.request_shape(cfg["graph"])
    calls = kernels.attention_calls(cfg["unet"], shape["height"] // 8,
                                    shape["width"] // 8)
    n, tokens, heads, dim = level0
    big = [c for c in calls if c["kind"] == "self" and c["q"] == tokens]
    assert len(big) == n
    assert {(c["heads"], c["head_dim"]) for c in big} == {(heads, dim)}
    cost = kernels.denoise_attention_per_image(cfg)
    # one sample's forward pass x 2 CFG rows x 20 steps
    assert cost["ops"] == by_hand() * 2 * 20
    assert cost["ops"] / 1e12 == pytest.approx(total_tflop, abs=1e-3)
    bound = kernels.attention_bound(cfg, peaks())
    # at these head dims attention is compute-bound on a v5e by far
    assert bound["bound"] == "compute"
    assert bound["seconds"] == pytest.approx(cost["ops"] / 197e12)
    assert bound["memory_s"] == pytest.approx(cost["bytes"] / 819e9)
    assert bound["memory_s"] < 0.5 * bound["compute_s"]


def test_attention_bound_names_the_memory_side_when_it_is_the_larger():
    slow_memory = dict(peaks(), hbm_bytes_per_s=1e9)
    bound = kernels.attention_bound(config(), slow_memory)
    assert bound["bound"] == "memory"
    assert bound["seconds"] == bound["memory_s"] > bound["compute_s"]


# --- the readers of the program's trace summary ----------------------------

@pytest.mark.parametrize("name", sorted(SCOPE_READERS))
def test_class_readers_add_their_classes_per_image(name):
    assert reader(name)(FakeContext()) == pytest.approx(SCOPE_READERS[name])


def test_classes_other_and_gaps_add_up_to_the_denoise_execution():
    prof = profile()["programs"]["jit_core"]
    named = sum(SCOPE_READERS.values()) + 0.001 + 0.004
    assert named + 0.03 + 0.015 == pytest.approx(prof["mean_s"])


def test_attn_roofline_is_the_bound_over_the_measured_seconds(capsys):
    value = reader("attn_roofline_pct")(FakeContext())
    bound = kernels.attention_bound(config(), peaks())
    assert value == pytest.approx(100.0 * bound["seconds"] / 0.31)
    assert 0 < value < 100
    assert "compute-bound" in capsys.readouterr().out


def test_idle_under_dispatch_is_a_share_of_the_traced_window():
    assert reader("idle_under_dispatch_pct")(FakeContext()) == \
        pytest.approx(3.0)
    # a slice shorter than a dispatch span holds none whole (the profiler
    # drops what began before it started): unknown, not 0
    ctx = FakeContext()
    ctx.metrics_window["profile"]["host_spans"] = ["exec_idle"]
    assert reader("idle_under_dispatch_pct")(ctx) is None


@pytest.mark.parametrize("name", TRACE_READERS)
def test_device_trace_readers_report_nothing_without_their_source(name):
    # the CPU rehearsal has no device trace
    assert reader(name)(FakeContext(traced=False)) is None
    # a program from before the summary serves no summary (the parent
    # commit under this benchmark)
    assert reader(name)(FakeContext(with_profile=False)) is None
    ctx = FakeContext()
    ctx.metrics_window["profile"]["chips"] = []
    assert reader(name)(ctx) is None


@pytest.mark.parametrize("name", TRACE_READERS)
def test_a_trace_without_names_fails_the_run_and_is_never_zero(name):
    ctx = FakeContext()
    ctx.metrics_window["profile"]["names_found"] = False
    with pytest.raises(BenchFailure, match="no op_name path"):
        reader(name)(ctx)


def test_two_programs_matching_the_denoise_pattern_fail_the_run():
    ctx = FakeContext()
    ctx.config["programs"]["denoise"] = "^jit_"
    with pytest.raises(BenchFailure, match="2 programs"):
        reader("norm_device_s_per_image")(ctx)


# --- the readers of the program's spans and counters -----------------------

def test_span_readers_on_a_window_of_ten_requests():
    stages = {"dispatch": stage(0.25), "exec_idle": stage(5.1, 12),
              "gather": stage(0.004), "queue_to_device": stage(6.5)}
    ctx = FakeContext(stages=stages,
                      setup_stages={"load_weights": stage(50.5, 2)},
                      retraces={"traces": 40, "compiles": 46,
                                "cache_loads": 4, "compiles_uncached": 42,
                                "trace_s": 30.0, "lower_s": 9.0,
                                "compile_s": 4.0, "cache_load_s": 8.0})
    assert reader("dispatch_host_ms_per_request")(ctx) == pytest.approx(25.0)
    assert reader("exec_idle_pct")(ctx) == pytest.approx(10.0)
    assert reader("gather_ms_per_request")(ctx) == pytest.approx(0.4)
    assert reader("queue_to_device_mean_ms")(ctx) == pytest.approx(650.0)
    assert reader("setup_weights_s")(ctx) == pytest.approx(50.5)
    assert reader("setup_trace_compile_s")(ctx) == pytest.approx(51.0)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_report_nothing_from_a_program_without_the_span(name):
    # the parent commit: no such stage, no seconds on the counters
    assert reader(name)(FakeContext()) is None


def test_span_readers_need_no_device_trace():
    ctx = FakeContext(traced=False, with_profile=False,
                      stages={"dispatch": stage(0.1)})
    assert reader("dispatch_host_ms_per_request")(ctx) == pytest.approx(10.0)
    assert reader("dispatch_host_ms_per_request")(
        FakeContext(stages={"dispatch": stage(0.1)}, completed=0)) is None


def test_every_new_metric_has_its_reader_and_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entries = {x["name"]: x for x in m["per_layer"]}
    sat = ["sdxl_1024_sat", "sd15_512_sat"]
    for name in TRACE_READERS:
        assert entries[name]["source"] == "device_trace"
    for name in list(SCOPE_READERS) + ["attn_roofline_pct"]:
        assert entries[name]["workloads"] == sat
        assert entries[name]["layer"] == "Denoise"
    assert entries["attn_roofline_pct"]["unit"] == "%"
    assert entries["queue_to_device_mean_ms"]["workloads"] == \
        ["sd15_512_steady"]
    assert entries["gather_ms_per_request"]["workloads"] == \
        ["sdxl_1024_fanout4"]
    for name in ("setup_weights_s", "setup_trace_compile_s"):
        assert len(entries[name]["workloads"]) == len(m["workloads"])
        assert entries[name]["moves"] == "setup_s"
    # appended: the twelve accepted entries stand first, as they were
    assert [x["name"] for x in m["per_layer"]][:12][-1] == "device_idle_pct"
    assert len(m["per_layer"]) == 24
