"""Tests of what PR 26 added to the benchmark: the language-model
configuration and cell, ``lib/lm_bytes.py`` against hand counts, the six
readers on a made-up context, the comparison of ``verify_lm.py``, and the
cell's rehearsal end to end, on the CPU.

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

from lib import lm_bytes                        # noqa: E402
from lib.context import Context                 # noqa: E402
from lib.server import BenchFailure             # noqa: E402

CELL = "ouro_expand_sd15_512_sat"
CONFIG = "ouro-2.6b-expand-sd15-512"
LM_READERS = ["lm_device_s_per_request", "lm_decode_ms_per_token",
              "lm_share_of_busy_pct", "lm_decode_hbm_roofline_pct",
              "lm_mlp_device_s_per_request", "lm_attn_device_s_per_request"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"lm_metric_{name}", os.path.join(BENCH, "layer_metrics",
                                          name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --- the manifest and the configuration ------------------------------------

def test_the_cell_its_configuration_and_its_metrics_are_entries():
    m = manifest()
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": "closed2_unique",
                    "chips": 1}
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == [] and entry["source"] == config()["source"]
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    reported = {x["name"] for g in ("end_to_end", "per_layer") for x in m[g]
                if CELL in x.get("workloads", [CELL])}
    assert {"images_per_s", "tti_p50_s", "setup_s"} <= reported
    assert set(LM_READERS) <= reported
    for x in m["per_layer"]:
        if x["name"] in LM_READERS:
            assert x["layer"] == "Language model"
            assert x["workloads"] == [CELL] and x["source"] == "device_trace"
    # entries are appended: the new ones close their lists
    assert m["workloads"][-1]["name"] == CELL
    assert m["configs"][-1]["name"] == CONFIG
    assert [x["name"] for x in m["per_layer"][-6:]] == LM_READERS


def test_the_configuration_holds_the_catalogs_config_and_cuts_nothing():
    cfg = config()
    lm = cfg["lm"]
    assert cfg["reduced"] == [] and cfg["name"] == CONFIG
    # the published widths, the full depth, the four loops
    assert (lm["hidden_size"], lm["num_hidden_layers"], lm["total_ut_steps"],
            lm["num_attention_heads"], lm["num_key_value_heads"],
            lm["head_dim"], lm["intermediate_size"], lm["vocab_size"]) \
        == (2048, 48, 4, 16, 16, 128, 5632, 49152)
    # every key stands at the top level too, where the driver compares
    for key, value in lm.items():
        assert cfg[key] == value, key
    if os.path.isfile(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            rows = [json.loads(line) for line in f]
        (row,) = [r for r in rows if r["name"] == "Ouro-2.6B"]
        assert lm == row["config"] and cfg["source"] == row["source_url"]
    gen = [n["inputs"] for n in cfg["graph"].values()
           if n["class_type"] == "LanguageModelGenerate"]
    assert len(gen) == 1 and gen[0]["max_new_tokens"] == 64
    assert gen[0]["prompt_tokens"] == 64 and gen[0]["temperature"] == 0.0
    assert cfg["vary"]["text"] == [["21", "text"]]
    assert cfg["graph"]["6"]["inputs"]["text"] == ["21", 0]
    assert "rotate" not in cfg
    assert set(cfg["programs"]) == {"lm_generate", "denoise", "vae_decode",
                                    "text_encode"}
    # behind the STRING everything is sd15-512's
    with open(os.path.join(BENCH, "configs", "sd15-512.json")) as f:
        sd15 = json.load(f)
    assert cfg["unet"] == sd15["unet"]
    for nid in ("4", "5", "7", "13", "3", "8", "14"):
        assert cfg["graph"][nid] == sd15["graph"][nid], nid


# --- bytes from shapes -------------------------------------------------------

def test_decode_bytes_against_hand_counts():
    lm = config()["lm"]
    # one layer: q, k, v, o of 2048 x 2048; gate, up, down of 2048 x 5632;
    # four gains
    assert lm_bytes.layer_params(lm) == 4 * 2048 * 2048 + 3 * 2048 * 5632 \
        + 4 * 2048 == 51_388_416
    # 192 slots x keys and values x 16 heads x 128: 1.5 MiB a position
    assert lm_bytes.cache_values_per_position(lm) * 2 == 1.5 * 2 ** 20
    empty = lm_bytes.decode_bytes_per_token(lm)
    by_hand = 2 * (4 * (48 * 51_388_416 + 2048 + 2048 + 1)
                   + 2048 * 49152 + 2048) + 1.5 * 2 ** 20
    assert empty == by_hand
    assert empty / 1e9 == pytest.approx(19.9, abs=0.05)     # the issue's
    # every cached position adds its keys and values in every slot
    assert lm_bytes.decode_bytes_per_token(lm, 95) - empty \
        == 95 * 1.5 * 2 ** 20
    # a model small enough to count on one's fingers
    tiny = {"hidden_size": 4, "intermediate_size": 8, "num_hidden_layers": 2,
            "total_ut_steps": 3, "num_attention_heads": 2,
            "num_key_value_heads": 2, "head_dim": 2, "vocab_size": 10}
    layer = 4 * 4 * 4 + 3 * 4 * 8 + 4 * 4
    assert lm_bytes.layer_params(tiny) == layer == 176
    assert lm_bytes.decode_bytes_per_token(tiny, 5) == 2 * (
        3 * (2 * 176 + 4 + 4 + 1) + 4 * 10 + 4 + 3 * 2 * 2 * 2 * 2 * 6)


# --- the readers -------------------------------------------------------------

def chip(lm_count=1, lm_s=1.9, denoise_count=2):
    modules = {"jit_lm_generate": {"count": lm_count,
                                   "total_s": lm_s * lm_count},
               "jit_core": {"count": denoise_count,
                            "total_s": 0.36 * denoise_count},
               "jit__lambda": {"count": denoise_count,
                               "total_s": 0.02 * denoise_count},
               "jit__unknown": {"count": 2 * denoise_count,
                                "total_s": 0.001 * denoise_count},
               "jit_convert_element_type": {"count": 30, "total_s": 0.0}}
    programs = {"lm_generate": modules["jit_lm_generate"],
                "denoise": modules["jit_core"],
                "vae_decode": modules["jit__lambda"],
                "text_encode": modules["jit__unknown"]}
    return {"chip": 0, "busy_s": 4.7, "modules": modules,
            "programs": programs}


def context(traced=True, requests=20, profile=True, **chip_kw):
    classes = {"lm_mlp": 1.18, "lm_proj": 0.50, "lm_attn": 0.10,
               "lm_cache": 0.06, "lm_norm": 0.04, "lm_head": 0.02,
               "embed": 0.0, "other": 0.0, "gaps": 0.0}
    window = {"pipeline": {
        "stages": {"lm_generate": {"count": requests, "total_s": 45.0}},
        "counters": {"lm.tokens_decoded": 64 * requests,
                     "lm.prompt_tokens": 27 * requests,
                     "lm.layer_applications": 64 * 192 * requests}}}
    if profile:
        window["profile"] = {
            "chips": [{"chip": 0}], "names_found": True,
            "programs": {"jit_lm_generate": {"count": 1.0, "mean_s": 1.9,
                                             "classes": classes},
                         "jit_core": {"count": 2.0, "mean_s": 0.36,
                                      "classes": {"ff": 0.1}}}}
    with open(os.path.join(BENCH, "lib", "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"]["TPU v5 lite"]
    trace = {"window_s": 4.9, "busy_s": 4.7, "chips": [chip(**chip_kw)]} \
        if traced else None
    return Context(cell={}, config=config(), mix={}, seconds=51.0,
                   images_per_request=1, setup_s=100.0, records=[],
                   window={}, metrics_setup={}, metrics_window=window,
                   compiles_in_window=0, resource={}, device={},
                   peaks=peaks, trace=trace)


@pytest.mark.parametrize("name", LM_READERS)
def test_readers_give_nothing_without_a_trace(name):
    assert reader(name)(context(traced=False)) is None


@pytest.mark.parametrize("name", LM_READERS)
def test_readers_give_nothing_for_a_configuration_with_no_such_program(
        name):
    """The other configurations' files name no ``lm_generate`` pattern: a
    reader laid over them reads nothing and does not raise."""
    ctx = context()
    del ctx.config["programs"]["lm_generate"]
    assert reader(name)(ctx) is None


def test_program_seconds_and_milliseconds_a_token():
    ctx = context(lm_count=2)
    assert reader("lm_device_s_per_request")(ctx) == pytest.approx(1.9)
    # 64 tokens an execution, from the program's own counter
    assert reader("lm_decode_ms_per_token")(ctx) \
        == pytest.approx(1900.0 / 64)
    # no generate execution counted in the window: no reading
    ctx.metrics_window["pipeline"]["stages"] = {}
    assert reader("lm_decode_ms_per_token")(ctx) is None
    assert reader("lm_decode_hbm_roofline_pct")(ctx) is None


def test_share_is_per_request_and_a_cut_execution_does_not_move_it():
    # a request: 1.9 s of the language model, and 0.36 + 0.02 + 0.001 s
    want = 100.0 * 1.9 / (1.9 + 0.381)
    assert reader("lm_share_of_busy_pct")(context()) == pytest.approx(want)
    # the slice's edge cut one of two generate executions out: the same
    assert reader("lm_share_of_busy_pct")(
        context(lm_count=1, denoise_count=2)) == pytest.approx(want)
    assert reader("lm_share_of_busy_pct")(
        context(lm_count=2, denoise_count=2)) == pytest.approx(want)


def test_roofline_is_the_least_seconds_over_the_measured(capsys):
    value = reader("lm_decode_hbm_roofline_pct")(context())
    # mean position: 27 prompt ids and on average 31.5 tokens before it
    nbytes = lm_bytes.decode_bytes_per_token(config()["lm"], 27 + 31.5)
    assert value == pytest.approx(100.0 * (nbytes / 819e9) / (1.9 / 64))
    assert 1 < value < 100
    assert "GB a token" in capsys.readouterr().out
    # at the roofline itself it reads 100, and only a time under the
    # least possible could pass it
    at_peak = 64 * nbytes / 819e9
    assert reader("lm_decode_hbm_roofline_pct")(context(lm_s=at_peak)) \
        == pytest.approx(100.0)


def test_class_readers_add_their_classes():
    assert reader("lm_mlp_device_s_per_request")(context()) \
        == pytest.approx(1.18)
    assert reader("lm_attn_device_s_per_request")(context()) \
        == pytest.approx(0.50 + 0.10 + 0.06)
    # a program that writes no summary (the parent): nothing
    assert reader("lm_mlp_device_s_per_request")(
        context(profile=False)) is None


def test_a_pattern_that_matches_no_program_is_an_error_never_a_zero():
    ctx = context()
    ctx.config["programs"]["lm_generate"] = "^jit_renamed$"
    ctx.trace["chips"][0]["programs"]["lm_generate"] = {"count": 0,
                                                        "total_s": 0.0}
    with pytest.raises(BenchFailure, match="matches no program"):
        reader("lm_device_s_per_request")(ctx)
    with pytest.raises(BenchFailure, match="programs of the summary"):
        reader("lm_mlp_device_s_per_request")(ctx)


# --- the comparison with the reference ---------------------------------------

def load_verify():
    spec = importlib.util.spec_from_file_location(
        "chipbench_verify_lm_cell", os.path.join(BENCH, "verify_lm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_limits_lie_between_the_chips_two_readings():
    """PERF.md section 6, PR 26, call 3, at the published widths: the
    served path over three requests, and the nearest precision below the
    stated bf16 (the cache, then the weights, in float8_e4m3fn).  Every
    served reading is inside every limit with a factor of 1.85 to spare,
    and each 8-bit reading is outside at least one by as much."""
    verify = load_verify()
    limits = verify.LIMITS
    served = [{"max_over_std": 0.02256, "mean_over_std": 0.00292,
               "margin_over_std": 0.0},
              {"max_over_std": 0.02568, "mean_over_std": 0.00298,
               "margin_over_std": 0.00187},
              {"max_over_std": 0.02117, "mean_over_std": 0.00305,
               "margin_over_std": 0.0}]
    cache_8bit = {"max_over_std": 0.09293, "mean_over_std": 0.01316}
    weights_8bit = {"max_over_std": 0.84197, "mean_over_std": 0.13055}
    assert set(limits) == {"max_over_std", "mean_over_std",
                           "margin_over_std"}
    assert limits["margin_over_std"] == 2 * limits["max_over_std"]
    for reading in served:
        for key, value in reading.items():
            assert value <= limits[key] / 1.85, (key, value)
    for low in (cache_8bit, weights_8bit):
        for key, value in low.items():
            assert value >= 1.85 * limits[key], (key, value)


def test_compare_logits_holds_each_reading_to_its_limit():
    import numpy as np
    verify = load_verify()
    rng = np.random.default_rng(0)
    reference = rng.standard_normal((8, 1000))
    tokens = reference.argmax(axis=-1)
    limits = {"max_over_std": 0.05, "mean_over_std": 0.006,
              "margin_over_std": 0.1}
    std = reference.std()
    near = reference + 0.004 * std * rng.standard_normal(reference.shape)
    got = verify.compare_logits(near, reference, tokens, limits)
    assert got["correct"] and got["argmax_agree"] == 1.0
    assert got["mean_over_std"] == pytest.approx(0.004 * 0.798, rel=0.05)
    far = reference + 0.02 * std * rng.standard_normal(reference.shape)
    got = verify.compare_logits(far, reference, tokens, limits)
    assert not got["correct"] and got["mean_over_std"] > 0.006
    # one logit far off moves the maximum alone
    spike = reference.copy()
    spike[3, 7] += 0.2 * std
    got = verify.compare_logits(spike, reference, tokens, limits)
    assert not got["correct"] and got["mean_over_std"] < 0.006
    # a NaN is never inside a limit
    spike[0, 0] = np.nan
    assert not verify.compare_logits(spike, reference, tokens,
                                     limits)["correct"]


def test_the_verify_graph_differs_from_a_timed_one_by_one_node():
    verify = load_verify()
    from lib.traffic import fill_graph
    cfg = config()
    req = {"index": 0, "text": "twelve seeded words", "seed": 7}
    timed = fill_graph(cfg, req, "p")
    graph = verify.verify_graph(cfg, req["text"], req["seed"], "p")
    extra = set(graph) - set(timed)
    assert len(extra) == 1
    (node,) = [graph[k] for k in extra]
    assert node["class_type"] == "SaveLanguageModelOutput"
    assert node["inputs"]["lm_output"] == ["21", 1]
    assert {k: graph[k] for k in timed} == timed


# --- the command, end to end on the CPU ------------------------------------

def test_rehearsal_of_the_cell_holds_the_last_line_to_the_contract(tmp_path):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "4", "--trace", "0",
         "--rehearse", "--out", str(tmp_path)],
        cwd=ROOT, env=e, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device"}
    faults = [ln for ln in p.stdout.splitlines() if "FAULT" in ln]
    assert out["correct"] is True and out["failed"] == 0, faults
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    assert set(out["metrics"]) == {"images_per_s", "tti_p50_s", "setup_s"}
    with open(tmp_path / "run.json") as f:
        run = json.load(f)
    # no device metric from a CPU run, under any name
    assert not set(LM_READERS) & set(run["all_metrics"])
    assert run["all_metrics"]["compiles_in_window"]["value"] == 0
    assert run["window_counters"]["lm.tokens_decoded"] \
        == 4 * run["window_stages"]["lm_generate"]["count"]
    assert run["setup"]["node_total_s"]["LanguageModelLoader"] > 0
