"""What PR 34 added to the benchmark, tested from outside it (the
benchmark's own test files are not a ``model_config`` PR's to edit): the
configuration against the catalog and against the program,
``lib/lm_swa_moe_bytes.py`` against hand counts, the three readers on a
made-up context (with the program's counters and phases, and on Ouro's
and openPangu's programs, which have neither, as the parent), the
accepted readers on the new program, and the seconds by phase of a
made-up trace.
"""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")
sys.path.insert(0, BENCH)

from lib import lm_swa_moe_bytes as swa_bytes       # noqa: E402

CELL = "exaone_expand_sd15_512_sat4"
CONFIG = "k-exaone-236b-expand-sd15-512"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 19200,
           "num_nextn_predict_layers": 0}
SLIDING, FULL = "sliding_attention", "full_attention"
HELD_TYPES = [SLIDING, SLIDING, SLIDING, FULL, SLIDING]
NEW_READERS = ["lm_prefill_device_s_per_request",
               "lm_swa_moe_decode_hbm_roofline_pct",
               "lm_prefill_flops_util_pct"]


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lm_cell = _load("chipbench_tests_lm_cell_for_swa_moe",
                os.path.join(BENCH, "tests", "test_lm_cell.py"))


def config(name=CONFIG):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def reader(name):
    return _load(f"swa_moe_metric_{name}",
                 os.path.join(BENCH, "layer_metrics", name + ".py")).read


# --- the configuration ---------------------------------------------------------

def test_the_configuration_holds_every_published_width_and_says_what_it_cut():
    cfg, lm = config(), config()["lm"]
    assert cfg["name"] == CONFIG and cfg["reduced"] == list(REDUCED)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmarks/chip/configs/{CONFIG}.json"
    (cell,) = [w for w in m["workloads"] if w["config"] == CONFIG]
    assert (cell["name"], cell["traffic"], cell["chips"]) == \
        (CELL, "closed4_unique", 1)
    # every width as published
    assert (lm["hidden_size"], lm["num_attention_heads"],
            lm["num_key_value_heads"], lm["head_dim"],
            lm["intermediate_size"], lm["moe_intermediate_size"],
            lm["num_experts_per_tok"], lm["num_shared_experts"],
            lm["routed_scaling_factor"], lm["sliding_window"]) \
        == (6144, 64, 8, 128, 18432, 2048, 8, 1, 2.5, 128)
    assert lm["layer_types"] == HELD_TYPES == cfg["layer_types"][:5]
    # the router keeps its published width; the held counts stand beside
    # the published ones
    assert lm["router_outputs"] == 128 == cfg["published"]["num_experts"]
    assert cfg["published"] == {**cfg["published"], "num_hidden_layers": 48,
                                "first_k_dense_replace": 1,
                                "vocab_size": 153600,
                                "num_nextn_predict_layers": 1}
    assert cfg["held_here"] == {
        "dense_blocks": 1, "expert_blocks": 4, "layer_types": HELD_TYPES,
        "routed_experts": [32, 48], "vocabulary_rows": 19200,
        "chips_sharing_a_layer": 8}
    assert "8 chips share each layer" in cfg["deployment"]
    assert "NOT HELD" in cfg["multi_token_prediction"]
    # every item the issue lists as not in the catalog's config
    assumed = " ".join(cfg["assumed"])
    for said in ("post_attention_layernorm", "post_feedforward_layernorm",
                 "q_norm, k_norm", "sliding_attention layers ONLY",
                 "rotate_half", "counts the query's own position",
                 "no bias anywhere", "n_group 1", "no score-correction bias",
                 "added unweighted", "float32", "gains half of that (0.5"):
        assert said in assumed, said
    assert len(cfg["assumed"]) >= 12
    # no width among the cuts
    for key in cfg["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) \
            or key == "vocab_size"
    # every number stands at the top level too, where the driver compares
    for key, value in lm.items():
        if key not in ("router_outputs", "dense_layers_held",
                       "experts_first", "layer_types", "rope_theta"):
            assert cfg[key] == value, key
    assert lm["rope_theta"] == cfg["rope_parameters"]["rope_theta"] == 1e6
    if os.path.isfile(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            rows = [json.loads(line) for line in f]
        (row,) = [r for r in rows if r["name"] == "K-EXAONE-236B-A23B"]
        assert cfg["source"] == row["source_url"]
        differs = {k: cfg[k] for k, v in row["config"].items()
                   if cfg.get(k) != v}
        assert differs == REDUCED


def test_the_program_serves_what_the_configuration_states():
    from comfyui_distributed_tpu.models import swa_moe
    lm, share = config()["lm"], swa_moe.K_EXAONE_SHARE
    same = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok", "num_shared_experts",
            "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
            "rope_theta", "sliding_window", "vocab_size",
            "num_hidden_layers")
    for key in same:
        assert getattr(share, key) == lm[key], key
    assert list(share.layer_types) == lm["layer_types"]
    assert share.num_experts == lm["router_outputs"]
    assert share.experts_held == lm["num_experts"]
    assert share.experts_first == lm["experts_first"]
    assert share.first_k_dense_replace == lm["dense_layers_held"]
    # and the bytes of the benchmark count the program's tree
    assert swa_bytes.resident_params(lm) \
        + 4 * 16 * swa_bytes.expert_params(lm) \
        + lm["hidden_size"] * lm["vocab_size"] \
        == swa_moe.param_count(share)
    assert swa_bytes.key_bytes(lm) * (4 * 128 + 576) \
        == swa_moe.kv_cache_bytes(share, 1, 576)
    # the graph's node asks for the model by a name of this family
    nodes = {n["class_type"]: n["inputs"] for n in config()["graph"].values()}
    from comfyui_distributed_tpu.models import registry
    assert registry.detect_lm_family(
        nodes["LanguageModelLoader"]["model_name"])[0] == "exaone"


# --- bytes and FLOPs from shapes ---------------------------------------------------

def test_decode_bytes_against_hand_counts():
    lm = config()["lm"]
    attention = 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144
    assert swa_bytes.attention_params(lm) == attention == 113_246_208
    assert swa_bytes.expert_params(lm) == 3 * 6144 * 2048 == 37_748_736
    assert 2 * swa_bytes.expert_params(lm) / 1e6 == pytest.approx(75.5,
                                                                  abs=0.05)
    assert swa_bytes.key_bytes(lm) == 4096                  # 4 KiB a key
    matrices = 5 * attention + 3 * 6144 * 18432 \
        + 4 * (6144 * 128 + 37_748_736)
    assert swa_bytes.block_matrices(lm) == matrices == 1_060_110_336
    resident = matrices + 5 * 2 * (6144 + 128) + 6144 + 6144 * 19200
    assert swa_bytes.resident_params(lm) == resident
    assert resident * 2 / 1e9 == pytest.approx(2.356, abs=0.001)  # 2.36 GB
    empty = swa_bytes.decode_bytes_per_step(lm)
    assert empty == 2 * resident + 2 * 6144 + 5 * 4096
    # a key attended to adds 4 KiB a row; a hit expert 75.5 MB
    assert swa_bytes.decode_bytes_per_step(lm, 1000, 4) - empty \
        == 3 * (2 * 6144 + 5 * 4096) + 4 * 1000 * 4096
    assert swa_bytes.decode_bytes_per_step(lm, hits=11) - empty \
        == 11 * 75_497_472
    # ISSUE 34: 3.2 GB a step of 4 rows: 11 experts hit, four full rings
    # and ~520 positions of the full layer a row
    assert swa_bytes.decode_bytes_per_step(lm, 4 * 128 + 520, 4, 11) / 1e9 \
        == pytest.approx(3.2, abs=0.02)
    # 7.2 GB if all 16 experts of each layer were streamed
    assert swa_bytes.decode_bytes_per_step(lm, 4 * 128 + 520, 4, 64) / 1e9 \
        == pytest.approx(7.2, abs=0.05)


def test_prefill_flops_against_hand_counts():
    lm = config()["lm"]
    # a row of 477 real positions: a full layer's triangle, four bands
    band = 128 * 129 // 2 + (477 - 128) * 128
    assert swa_bytes.visible_pairs(lm, 477) \
        == 477 * 478 / 2 + 4 * band == 325_715
    # a prompt shorter than the window: every layer a triangle
    assert swa_bytes.visible_pairs(lm, 100) == 5 * 100 * 101 / 2
    assert band / (477 * 478 / 2) < 0.47        # the band, not the square
    flops = swa_bytes.prefill_flops(lm, 4, 512, 477, 8192)
    products = 2 * 1_060_110_336 * 4 * 512
    experts = 8192 * 2 * 37_748_736
    attention = 4 * 8192 * 4 * 325_715
    head = 2 * 6144 * 19200 * 4
    assert flops == products + experts + attention + head
    assert (products / 1e12, experts / 1e12) == (
        pytest.approx(4.34, abs=0.01), pytest.approx(0.62, abs=0.01))
    assert flops / 1e12 == pytest.approx(5.0, abs=0.05)
    # every hit expert over all 2048 tokens, as `_routed` computed them
    # until PR 35, is 16 x the local pairs; in tiles of `EXPERT_TILE` rows
    # (each expert's own tokens, its last tile partly padding: at most
    # 16 experts x 4 blocks x 127 rows over the pairs) it is under 2 x
    assert 16 * 2048 * 4 * 2 * 37_748_736 / 1e12 == pytest.approx(9.9,
                                                                  abs=0.01)
    from comfyui_distributed_tpu.models import mla_moe
    assert (8192 + 16 * 4 * (mla_moe.EXPERT_TILE - 1)) / 8192 < 2


# --- the readers -------------------------------------------------------------------

def context(phases=True, counted=True, requests=21, rows=3, padded=1,
            name=CONFIG, **kw):
    """test_lm_cell's made-up window with this cell's configuration and,
    with ``counted`` / ``phases``, what this family's program counts and
    the seconds by phase its trace summary has."""
    ctx = lm_cell.context(requests=requests, rows=rows, padded=padded, **kw)
    ctx.config = config(name)
    counters = ctx.metrics_window["pipeline"]["counters"]
    counters["lm.prompt_tokens"] = 477 * requests
    executions = counters["lm.executions"]
    if counted:
        counters.update({
            "lm.expert_pairs": requests * 64 * 4 * 8,
            "lm.expert_pairs_local": requests * 64 * 4,
            "lm.expert_hits": executions * 64 * 11,
            "lm.expert_pairs_dropped": 0,
            "lm.expert_pairs_local_prefill": executions * 8192,
            "lm.keys_attended_window": requests * 64 * 4 * 128,
            "lm.keys_attended_full": int(requests * 64 * 509.5)})
    prof = ctx.metrics_window.get("profile")
    if prof:
        program = prof["programs"]["jit_lm_generate"]
        program["classes"]["lm_experts"] = 0.09
        if phases:
            program["phases"] = {"prefill": 0.12, "decode": 0.32}
    return ctx


def test_the_prefill_reader_is_the_phases_seconds_per_request():
    assert reader("lm_prefill_device_s_per_request")(context()) \
        == pytest.approx(0.12 / 3)


def test_the_roofline_reader_counts_the_keys_attended_and_the_experts_hit():
    ctx = context()
    lm = ctx.config["lm"]
    # 3 requests in 4 program rows, 64 steps of the decode phase alone:
    # 512 ring slots and 509.5 full positions a row a step, 11 experts
    nbytes = swa_bytes.decode_bytes_per_step(lm, 512 + 509.5, 4.0, 11.0)
    want = 100.0 * (nbytes / 819e9) / (0.32 / 64)
    assert reader("lm_swa_moe_decode_hbm_roofline_pct")(ctx) \
        == pytest.approx(want)
    assert 75 < want < 82
    # the whole execution's seconds (1.9 in the made-up summary) are not
    # what it divides by: the prefill is none of a step's
    assert want > 5 * 100.0 * (nbytes / 819e9) / (1.9 / 64)
    # a program that attended to a full cache in every layer is held to
    # more bytes
    ctx.metrics_window["pipeline"]["counters"]["lm.keys_attended_window"] \
        *= 4
    assert reader("lm_swa_moe_decode_hbm_roofline_pct")(ctx) > want


def test_the_prefill_utilisation_reader_counts_the_local_pairs():
    ctx = context()
    flops = swa_bytes.prefill_flops(ctx.config["lm"], 4.0, 512, 477.0, 8192)
    want = 100.0 * flops / 0.12 / 197e12
    assert reader("lm_prefill_flops_util_pct")(ctx) == pytest.approx(want)
    assert 20 < want < 22
    ctx.metrics_window["pipeline"]["counters"][
        "lm.expert_pairs_local_prefill"] *= 16
    assert reader("lm_prefill_flops_util_pct")(ctx) > 1.8 * want


@pytest.mark.parametrize("other", ["ouro-2.6b-expand-sd15-512",
                                   "pangu-ultra-moe-expand-sd15-512"])
@pytest.mark.parametrize("name", NEW_READERS)
def test_the_new_readers_give_nothing_on_the_other_families_programs(
        name, other):
    """Ouro's and openPangu's programs, and the parent's, have no phase
    scope and count no keys: the readers give nothing and do not raise;
    nor without a trace, a profile, or the program's pattern, nor where
    one of the two sources is there and the other is not."""
    assert reader(name)(context(phases=False, counted=False,
                                name=other)) is None
    assert reader(name)(context(phases=False, counted=False)) is None
    assert reader(name)(context(phases=False)) is None
    assert reader(name)(context(counted=False)) is None \
        or name == "lm_prefill_device_s_per_request"
    assert reader(name)(context(traced=False)) is None
    assert reader(name)(context(profile=False)) is None
    ctx = context()
    del ctx.config["programs"]["lm_generate"]
    assert reader(name)(ctx) is None
    ctx = context()
    for key in ("lm.executions", "lm.rows", "lm.padded_rows"):
        del ctx.metrics_window["pipeline"]["counters"][key]
    assert reader(name)(ctx) is None


def test_the_accepted_lm_readers_read_the_new_program():
    """The cell lists the six accepted language-model readers that count
    no bytes: each finds its program and its classes in this
    configuration."""
    ctx = context(lm_s=0.44)
    assert reader("lm_device_s_per_request")(ctx) == pytest.approx(0.44 / 3)
    assert reader("lm_decode_ms_per_token")(ctx) \
        == pytest.approx(440.0 / 64)
    assert reader("lm_mlp_device_s_per_request")(ctx) > 0
    assert reader("lm_attn_device_s_per_request")(ctx) > 0
    assert reader("lm_experts_device_s_per_request")(ctx) \
        == pytest.approx(0.09 / 3)
    assert 0 < reader("lm_share_of_busy_pct")(ctx) < 100
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    listed = {x["name"] for x in m["per_layer"]
              if CELL in x.get("workloads", [])}
    assert {"lm_device_s_per_request", "lm_decode_ms_per_token",
            "lm_mlp_device_s_per_request", "lm_attn_device_s_per_request",
            "lm_experts_device_s_per_request", "lm_share_of_busy_pct",
            *NEW_READERS} <= listed
    assert not {"lm_moe_decode_hbm_roofline_pct",
                "lm_decode_hbm_roofline_pct"} & listed


# --- seconds by phase ------------------------------------------------------------

def _events(with_phases: bool):
    """One chip, one whole execution of ``jit_lm_generate`` (100 us to
    1100 us) with five leaf operations: under ``prefill`` 300 us in two,
    under ``decode`` 500 us in two (inside a ``while`` that contains
    them), and 50 us of the program's own glue under neither."""
    model = "ExaoneMoe" if with_phases else "PanguUltraMoE"

    def path(rest):
        rest = rest if with_phases else rest.split("/", 1)[1]
        return f"jit(lm_generate)/{model}/{rest}"
    names = ["copy.1", "fusion.1", "fusion.2", "while.1", "fusion.3",
             "fusion.4"]
    paths = [f"jit(lm_generate)/{model}/convert",
             path("prefill/moe_layers/while/body/self_attn/q_proj/dot"),
             path("prefill/moe_layers/while/body/mlp/experts/dot"),
             path("decode/while"),
             path("decode/while/body/moe_layers/while/body/mlp/gate/dot"),
             path("decode/while/body/lm_head/dot")]
    start = [100_000, 160_000, 300_000, 500_000, 510_000, 800_000]
    dur = [50_000, 100_000, 200_000, 600_000, 200_000, 300_000]
    ops = {"name": "XLA Ops", "names": names, "paths": paths,
           "name_idx": list(range(6)), "start_ns": start, "dur_ns": dur}
    modules = {"name": "XLA Modules", "names": ["jit_lm_generate(7)",
                                                "jit_core(9)"],
               "name_idx": [1, 0, 1], "start_ns": [0, 100_000, 1_200_000],
               "dur_ns": [50_000, 1_000_000, 50_000]}
    return {"planes": [{"name": "/device:TPU:0", "lines": [modules, ops]}]}


def test_a_programs_seconds_by_phase_stand_beside_its_seconds_by_class():
    from comfyui_distributed_tpu.utils import trace_summary
    got = trace_summary.summarize(_events(True))["programs"]
    program = got["jit_lm_generate"]
    assert program["phases"] == pytest.approx(
        {"prefill": 300e-6, "decode": 500e-6})
    assert program["classes"]["lm_experts"] == pytest.approx(400e-6)
    assert program["classes"]["lm_proj"] == pytest.approx(150e-6)
    assert program["classes"]["lm_head"] == pytest.approx(300e-6)
    # the phases leave out the gaps and what lies under no phase; the
    # classes add up to the execution
    assert sum(program["classes"].values()) == pytest.approx(1000e-6)
    assert sum(program["phases"].values()) == pytest.approx(
        1000e-6 - program["classes"]["gaps"] - 50e-6)


def test_a_program_without_phase_scopes_gets_no_phases():
    """A program whose paths carry no phase (the denoise, the VAE, the
    text encoders; a language model's from before PR 38): its summary row
    has no ``phases`` and its account no ``by_phase``."""
    from comfyui_distributed_tpu.utils import trace_summary
    got = trace_summary.summarize(_events(False))["programs"]
    assert set(got["jit_lm_generate"]) == {"count", "mean_s", "classes",
                                           "top_other", "account"}
    assert "by_phase" not in got["jit_lm_generate"]["account"]
    assert got["jit_lm_generate"]["classes"]["lm_experts"] \
        == pytest.approx(400e-6)


def test_the_shipped_workflow_is_the_configurations_graph():
    """``workflows/prompt-expand-fewshot-txt2img.json`` is what the cell
    times, with PreviewImage where the configuration saves."""
    with open(os.path.join(REPO, "workflows",
                           "prompt-expand-fewshot-txt2img.json")) as f:
        shipped = json.load(f)
    assert "few-shot instructions" in shipped.pop("__doc__")
    graph = config()["graph"]
    assert {nid for nid in graph if graph[nid] != shipped[nid]} == {"9"}
    assert shipped["9"]["class_type"] == "PreviewImage"
    assert set(shipped) == set(graph)
