"""What PR 49 added to the benchmark, tested from outside it (the
benchmark's own test files are not a ``model_config`` PR's to edit): the
configuration against the catalog and against the program, the manifest's
entries by membership, ``lib/lm_scmoe_bytes.py`` against hand counts, the
three readers on a made-up context (with the program's counters, classes
and phases, and on the other six families' programs, which have none of
them, as the parent), the accepted readers on the new program, and the
cell's rehearsal on the CPU.  Since PR 50 the cell's rows start from a
resident snapshot of the operator's instructions: the cell's traffic
against `LanguageModel.shared_prefix`, the rehearsal's window counters,
and the two accepted readers on a window that prefilled 97 positions a row.
"""

import importlib.util
import json
import os
import random
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")
sys.path.insert(0, BENCH)

from lib import lm_scmoe_bytes as scmoe_bytes       # noqa: E402

CELL = "longcat_expand_sd15_512_sat4"
CONFIG = "longcat-flash-omni-expand-sd15-512"
GRANITE = "granite-4.0-h-micro-expand-sd15-512"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ["lm_scmoe_decode_hbm_roofline_pct",
               "lm_scmoe_prefill_flops_util_pct",
               "lm_zero_device_s_per_request"]
OTHER_CONFIGS = ["ouro-2.6b-expand-sd15-512",
                 "pangu-ultra-moe-expand-sd15-512",
                 "k-exaone-236b-expand-sd15-512", GRANITE,
                 "keye-vl-2.0-30b-a3b-expand-sd15-512",
                 "phi-4-mini-flash-expand-sd15-512"]


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lm_cell = _load("chipbench_tests_lm_cell_for_scmoe",
                os.path.join(BENCH, "tests", "test_lm_cell.py"))


def config(name=CONFIG):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def reader(name):
    return _load(f"scmoe_metric_{name}",
                 os.path.join(BENCH, "layer_metrics", name + ".py")).read


# --- the configuration ---------------------------------------------------------

def test_the_configuration_carries_every_published_width_and_its_cut():
    cfg, lm = config(), config()["lm"]
    assert cfg["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert len(cfg["source"]) <= 200
    # every number of the ``lm`` block stands at the top level too, where
    # the driver compares (the block adds the router's width and the range)
    for key, value in lm.items():
        if key not in ("router_outputs", "experts_first"):
            assert cfg[key] == value, key
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"],
            cfg["zero_expert_num"], cfg["moe_topk"],
            cfg["routed_scaling_factor"], cfg["rope_theta"]) == (
        6144, 64, 128, 64, 128, 1536, 512, 12288, 2048, 256, 12, 6, 10000000)
    assert cfg["mla_scale_q_lora"] is True \
        and cfg["mla_scale_kv_lora"] is True \
        and cfg["zero_expert_type"] == "identity"
    assert (cfg["num_layers"], cfg["n_routed_experts"], cfg["vocab_size"],
            lm["router_outputs"], lm["experts_first"]) == (
        4, 16, 16384, 768, 96)
    assert cfg["published"]["num_layers"] == 28 \
        and cfg["published"]["n_routed_experts"] == 512 \
        and cfg["published"]["vocab_size"] == 131072
    assert cfg["held_here"] == {
        "layers": 4, "routed_experts": [96, 112], "zero_experts": 256,
        "router_outputs": 768, "vocabulary_rows": 16384,
        "chips_sharing_a_layer": 32}
    assert "32 chips share each layer" in cfg["deployment"] \
        and "nothing stands in for the absent chips" in cfg["deployment"]
    # the floors: 4 layers, at least 8 experts, an eighth of the vocabulary
    assert cfg["num_layers"] >= 4 and cfg["n_routed_experts"] >= 8 \
        and cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    if os.path.isfile(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            rows = [json.loads(line) for line in f]
        (row,) = [r for r in rows if r["name"] == "LongCat-Flash-Omni"]
        assert cfg["source"] == row["source_url"]
        assert {k for k, v in row["config"].items()
                if k not in cfg or cfg[k] != v} == set(cfg["reduced"])
        # and no width is among the reduced
        assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
            k: row["config"][k] for k in cfg["reduced"]}


@pytest.mark.parametrize("reading", [
    "the expert layer reads the FIRST sub-layer's normed output",
    "sqrt(hidden_size / q_lora_rank) = 2",
    "sqrt(hidden_size / kv_lora_rank) = 3.4641", "NOT the rotary key k_r",
    "The cache holds the SCALED latent", "softmax scale 1/sqrt(128 + 64)",
    "rope_theta 1e7, no scaling", "hidden_act silu",
    "softmax over ALL 768 outputs", "the bias moving the SELECTION only",
    "NO renormalisation (norm_topk_prob false)", "router_bias false",
    "ONE scaled add", "N(0, (1/768)^2)", "tie_word_embeddings false",
    "kv_a_layernorm's 0.5 x that", "96..111 (chip 6 of 32)",
    "groups of at most 4,096 positions", "hashed-id tokenizer"])
def test_every_assumed_reading_is_written_in_the_file(reading):
    assert any(reading in a for a in config()["assumed"]), reading


def test_the_configuration_file_stays_a_file_the_driver_reads():
    path = os.path.join(BENCH, "configs", CONFIG + ".json")
    with open(path, "rb") as f:
        raw = f.read()
    assert len(raw) < 65_536
    raw.decode("ascii")

    def strict(pairs):
        keys = [k for k, _ in pairs]
        assert len(keys) == len(set(keys)), keys
        return dict(pairs)

    def constant(name):
        raise AssertionError(name)      # NaN, Infinity: not JSON

    assert isinstance(json.loads(raw, object_pairs_hook=strict,
                                 parse_constant=constant), dict)


def test_the_graph_is_granites_with_another_model_behind_the_same_prompt():
    """Two families behind one operator's prompt: the generate node, its
    1,950 instruction ids among it, bit for bit."""
    cfg, other = config(), config(GRANITE)
    assert set(cfg["graph"]) == set(other["graph"])
    assert {nid for nid in cfg["graph"]
            if cfg["graph"][nid] != other["graph"][nid]} == {"20"}
    assert cfg["graph"]["20"]["inputs"] == {
        "model_name": "longcat-flash-omni.safetensors"}
    node = cfg["graph"]["21"]["inputs"]
    assert (node["prompt_tokens"], node["max_new_tokens"],
            node["temperature"]) == (2048, 64, 0.0)
    assert len(node["instructions"].split()) == 1950
    for key in ("programs", "unet", "vary", "text_encoders", "vae"):
        assert cfg[key] == other[key], key
    assert cfg["trace_slice"]["after_counter"] == "lm.executions" \
        and cfg["trace_slice"]["requests"] == \
        other["trace_slice"]["requests"]
    steps = {tuple(s[:2]): s[2] for s in cfg["rehearsal"]["set"]}
    assert steps[("3", "steps")] == 40      # four POSTs share an execution
    from comfyui_distributed_tpu.models import registry, tokenizer
    tok = tokenizer.make_lm_tokenizer(None, 16384)
    ids = tok.encode(f"{node['instructions']} "
                     + registry.EXPAND_TEMPLATE.format(text="a " * 12))
    assert 1950 + 12 < len(ids) <= 2048 and max(ids) < 16384


def test_the_program_serves_what_the_configuration_states():
    import dataclasses
    from comfyui_distributed_tpu.models import mla_scmoe, registry
    cfg, lm, full = config(), config()["lm"], \
        mla_scmoe.LONGCAT_FLASH_OMNI_SHARE
    for key in (f.name for f in dataclasses.fields(full)):
        if key in ("dtype", "experts_held", "n_routed_experts"):
            continue
        assert getattr(full, key) == lm[key], key
    # the file's key counts the experts HELD (it is reduced); the program's
    # the router's real outputs, the published count
    assert full.experts_held == lm["n_routed_experts"] == 16
    assert full.n_routed_experts == cfg["published"]["n_routed_experts"]
    assert full.router_outputs == lm["router_outputs"] \
        == cfg["published"]["n_routed_experts"] + lm["zero_expert_num"]
    # the bytes of the benchmark count the program's tree
    sizes = cfg["sizes"]
    assert mla_scmoe.param_count(full) == sizes["param_count"] \
        == 5_172_749_312 == sizes["layers_held"] \
        + sizes["embedding_and_head_slices"] + sizes["final_norm"]
    assert sizes["bytes_bf16"] == 2 * sizes["param_count"]
    assert mla_scmoe.published_param_count(full, 28, 131072) \
        == sizes["published_param_count"] == 560_664_980_480
    assert scmoe_bytes.attention_params(lm) == sizes["one_latent_attention"]
    assert scmoe_bytes.dense_mlp_params(lm) == sizes["one_dense_mlp"]
    assert scmoe_bytes.expert_params(lm) == sizes["one_expert"]
    assert 2 * scmoe_bytes.resident_params(lm) \
        == sizes["non_expert_bytes_a_decode_step"]
    # what the program holds and a step does not read whole: the experts
    # and the embedding (a row an id)
    assert mla_scmoe.param_count(full) == scmoe_bytes.resident_params(lm) \
        + 4 * 16 * sizes["one_expert"] + 16384 * 6144
    assert mla_scmoe.kv_cache_bytes(full, 1, 1) \
        == sizes["kv_cache_bytes_a_position_a_row"] == 9216 \
        == scmoe_bytes.attentions(lm) * scmoe_bytes.latent_bytes(lm)
    assert mla_scmoe.kv_cache_bytes(full, 1, 2112) \
        == sizes["kv_cache_bytes_a_row_at_2112"]
    nodes = {n["class_type"]: n["inputs"] for n in cfg["graph"].values()}
    assert registry.detect_lm_family(
        nodes["LanguageModelLoader"]["model_name"]) == ("longcat", "full")


# --- the manifest, by membership ---------------------------------------------------

def test_the_manifest_gained_one_configuration_one_cell_and_three_readers():
    m = manifest()
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert entry == m["configs"][-1]
    assert entry["reduced"] == config()["reduced"]
    assert entry["file"] == f"benchmarks/chip/configs/{CONFIG}.json"
    assert entry["source"] == config()["source"]
    assert len(entry["why"]) <= 200
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert cell == m["workloads"][-1]
    assert {k: cell[k] for k in ("config", "traffic", "chips")} == {
        "config": CONFIG, "traffic": "closed4_unique", "chips": 1}
    assert len(cell["why"]) <= 200 and "2048-id prefill" in cell["why"] \
        and "256 zero experts" in cell["why"]
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == \
        ["sdxl_1024_fanout4"]
    assert len(m["workloads"]) == 12 and len(m["configs"]) == 9
    assert {x["name"] for x in m["end_to_end"]
            if CELL in x.get("workloads", [CELL])} == {
        "images_per_s", "tti_p50_s", "setup_s"}
    new = [x for x in m["per_layer"] if x["name"] in NEW_READERS]
    assert [x["name"] for x in new] == NEW_READERS == \
        [x["name"] for x in m["per_layer"][53:56]]
    for x in new:
        assert x["workloads"] == [CELL] and x["layer"] == "Language model" \
            and x["source"] == "device_trace" \
            and x["moves"] == "images_per_s"
        assert set(x) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           x["name"] + ".py"))
    by_name = {x["name"]: x for x in new}
    for name in NEW_READERS[:2]:
        assert (by_name[name]["unit"], by_name[name]["better"]) == \
            ("%", "higher")
    assert (by_name[NEW_READERS[2]]["unit"],
            by_name[NEW_READERS[2]]["better"]) == ("s", "lower")
    with open(os.path.join(REPO, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) <= 64 * 1024
    # appended: the cell stands last in every list it is in
    for group in ("end_to_end", "per_layer"):
        for x in m[group]:
            if CELL in x.get("workloads", []):
                assert x["workloads"][-1] == CELL, x["name"]


# --- bytes and FLOPs from shapes ---------------------------------------------------

def test_decode_bytes_against_hand_counts():
    lm = config()["lm"]
    assert scmoe_bytes.attention_params(lm) == 90_572_800
    assert scmoe_bytes.dense_mlp_params(lm) == 226_492_416
    assert scmoe_bytes.expert_params(lm) == 37_748_736      # 75.5 MB
    assert scmoe_bytes.latent_bytes(lm) == 1152
    assert scmoe_bytes.layer_matrices(lm) == 4 * (
        2 * 90_572_800 + 2 * 226_492_416 + 6144 * 768)
    resident = 4 * 638_874_368 + 6144 + 6144 * 16384
    assert scmoe_bytes.resident_params(lm) == resident == 2_656_166_912
    # the layers' 5.11 GB and the head's 0.20
    assert 2 * 4 * 638_874_368 / 1e9 == pytest.approx(5.11, abs=0.005)
    assert 2 * 6144 * 16384 / 1e9 == pytest.approx(0.20, abs=0.005)
    # one row, nothing attended to, no expert hit: the weights, its
    # embedding row, the eight latents it writes
    assert scmoe_bytes.decode_bytes_per_step(lm) == 2 * resident \
        + 2 * 6144 + 8 * 1152
    # four program rows whose three real ones see 2,080 keys in each of
    # eight slots, 0.7 experts hit; the zero experts move NOTHING
    keys = 3 * 8 * 2080
    step = scmoe_bytes.decode_bytes_per_step(lm, 4.0, keys, 0.7)
    assert step == 2 * (resident + 0.7 * 37_748_736) \
        + 4 * (2 * 6144 + 8 * 1152) + keys * 1152
    assert step / 1e9 == pytest.approx(5.42, abs=0.01)
    # had each attention a per-head cache (64 heads of 192 + 128)
    assert keys * 64 * 320 * 2 / 1e9 == pytest.approx(2.04, abs=0.01)


def test_prefill_flops_against_hand_counts():
    lm = config()["lm"]
    positions, rows, real = 4 * 2048, 4.0, 1990
    keys = 4 * 8 * real * (real + 1) / 2
    local = positions * 4 * 12 * 16 / 768
    products = 2.0 * scmoe_bytes.layer_matrices(lm) * positions
    experts = 2.0 * 37_748_736 * local
    attention = 2.0 * 64 * (128 + 64 + 128) * keys
    head = 2.0 * 6144 * 16384 * rows
    assert scmoe_bytes.pair_flops(lm) == 40960
    assert scmoe_bytes.prefill_flops(lm, positions, rows, keys, local) \
        == products + experts + attention + head
    assert (products / 1e12, experts / 1e12, attention / 1e12) == (
        pytest.approx(41.87, abs=0.01), pytest.approx(0.618, abs=0.001),
        pytest.approx(2.60, abs=0.01))
    # a prefill that computed every pair it routed, absent experts too,
    # would do 48 times the experts' work; the zero experts none
    assert 768 / 16 == 48


# --- the readers -------------------------------------------------------------------

def context(classes=True, counted=True, account=True, requests=21, rows=3,
            padded=1, name=CONFIG, **kw):
    """test_lm_cell's made-up window with this cell's configuration and,
    with ``counted`` / ``classes`` / ``account``, what this family's
    program counts and the classes and the account by phase its trace
    summary has."""
    ctx = lm_cell.context(requests=requests, rows=rows, padded=padded, **kw)
    ctx.config = config(name)
    counters = ctx.metrics_window["pipeline"]["counters"]
    counters["lm.prompt_tokens"] = 1990 * requests
    executions = counters["lm.executions"]
    if counted:
        counters.update({
            "lm.prefill_positions": executions * 4 * 2048,
            "lm.keys_attended": requests * 8 * sum(
                1990 + i + 1 for i in range(64)),
            "lm.keys_attended_prefill": executions * 4 * 8
            * 1990 * 1991 // 2,
            "lm.expert_pairs": requests * 64 * 4 * 12,
            "lm.expert_pairs_local": requests * 64,
            "lm.expert_pairs_zero": requests * 64 * 4 * 4,
            "lm.expert_hits": executions * 64 * 3,
            "lm.expert_pairs_dropped": 0,
            "lm.expert_pairs_local_prefill": executions * 8192,
            "lm.expert_pairs_zero_prefill": executions * 131072,
            "lm.expert_rows_computed_prefill": executions * 16384})
    prof = ctx.metrics_window.get("profile")
    if prof:
        program = prof["programs"]["jit_lm_generate"]
        if classes:
            program["classes"].update(lm_experts=0.04, lm_zero=0.0021)
        if account:
            program["phases"] = {"prefill": 0.55, "decode": 0.56}
            program["account"] = {"by_class": {}, "by_phase": {
                "prefill": {"lm_proj": 0.2, "lm_mlp": 0.25, "lm_attn": 0.05,
                            "lm_experts": 0.03,
                            "lm_zero": 0.001 if classes else 0.0,
                            "idle": 0.02},
                "decode": {"lm_proj": 0.2, "lm_mlp": 0.3, "lm_attn": 0.02,
                           "lm_experts": 0.02, "lm_head": 0.015,
                           "idle": 0.005}}}
    return ctx


def test_the_zero_experts_reader_is_its_class_seconds_per_request():
    ctx = context()
    assert reader("lm_zero_device_s_per_request")(ctx) \
        == pytest.approx(0.0021 / 3)
    # and the experts' accepted readers read this family's classes
    assert reader("lm_experts_device_s_per_request")(ctx) \
        == pytest.approx(0.04 / 3)
    assert reader("lm_prefill_experts_device_s_per_request")(ctx) \
        == pytest.approx(0.03 / 3)


def test_the_roofline_reader_counts_the_experts_hit_and_the_keys_attended():
    ctx = context()
    lm = ctx.config["lm"]
    # 3 requests in 4 program rows, 64 steps of the decode phase's WALL
    # seconds; a step: 3 experts hit, what the three real rows saw in
    # eight slots at the window's mean position
    keys = 3 * 8 * sum(1990 + i + 1 for i in range(64)) / 64
    nbytes = scmoe_bytes.decode_bytes_per_step(lm, 4.0, keys, 3.0)
    want = 100.0 * (nbytes / 819e9) / (0.56 / 64)
    assert reader("lm_scmoe_decode_hbm_roofline_pct")(ctx) \
        == pytest.approx(want)
    assert 75 < want < 80 and want < 100
    # the counters decide, not the shapes: fewer hits, fewer bytes; and a
    # pair to a zero expert moves nothing
    counters = ctx.metrics_window["pipeline"]["counters"]
    counters["lm.expert_hits"] //= 3
    fewer = reader("lm_scmoe_decode_hbm_roofline_pct")(ctx)
    assert want - 3 < fewer < want - 2
    counters["lm.expert_pairs_zero"] *= 2
    assert reader("lm_scmoe_decode_hbm_roofline_pct")(ctx) == fewer


def test_the_utilisation_reader_counts_what_the_program_counted():
    ctx = context()
    flops = scmoe_bytes.prefill_flops(
        ctx.config["lm"], 4 * 2048, 4.0, 4 * 8 * 1990 * 1991 // 2, 8192)
    want = 100.0 * flops / 0.551 / 197e12         # the phase, idle too
    assert reader("lm_scmoe_prefill_flops_util_pct")(ctx) \
        == pytest.approx(want)
    assert 40 < want < 43
    # a resident prefix: a tenth of the positions computed is a tenth of
    # the products, whatever `prompt_tokens` says
    counters = ctx.metrics_window["pipeline"]["counters"]
    counters["lm.prefill_positions"] //= 10
    assert reader("lm_scmoe_prefill_flops_util_pct")(ctx) < 0.2 * want


def test_both_accepted_readers_read_a_window_behind_a_snapshot():
    """The window's counters as the cell's executions leave them since
    PR 50 (4 program rows x 97 positions, a row's ~39 own queries against
    the 1,951 prefix keys and their causal part in eight attentions, the
    routers over 388 positions) under a prefill of a twentieth of the
    seconds: both readers divide by what the PROGRAM counted, so neither
    falls silent and neither passes 100."""
    ctx = context()
    counters = ctx.metrics_window["pipeline"]["counters"]
    executions = counters["lm.executions"]
    keys = 4 * 8 * sum(1951 + j + 1 for j in range(39))
    counters.update({
        "lm.prefill_positions": executions * 4 * 97,
        "lm.keys_attended_prefill": executions * keys,
        "lm.expert_pairs_local_prefill": executions * 388,
        "lm.expert_pairs_zero_prefill": executions * 6200,
        "lm.expert_rows_computed_prefill": executions * 2048,
        "lm.prefix_hits": counters["lm.rows"],
        "lm.prefix_positions_served": counters["lm.rows"] * 1951})
    program = ctx.metrics_window["profile"]["programs"]["jit_lm_generate"]
    program["phases"]["prefill"] = 0.026
    program["account"]["by_phase"]["prefill"] = {
        "lm_proj": 0.006, "lm_mlp": 0.012, "lm_attn": 0.004,
        "lm_experts": 0.002, "lm_cache": 0.001, "idle": 0.001}
    util = reader("lm_scmoe_prefill_flops_util_pct")(ctx)
    flops = scmoe_bytes.prefill_flops(ctx.config["lm"], 4 * 97, 4.0, keys,
                                      388)
    assert util == pytest.approx(100.0 * flops / 0.026 / 197e12)
    assert 0 < util < 100
    # 2.0 TFLOP: 388 positions x 2.55 G values x 2 and the attention's part
    assert 2.0e12 < flops < 2.3e12
    assert 0 < reader("lm_scmoe_decode_hbm_roofline_pct")(ctx) < 100


@pytest.mark.parametrize("other", OTHER_CONFIGS)
@pytest.mark.parametrize("name", NEW_READERS)
def test_the_new_readers_give_nothing_on_the_other_families_programs(
        name, other):
    """The other six families' programs, and the parent's, have no such
    class and count no such thing: the readers give nothing and do not
    raise; nor without a trace, a profile, an account or the program's
    pattern."""
    nothing = dict(classes=False, counted=False)
    ctx = context(name=other, **nothing)
    counters = ctx.metrics_window["pipeline"]["counters"]
    if "pangu" in other or "exaone" in other or "keye" in other:
        counters.update({"lm.expert_hits": 100, "lm.expert_pairs": 4000,
                         "lm.expert_pairs_local": 300})
    if "granite" in other or "keye" in other or "phi" in other:
        counters.update({"lm.prefill_positions": 4 * 2048})
    assert reader(name)(ctx) is None
    assert reader(name)(context(**nothing)) is None
    assert reader(name)(context(account=False, **nothing)) is None
    assert reader(name)(context(traced=False)) is None
    assert reader(name)(context(profile=False)) is None
    ctx = context()
    del ctx.config["programs"]["lm_generate"]
    assert reader(name)(ctx) is None
    ctx = context()
    for key in ("lm.executions", "lm.rows", "lm.padded_rows"):
        del ctx.metrics_window["pipeline"]["counters"][key]
    assert reader(name)(ctx) is None
    # one source there and the other not
    if name.endswith("_pct"):
        assert reader(name)(context(counted=False)) is None
        assert reader(name)(context(account=False)) is None
    else:
        assert reader(name)(context(classes=False)) is None


def test_the_accepted_lm_readers_read_the_new_program():
    ctx = context(lm_s=1.11)
    assert reader("lm_device_s_per_request")(ctx) == pytest.approx(1.11 / 3)
    assert reader("lm_decode_ms_per_token")(ctx) \
        == pytest.approx(1110.0 / 64)
    assert reader("lm_mlp_device_s_per_request")(ctx) is not None
    assert reader("lm_attn_device_s_per_request")(ctx) > 0
    assert reader("lm_decode_step_ms")(ctx) == pytest.approx(8.75)
    assert reader("lm_prefill_device_s_per_request")(ctx) \
        == pytest.approx(0.55 / 3)
    assert reader("lm_prefill_attn_device_s_per_request")(ctx) \
        == pytest.approx((0.2 + 0.05) / 3)
    assert 0 < reader("lm_share_of_busy_pct")(ctx) < 100


# --- the cell, rehearsed --------------------------------------------------------------

def _env():
    env = {k: v for k, v in os.environ.items() if not k.startswith("DTPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_the_cell_rehearses_on_the_cpu(tmp_path):
    """``run.py --rehearse`` of the new cell: a tiny model of THIS family
    behind the same nodes, hand-over and drain wait, every request
    served, nothing compiled in the window, the program's counters on the
    window's record: the three kinds of pair, the positions, the keys."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 49), "--seconds", "4", "--trace", "0",
         "--rehearse", "--out", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO, env=_env(), timeout=600)
    assert p.returncode == 0, p.stderr[-3000:] + p.stdout[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"images_per_s", "tti_p50_s", "setup_s"}
    with open(tmp_path / "run.json") as f:
        run = json.load(f)
    assert run["all_metrics"]["compiles_in_window"]["value"] == 0
    counters = run["window_counters"]
    assert counters["lm.executions"] >= 2
    rows = counters["lm.rows"] + counters["lm.padded_rows"]
    # the tiny family: 2 layers, top-4, 4 new tokens behind 48 positions
    assert counters["lm.expert_pairs"] == counters["lm.rows"] * 4 * 2 * 4
    assert 0 < counters["lm.expert_pairs_zero"] < counters["lm.expert_pairs"]
    assert counters["lm.expert_pairs_local"] \
        + counters["lm.expert_pairs_zero"] <= counters["lm.expert_pairs"]
    assert counters["lm.expert_pairs_dropped"] == 0
    # every row starts from the snapshot of the rehearsal's instructions
    # (14 ids with the first; a rotary key is rotated from a row's first
    # real id, so the snapshot stands at each row's offset) and the 34
    # positions behind them are computed, not 48
    held = 14
    assert counters["lm.prefix_hits"] == counters["lm.rows"]
    assert counters["lm.prefix_positions_served"] \
        == counters["lm.rows"] * held
    assert "lm.prefix_misses" not in counters      # made by the warm-ups
    assert "lm.prefix_evictions" not in counters
    assert counters["lm.prefill_positions"] == rows * (48 - held)
    assert counters["lm.expert_pairs_local_prefill"] \
        + counters["lm.expert_pairs_zero_prefill"] \
        <= rows * (48 - held) * 2 * 4
    real = counters["lm.prompt_tokens"]
    assert counters["lm.keys_attended"] == 4 * (
        4 * real + counters["lm.rows"] * (1 + 2 + 3 + 4))
    # a row's n own queries see the 14 prefix keys and their causal part:
    # at least the real rows' (a padded row repeats one of them)
    assert counters["lm.keys_attended_prefill"] >= 4 * held * (
        real - counters["lm.rows"] * held)


def test_the_cells_instructions_are_a_true_prefix_of_every_request():
    """What `LanguageModel.shared_prefix` needs of the CELL's traffic, at
    the published vocabulary slice: the operator's instructions encode to
    1,951 ids (the start id and 1,950 words) that are the first ids of
    every request's prompt, with 1 to 97 ids of the row's own behind them
    inside the 2048 positions.  A silent fall-back to the whole prompt
    would fail here, not only on the chip."""
    import numpy as np
    from comfyui_distributed_tpu.models import mla_scmoe, registry, tokenizer
    node = config()["graph"]["21"]["inputs"]
    model = registry.LanguageModel(
        "longcat-flash-omni.safetensors", mla_scmoe.LONGCAT_FLASH_OMNI_SHARE,
        None, tokenizer.make_lm_tokenizer(None, 16384), "longcat")
    with open(os.path.join(BENCH, "traffic", "words.txt")) as f:
        words = [w.strip() for w in f if w.strip()]
    rng = random.Random(7)
    rows = [registry.LMRow(" ".join(rng.choice(words) for _ in range(12)),
                           i, instructions=node["instructions"])
            for i in range(4)]
    prefix = model.shared_prefix(rows, node["prompt_tokens"])
    assert prefix is not None and len(prefix) == 1951
    for row in rows:
        ids = model.prompt_ids(row.text, node["prompt_tokens"],
                               row.instructions)
        assert np.array_equal(ids[:1951], prefix)
        assert 1 <= len(ids) - 1951 <= node["prompt_tokens"] - 1951 == 97
    # one row with other instructions, and the execution runs whole
    other = [*rows[:3], registry.LMRow("a cat", 3, instructions="draw it")]
    assert model.shared_prefix(other, node["prompt_tokens"]) is None
    assert mla_scmoe.prefix_bytes(model.cfg, 1951) == 18_355_008 \
        == 1951 * (9216 + 4 * 12 * 4)
