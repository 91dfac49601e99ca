"""What PR 42 added to the benchmark, tested from outside it (the
benchmark's own test files are not a ``model_config`` PR's to edit): the
configuration against the catalog and against the program, the manifest's
entries by membership, ``lib/lm_dsa_moe_bytes.py`` against hand counts,
the four readers on a made-up context (with the program's counters,
classes and phases, and on the other four families' programs, which have
none of them, as the parent), the accepted readers on the new program,
and the cell's rehearsal on the CPU.
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

from lib import lm_dsa_moe_bytes as dsa_bytes       # noqa: E402

CELL = "keye_expand_sd15_512_sat4"
GRANITE4 = "granite_expand_sd15_512_sat4"
CONFIG = "keye-vl-2.0-30b-a3b-expand-sd15-512"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ["lm_index_device_s_per_request",
               "lm_prefill_index_device_s_per_request",
               "lm_dsa_decode_hbm_roofline_pct",
               "lm_dsa_prefill_flops_util_pct"]
# granite's counts: a recurrent state's bytes and a recurrence's FLOPs
NOT_THIS_FAMILYS = {"lm_ssm_device_s_per_request",
                    "lm_prefill_ssm_device_s_per_request",
                    "lm_ssm_decode_hbm_roofline_pct",
                    "lm_ssm_prefill_flops_util_pct"}
EXPERT_READERS = {"lm_experts_device_s_per_request",
                  "lm_prefill_experts_device_s_per_request"}


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lm_cell = _load("chipbench_tests_lm_cell_for_dsa_moe",
                os.path.join(BENCH, "tests", "test_lm_cell.py"))


def config(name=CONFIG):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def reader(name):
    return _load(f"dsa_moe_metric_{name}",
                 os.path.join(BENCH, "layer_metrics", name + ".py")).read


# --- the configuration ---------------------------------------------------------

def test_the_configuration_holds_every_published_value_and_cuts_depth_alone():
    cfg, lm = config(), config()["lm"]
    assert cfg["reduced"] == ["num_hidden_layers"] and len(cfg["assumed"]) >= 14
    assert any("HEAD NORMS' GAINS" in a for a in cfg["assumed"])
    assert any("lightning indexer" in a for a in cfg["assumed"])
    assert "vision tower" in cfg["not_held"]
    assert len(cfg["source"]) <= 200
    # every number stands at the top level too, where the driver compares
    for key, value in lm.items():
        assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 6 >= 4
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["held_here"] == {
        "blocks": 6, "routed_experts": [0, 128], "vocabulary_rows": 151936,
        "pipeline_stages": 8, "this_stage": 0, "chips_sharing_a_layer": 1}
    # every published width unchanged
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 32, 4, 128)
    assert (cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_local_experts"], cfg["num_experts_per_tok"]) == \
        (768, 128, 128, 8)
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert (cfg["vocab_size"], cfg["rope_theta"]) == (151936, 10_000_000)
    assert cfg["sizes"]["param_count"] == 4_374_622_464
    if os.path.isfile(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            rows = [json.loads(line) for line in f]
        (row,) = [r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B"]
        assert cfg["source"] == row["source_url"]
        assert {k for k, v in row["config"].items()
                if k not in cfg or cfg[k] != v} == {"num_hidden_layers"}


def test_the_configuration_file_stays_a_file_the_driver_reads():
    """The driver refused the file at 66,011 bytes ("not a file that holds
    a JSON object": it parses here, so the size it reads is bounded; the
    manifest's own bound is 64 KiB).  The instructions alone are 51,467
    bytes and stay written out; the prose beside them is what gives."""
    path = os.path.join(BENCH, "configs", CONFIG + ".json")
    with open(path, "rb") as f:
        raw = f.read()
    assert len(raw) < 64_000
    raw.decode("ascii")

    def strict(pairs):
        keys = [k for k, _ in pairs]
        assert len(keys) == len(set(keys)), keys
        return dict(pairs)

    def constant(name):
        raise AssertionError(name)      # NaN, Infinity: not JSON

    assert isinstance(json.loads(raw, object_pairs_hook=strict,
                                 parse_constant=constant), dict)


def test_the_instructions_are_written_out_and_fill_the_prompt_buffer():
    """270 few-shot examples of 30 words, drawn once from the benchmark's
    words with ``random.Random(42)``: 8,100 ids in front of the template
    and the user's 12 words, inside the 8192 positions."""
    node = config()["graph"]["21"]["inputs"]
    assert (node["prompt_tokens"], node["max_new_tokens"],
            node["temperature"]) == (8192, 64, 0.0)
    with open(os.path.join(BENCH, "traffic", "words.txt")) as f:
        words = [w.strip() for w in f if w.strip()]
    rng, shots = random.Random(42), []
    for _ in range(270):
        shots += ["example", "prompt"] + [rng.choice(words)
                                          for _ in range(4)] \
            + ["detailed", "prompt"] + [rng.choice(words) for _ in range(22)]
    assert node["instructions"] == " ".join(shots)
    assert len(shots) == 8100
    from comfyui_distributed_tpu.models import registry, tokenizer
    tok = tokenizer.make_lm_tokenizer(None, 151936)
    text = " ".join(words[:12])
    ids = tok.encode(f"{node['instructions']} "
                     + registry.EXPAND_TEMPLATE.format(text=text))
    assert 8100 + 12 < len(ids) <= 8192 and max(ids) < 151936
    assert 8120 <= len(ids) <= 8140
    # three quarters of the prefill's queries see more than 2,048 keys
    assert (len(ids) - 2048) / len(ids) > 0.74


def test_the_graph_is_granites_with_another_model_and_a_longer_prompt():
    cfg, other = config(), config("granite-4.0-h-micro-expand-sd15-512")
    assert set(cfg["graph"]) == set(other["graph"])
    assert {nid for nid in cfg["graph"]
            if cfg["graph"][nid] != other["graph"][nid]} == {"20", "21"}
    assert cfg["graph"]["20"]["inputs"] == {
        "model_name": "keye-vl-2.0-30b-a3b.safetensors"}
    a, b = cfg["graph"]["21"]["inputs"], other["graph"]["21"]["inputs"]
    assert {k for k in a if a[k] != b[k]} == {"prompt_tokens",
                                              "instructions"}
    for key in ("programs", "unet", "vary", "text_encoders", "vae"):
        assert cfg[key] == other[key], key
    assert cfg["trace_slice"]["after_counter"] == "lm.executions"
    # (the warm-up request times the 1-row program, a third of what the
    # window's 4-row executions take: 4 requests of room, not 2.25)
    assert cfg["trace_slice"]["requests"] == 4.0
    assert [s[:2] for s in cfg["rehearsal"]["set"]] == \
        [s[:2] for s in other["rehearsal"]["set"]]


def test_the_program_serves_what_the_configuration_states():
    from comfyui_distributed_tpu.models import dsa_moe, registry
    lm, full = config()["lm"], dsa_moe.KEYE_VL2_STAGE
    same = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "num_hidden_layers", "head_dim", "moe_intermediate_size",
            "num_experts", "num_experts_per_tok", "norm_topk_prob",
            "rms_norm_eps", "rope_theta", "vocab_size")
    for key in same:
        assert getattr(full, key) == lm[key], key
    sa = lm["sa_config"]
    assert (full.indexer_num_heads, full.indexer_head_dim, full.topk,
            full.q_chunk_size) == (sa["indexer_num_heads"],
                                   sa["indexer_head_dim"], sa["topk"],
                                   sa["q_chunk_size"])
    assert list(full.mrope_section) == lm["rope_scaling"]["mrope_section"]
    assert lm["tie_word_embeddings"] is False and lm["mlp_only_layers"] == []
    assert lm["decoder_sparse_step"] == 1
    # the bytes of the benchmark count the program's tree
    sizes = config()["sizes"]
    assert dsa_moe.param_count(full) == sizes["param_count"] \
        == 6 * sizes["block"]["block"] + sizes["embedding_and_head"] \
        + sizes["final_norm"]
    assert sizes["bytes_bf16"] == 2 * sizes["param_count"]
    assert dsa_bytes.resident_params(lm) + 6 * 128 * \
        dsa_bytes.expert_params(lm) + lm["hidden_size"] * lm["vocab_size"] \
        == sizes["param_count"]             # the embedding is not read whole
    assert dsa_bytes.expert_params(lm) == sizes["block"]["one_expert"]
    assert 6 * (dsa_bytes.key_bytes(lm) + dsa_bytes.index_key_bytes(lm)) \
        == sizes["cache_bytes_a_position_a_row"] == 13_056
    assert dsa_moe.kv_cache_bytes(full, 1, 8256) \
        == sizes["cache_bytes_a_row_at_8256"]
    assert dsa_moe.kv_cache_bytes_by_kind(full, 1, 8256)["index_keys"] \
        == sizes["index_key_cache_bytes_a_row_at_8256"]
    nodes = {n["class_type"]: n["inputs"] for n in config()["graph"].values()}
    assert registry.detect_lm_family(
        nodes["LanguageModelLoader"]["model_name"]) == ("keye", "full")


# --- the manifest, by membership ---------------------------------------------------

def test_the_manifest_gained_one_configuration_one_cell_and_four_readers():
    m = manifest()
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert entry == m["configs"][6]
    assert entry["reduced"] == ["num_hidden_layers"] == config()["reduced"]
    assert entry["file"] == f"benchmarks/chip/configs/{CONFIG}.json"
    assert entry["source"] == config()["source"]
    assert len(entry["why"]) <= 200
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert cell == m["workloads"][9]
    assert {k: cell[k] for k in ("config", "traffic", "chips")} == {
        "config": CONFIG, "traffic": "closed4_unique", "chips": 1}
    assert len(cell["why"]) <= 200 and "8192-id prefill" in cell["why"]
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == \
        ["sdxl_1024_fanout4"]
    # (what PR 46 added stands behind them)
    assert len(m["workloads"]) >= 10 and len(m["configs"]) >= 7
    assert {x["name"] for x in m["end_to_end"]
            if CELL in x.get("workloads", [CELL])} == {
        "images_per_s", "tti_p50_s", "setup_s"}
    new = [x for x in m["per_layer"] if x["name"] in NEW_READERS]
    assert [x["name"] for x in new] == NEW_READERS == \
        [x["name"] for x in m["per_layer"][45:49]]
    for x in new:
        assert x["workloads"] == [CELL] and x["layer"] == "Language model" \
            and x["source"] == "device_trace"
        assert set(x) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           x["name"] + ".py"))
    by_name = {x["name"]: x for x in new}
    assert (by_name["lm_index_device_s_per_request"]["moves"],
            by_name["lm_prefill_index_device_s_per_request"]["moves"]) == \
        ("images_per_s", "tti_p50_s")
    for name in NEW_READERS[:2]:
        assert (by_name[name]["unit"], by_name[name]["better"]) == \
            ("s", "lower")
    for name in NEW_READERS[2:]:
        assert (by_name[name]["unit"], by_name[name]["better"],
                by_name[name]["moves"]) == ("%", "higher", "images_per_s")
    with open(os.path.join(REPO, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) <= 64 * 1024


def test_the_cell_is_appended_where_the_reader_is_family_neutral():
    """It stands behind granite's cell in every list that cell is in, but
    for the four whose counts are that family's, and behind K-EXAONE's in
    the experts' two."""
    m = manifest()

    def listed(cell):
        return {x["name"] for g in ("end_to_end", "per_layer")
                for x in m[g] if cell in x.get("workloads", [])}
    assert listed(GRANITE4) - listed(CELL) == NOT_THIS_FAMILYS
    assert listed(CELL) - listed(GRANITE4) == set(NEW_READERS) \
        | EXPERT_READERS
    for group in ("end_to_end", "per_layer"):
        for x in m[group]:
            cells = x.get("workloads", [])
            if CELL in cells:       # only later PRs' cells behind it
                order = [w["name"] for w in m["workloads"]]
                assert all(order.index(c) > order.index(CELL)
                           for c in cells[cells.index(CELL) + 1:]), x["name"]
    neutral = {"lm_device_s_per_request", "lm_decode_ms_per_token",
               "lm_share_of_busy_pct", "lm_mlp_device_s_per_request",
               "lm_attn_device_s_per_request",
               "lm_prefill_device_s_per_request", "lm_decode_step_ms",
               "lm_prefill_attn_device_s_per_request", "peak_hbm_gb",
               "compiles_in_window", "device_idle_pct",
               "denoise_device_s_per_image", "vae_device_s_per_image",
               "clip_device_ms_per_request", "setup_weights_s",
               "setup_trace_compile_s", "dispatch_host_ms_per_request"}
    assert neutral <= listed(CELL)


# --- bytes and FLOPs from shapes ---------------------------------------------------

def test_decode_bytes_against_hand_counts():
    lm = config()["lm"]
    assert dsa_bytes.attention_params(lm) == 18_874_368
    assert dsa_bytes.indexer_matrices(lm) == 2_260_992
    assert dsa_bytes.expert_params(lm) == 4_718_592         # 9,437,184 B
    assert dsa_bytes.key_bytes(lm) == 2048                  # 2 KiB a key
    assert dsa_bytes.index_key_bytes(lm) == 128
    matrices = 6 * (18_874_368 + 2_260_992 + 2048 * 128)
    assert dsa_bytes.block_matrices(lm) == matrices == 128_385_024
    resident = matrices + 6 * 2 * (2048 + 128 + 64) + 2048 \
        + 2048 * 151_936
    assert dsa_bytes.resident_params(lm) == resident == 439_578_880
    # one row, nothing scored, nothing attended to, no expert hit: the
    # weights, its embedding row, what it writes in six blocks
    assert dsa_bytes.decode_bytes_per_step(lm) == 2 * resident + 4096 \
        + 6 * (2048 + 128)
    # four program rows whose three real ones score 8,200 index keys and
    # attend to 2,048 keys a block, 28 experts hit a block
    scored, attended, hits = 3 * 6 * 8200, 3 * 6 * 2048, 6 * 28
    step = dsa_bytes.decode_bytes_per_step(lm, 4.0, scored, attended, hits)
    assert step == 2 * resident + hits * 9_437_184 \
        + 4 * (4096 + 6 * 2176) + scored * 128 + attended * 2048
    assert step / 1e9 == pytest.approx(2.559, abs=0.002)
    # had the step read the cache's length and not the selection
    dense = dsa_bytes.decode_bytes_per_step(lm, 4.0, 0, 3 * 6 * 8200, hits)
    assert (dense - step) / 1e6 == pytest.approx(207.9, abs=0.5)


def test_prefill_flops_against_hand_counts():
    lm = config()["lm"]
    positions, rows = 4 * 8192, 4.0
    # what four rows of 8,127 real ids come to, a block
    real = 8127
    scored = 6 * 4 * sum(t + 1 for t in range(2048, real))
    attended = 6 * 4 * sum(min(t + 1, 2048) for t in range(real))
    pairs = positions * 6 * 8
    products = 2.0 * 128_385_024 * positions
    experts = 2.0 * 4_718_592 * pairs
    index = 2.0 * 16 * 65 * scored
    attention = 4.0 * 4096 * attended
    head = 2.0 * 2048 * 151_936 * rows
    assert dsa_bytes.prefill_flops(lm, positions, rows, scored, attended,
                                   pairs) \
        == products + experts + index + attention + head
    assert (products / 1e12, experts / 1e12, index / 1e12,
            attention / 1e12) == (
        pytest.approx(8.41, abs=0.01), pytest.approx(14.84, abs=0.01),
        pytest.approx(1.54, abs=0.01), pytest.approx(5.72, abs=0.01))
    # attention over the selection, not the triangle the masked products
    # walk: the triangle would be 2.2 times as much
    triangle = 6 * 4 * real * (real + 1) / 2
    assert triangle / attended == pytest.approx(2.27, abs=0.01)


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
    counters["lm.prompt_tokens"] = 8127 * requests
    executions = counters["lm.executions"]
    if counted:
        counters.update({
            "lm.prefill_positions": executions * 4 * 8192,
            "lm.keys_scored_prefill": executions * 4 * 6 * 30_900_000,
            "lm.keys_selected_prefill": executions * 4 * 6 * 12_450_000,
            "lm.keys_attended_prefill": executions * 4 * 6 * 14_550_000,
            "lm.expert_pairs_local_prefill": executions * 4 * 8192 * 48,
            "lm.keys_scored_decode": requests * 6 * 64 * 8160,
            "lm.keys_selected": requests * 6 * 64 * 2048,
            "lm.keys_attended": requests * 6 * 64 * 2048,
            "lm.expert_hits": executions * 64 * 6 * 22})
    prof = ctx.metrics_window.get("profile")
    if prof:
        program = prof["programs"]["jit_lm_generate"]
        if classes:
            program["classes"].update(lm_index=0.62, lm_experts=0.55)
        if account:
            program["phases"] = {"prefill": 1.85, "decode": 0.64}
            program["account"] = {"by_class": {}, "by_phase": {
                "prefill": {"lm_experts": 0.5, "lm_proj": 0.3, "lm_attn": 0.6,
                            "lm_index": 0.45 if classes else 0.0,
                            "idle": 0.05},
                "decode": {"lm_experts": 0.2, "lm_proj": 0.1, "lm_attn": 0.05,
                           "lm_index": 0.17, "lm_head": 0.12, "idle": 0.0}}}
    return ctx


def test_the_two_class_readers_are_the_selections_own_seconds_per_request():
    ctx = context()
    assert reader("lm_index_device_s_per_request")(ctx) \
        == pytest.approx(0.62 / 3)
    assert reader("lm_prefill_index_device_s_per_request")(ctx) \
        == pytest.approx(0.45 / 3)


def test_the_roofline_reader_counts_the_keys_selected_not_the_caches_length():
    ctx = context()
    lm = ctx.config["lm"]
    # 3 requests in 4 program rows, 64 steps of the decode phase's WALL
    # seconds; a step: what the three real rows scored and attended to,
    # the experts the step hit
    nbytes = dsa_bytes.decode_bytes_per_step(
        lm, 4.0, 3 * 6 * 8160, 3 * 6 * 2048, 6 * 22)
    want = 100.0 * (nbytes / 819e9) / (0.64 / 64)
    assert reader("lm_dsa_decode_hbm_roofline_pct")(ctx) \
        == pytest.approx(want)
    assert 25 < want < 30 and want < 100
    # a program that attended to every cached key would be credited with
    # four times the cache bytes: the counter decides, not the shapes
    ctx.metrics_window["pipeline"]["counters"]["lm.keys_attended"] *= 4
    assert reader("lm_dsa_decode_hbm_roofline_pct")(ctx) > want + 2


def test_the_utilisation_reader_counts_what_the_program_counted():
    ctx = context()
    flops = dsa_bytes.prefill_flops(
        ctx.config["lm"], 4 * 8192, 4.0, 4 * 6 * 30_900_000,
        4 * 6 * 14_550_000, 4 * 8192 * 48)
    want = 100.0 * flops / 1.90 / 197e12
    assert reader("lm_dsa_prefill_flops_util_pct")(ctx) \
        == pytest.approx(want)
    assert 7 < want < 9
    # a program that computed a tenth of the positions is credited with a
    # tenth of the products, whatever `prompt_tokens` says
    ctx.metrics_window["pipeline"]["counters"]["lm.prefill_positions"] //= 10
    assert reader("lm_dsa_prefill_flops_util_pct")(ctx) < 0.8 * want


@pytest.mark.parametrize("other", ["ouro-2.6b-expand-sd15-512",
                                   "pangu-ultra-moe-expand-sd15-512",
                                   "k-exaone-236b-expand-sd15-512",
                                   "granite-4.0-h-micro-expand-sd15-512"])
@pytest.mark.parametrize("name", NEW_READERS)
def test_the_new_readers_give_nothing_on_the_other_families_programs(
        name, other):
    """The other four families' programs, and the parent's, have no such
    class and count no such thing: the readers give nothing and do not
    raise; nor without a trace, a profile, an account or the program's
    pattern."""
    nothing = dict(classes=False, counted=False)
    ctx = context(name=other, **nothing)
    if "granite" in other:          # it counts its prefill's positions too
        ctx.metrics_window["pipeline"]["counters"].update({
            "lm.prefill_positions": 4 * 2048, "lm.state_steps": 9216})
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


@pytest.mark.parametrize("name", sorted(NOT_THIS_FAMILYS) + [
    "lm_swa_moe_decode_hbm_roofline_pct"])
def test_the_other_families_readers_give_nothing_on_this_program(name):
    """(The cell lists none of them; openPangu's byte count reads a
    latent's ranks and is not asked.)"""
    assert reader(name)(context()) is None


def test_the_accepted_lm_readers_read_the_new_program():
    """The cell lists the accepted language-model readers that count no
    family's bytes, and the experts' two: each finds its program and its
    classes in this configuration."""
    ctx = context(lm_s=2.6)
    assert reader("lm_device_s_per_request")(ctx) == pytest.approx(2.6 / 3)
    assert reader("lm_decode_ms_per_token")(ctx) \
        == pytest.approx(2600.0 / 64)
    assert reader("lm_mlp_device_s_per_request")(ctx) is not None
    assert reader("lm_attn_device_s_per_request")(ctx) > 0
    assert reader("lm_decode_step_ms")(ctx) == pytest.approx(10.0)
    assert reader("lm_prefill_device_s_per_request")(ctx) \
        == pytest.approx(1.85 / 3)
    assert reader("lm_prefill_attn_device_s_per_request")(ctx) \
        == pytest.approx((0.3 + 0.6) / 3)
    assert reader("lm_experts_device_s_per_request")(ctx) \
        == pytest.approx(0.55 / 3)
    assert reader("lm_prefill_experts_device_s_per_request")(ctx) \
        == pytest.approx(0.5 / 3)
    assert 0 < reader("lm_share_of_busy_pct")(ctx) < 100


def test_the_shipped_workflow_is_the_configurations_graph():
    """``workflows/prompt-expand-sysprompt-txt2img.json`` is what the cell
    times, with PreviewImage where the configuration saves."""
    with open(os.path.join(REPO, "workflows",
                           "prompt-expand-sysprompt-txt2img.json")) as f:
        shipped = json.load(f)
    doc = shipped.pop("__doc__")
    assert "8,100 ids" in doc and "vision tower" in doc
    graph = config()["graph"]
    assert {nid for nid in graph if graph[nid] != shipped[nid]} == {"9"}
    assert shipped["9"]["class_type"] == "PreviewImage"
    assert list(shipped) == list(graph)


# --- the cell, rehearsed -----------------------------------------------------------

def test_the_cell_rehearses_on_the_cpu(tmp_path):
    """``run.py --rehearse`` of the new cell: a tiny model of THIS family
    behind the same nodes, hand-over and drain wait, every request
    served, nothing compiled in the window, the program's counters on the
    window's record."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("DTPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 42), "--seconds", "4", "--trace", "0",
         "--rehearse", "--out", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600)
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
    # every row starts from the snapshot of the rehearsal's instructions
    # (14 ids with the first; this family's rotation counts from a row's
    # first real id, so the snapshot stands at each row's offset) and the
    # 34 positions behind them are computed; 4 new tokens, 3 blocks, topk 8
    held = 14
    assert counters["lm.prefix_hits"] == counters["lm.rows"]
    assert counters["lm.prefix_positions_served"] \
        == counters["lm.rows"] * held
    assert "lm.prefix_misses" not in counters      # made by the warm-ups
    assert counters["lm.prefill_positions"] == rows * (48 - held)
    assert counters["lm.expert_pairs_local_prefill"] \
        == rows * (48 - held) * 3 * 2
    assert counters["lm.expert_pairs_dropped"] == 0
    assert counters["lm.keys_attended"] == counters["lm.keys_selected"] \
        == counters["lm.rows"] * 4 * 3 * 8
    assert counters["lm.keys_scored_decode"] > 4 * counters["lm.keys_attended"]
    assert counters["lm.keys_scored_prefill"] \
        > counters["lm.keys_attended_prefill"] > 0
    assert "lm.state_steps" not in counters


def test_the_verify_script_rehearses_behind_a_snapshot(tmp_path):
    """``verify_lm_dsa_moe.py --rehearse`` (the benchmark's, unedited): its
    served requests carry the rehearsal's instructions, so each starts
    from their snapshot, and the reference is forced to the program's
    record of the WHOLE prompt, the snapshot's 14 positions too: one
    request alone and four as the rows of one execution inside every
    limit, every reading that has to fail outside one."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("DTPU_")}
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "verify_lm_dsa_moe.py"),
         "--rehearse", "--out", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:] + p.stdout[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["ok"] is True and len(got["served"]) == 5
    for row in got["served"]:
        assert row["correct"] and row["selection_agree"] == 1.0
        assert 14 < row["prompt_ids"] <= 48
    assert got["together"]["executions"] == 1 \
        and got["together"]["rows"] == 4
    for reading in ("weights_8bit", "cache_8bit", "no_selection",
                    "last_topk", "no_relu", "no_head_weights", "top7_of_8",
                    "no_renormalisation"):
        assert got[reading]["correct"] is False, reading


def test_the_cells_instructions_are_a_true_prefix_of_every_request():
    """What `LanguageModel.shared_prefix` needs of the CELL's traffic, at
    the published vocabulary: the operator's instructions encode to 8,101
    ids (the start id and 8,100 words) that are the first ids of every
    request's prompt, with 1 to 91 ids of the row's own behind them inside
    the 8192 positions.  A silent fall-back to the whole prompt would
    fail here, not only on the chip."""
    import numpy as np
    from comfyui_distributed_tpu.models import dsa_moe, registry, tokenizer
    node = config()["graph"]["21"]["inputs"]
    model = registry.LanguageModel(
        "keye-vl-2.0-30b-a3b.safetensors", dsa_moe.KEYE_VL2_STAGE, None,
        tokenizer.make_lm_tokenizer(None, 151936), "keye")
    with open(os.path.join(BENCH, "traffic", "words.txt")) as f:
        words = [w.strip() for w in f if w.strip()]
    rng = random.Random(7)
    rows = [registry.LMRow(" ".join(rng.choice(words) for _ in range(12)),
                           i, instructions=node["instructions"])
            for i in range(4)]
    prefix = model.shared_prefix(rows, node["prompt_tokens"])
    assert prefix is not None and len(prefix) == 8101
    for row in rows:
        ids = model.prompt_ids(row.text, node["prompt_tokens"],
                               row.instructions)
        assert np.array_equal(ids[:8101], prefix)
        assert 1 <= len(ids) - 8101 <= node["prompt_tokens"] - 8101 == 91
    # one row with other instructions, and the execution runs whole
    other = [*rows[:3], registry.LMRow("a cat", 3, instructions="draw it")]
    assert model.shared_prefix(other, node["prompt_tokens"]) is None
    assert dsa_moe.prefix_bytes(model.cfg, 8101) == 156_705_744
