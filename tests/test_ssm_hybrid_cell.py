"""What PR 40 added to the benchmark, tested from outside it (the
benchmark's own test files are not a ``model_config`` PR's to edit): the
configuration against the catalog and against the program, the manifest's
entries by membership, ``lib/lm_ssm_bytes.py`` against hand counts, the
four readers on a made-up context (with the program's counters, classes
and phases, and on the other three families' programs, which have none of
them, as the parent), the accepted readers on the new program, and the
cell's rehearsal on the CPU.
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

from lib import lm_ssm_bytes as ssm_bytes           # noqa: E402

CELL = "granite_expand_sd15_512_sat4"
EXAONE4 = "exaone_expand_sd15_512_sat4"
CONFIG = "granite-4.0-h-micro-expand-sd15-512"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ["lm_ssm_device_s_per_request",
               "lm_prefill_ssm_device_s_per_request",
               "lm_ssm_decode_hbm_roofline_pct",
               "lm_ssm_prefill_flops_util_pct"]
# K-EXAONE's counts: its bytes and FLOPs read experts and a ring
NOT_THIS_FAMILYS = {"lm_experts_device_s_per_request",
                    "lm_prefill_experts_device_s_per_request",
                    "lm_swa_moe_decode_hbm_roofline_pct",
                    "lm_prefill_flops_util_pct"}


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lm_cell = _load("chipbench_tests_lm_cell_for_ssm_hybrid",
                os.path.join(BENCH, "tests", "test_lm_cell.py"))


def config(name=CONFIG):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def reader(name):
    return _load(f"ssm_hybrid_metric_{name}",
                 os.path.join(BENCH, "layer_metrics", name + ".py")).read


# --- the configuration ---------------------------------------------------------

def test_the_configuration_holds_every_published_value_and_cuts_nothing():
    cfg, lm = config(), config()["lm"]
    assert cfg["reduced"] == [] and len(cfg["assumed"]) >= 12
    assert any("FLOAT32" in a for a in cfg["assumed"])
    assert any("A uniform in 1..16" in a for a in cfg["assumed"])
    assert len(cfg["source"]) <= 200
    # every number stands at the top level too, where the driver compares
    for key, value in lm.items():
        if key != "head_dim":                   # derived: under `assumed`
            assert cfg[key] == value, key
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 40
    assert cfg["layer_types"] == (["mamba"] * 5 + ["attention"]
                                  + ["mamba"] * 4) * 4
    assert cfg["sizes"]["param_count"] == 3_191_396_096
    if os.path.isfile(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            rows = [json.loads(line) for line in f]
        (row,) = [r for r in rows if r["name"] == "granite-4.0-h-micro"]
        assert cfg["source"] == row["source_url"]
        assert {k for k, v in row["config"].items()
                if k not in cfg or cfg[k] != v} == set()


def test_the_instructions_are_written_out_and_fill_the_prompt_buffer():
    """65 few-shot examples of 30 words, drawn once from the benchmark's
    words with ``random.Random(40)``: 1,950 ids in front of the template
    and the user's 12 words, inside the 2048 positions."""
    node = config()["graph"]["21"]["inputs"]
    assert (node["prompt_tokens"], node["max_new_tokens"],
            node["temperature"]) == (2048, 64, 0.0)
    with open(os.path.join(BENCH, "traffic", "words.txt")) as f:
        words = [w.strip() for w in f if w.strip()]
    rng, shots = random.Random(40), []
    for _ in range(65):
        shots += ["example", "prompt"] + [rng.choice(words)
                                          for _ in range(4)] \
            + ["detailed", "prompt"] + [rng.choice(words) for _ in range(22)]
    assert node["instructions"] == " ".join(shots)
    assert len(shots) == 1950
    from comfyui_distributed_tpu.models import registry, tokenizer
    tok = tokenizer.make_lm_tokenizer(None, 100352)
    text = " ".join(words[:12])
    ids = tok.encode(f"{node['instructions']} "
                     + registry.EXPAND_TEMPLATE.format(text=text))
    assert 1950 + 12 < len(ids) <= 2048 and max(ids) < 100352
    assert 1970 <= len(ids) <= 2000


def test_the_graph_is_k_exaones_with_another_model_and_a_longer_prompt():
    cfg, other = config(), config("k-exaone-236b-expand-sd15-512")
    assert set(cfg["graph"]) == set(other["graph"])
    assert {nid for nid in cfg["graph"]
            if cfg["graph"][nid] != other["graph"][nid]} == {"20", "21"}
    assert cfg["graph"]["20"]["inputs"] == {
        "model_name": "granite-4.0-h-micro.safetensors"}
    a, b = cfg["graph"]["21"]["inputs"], other["graph"]["21"]["inputs"]
    assert {k for k in a if a[k] != b[k]} == {"prompt_tokens",
                                              "instructions"}
    for key in ("programs", "unet", "vary", "text_encoders", "vae"):
        assert cfg[key] == other[key], key
    assert cfg["trace_slice"]["after_counter"] == "lm.executions"
    assert cfg["trace_slice"]["requests"] == 2.25
    assert [s[:2] for s in cfg["rehearsal"]["set"]] == \
        [s[:2] for s in other["rehearsal"]["set"]]


def test_the_program_serves_what_the_configuration_states():
    from comfyui_distributed_tpu.models import registry, ssm_hybrid
    lm, full = config()["lm"], ssm_hybrid.GRANITE_4_0_H_MICRO
    same = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "num_hidden_layers", "shared_intermediate_size", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_n_groups",
            "mamba_expand", "mamba_chunk_size", "attention_multiplier",
            "embedding_multiplier", "residual_multiplier", "logits_scaling",
            "rms_norm_eps", "vocab_size", "head_dim")
    for key in same:
        assert getattr(full, key) == lm[key], key
    assert list(full.layer_types) == lm["layer_types"]
    assert lm["tie_word_embeddings"] is True
    assert lm["num_local_experts"] == lm["num_experts_per_tok"] == 0
    assert lm["position_embedding_type"] == "nope"
    # and the bytes of the benchmark count the program's tree: the tied
    # leaf ONCE
    assert ssm_bytes.resident_params(lm) == ssm_hybrid.param_count(full) \
        == config()["sizes"]["param_count"]
    assert ssm_bytes.state_bytes_per_row(lm) \
        == ssm_hybrid.state_bytes(full, 1) \
        == config()["sizes"]["recurrent_state_bytes_a_row"]
    assert ssm_bytes.key_bytes(lm) * 4 * 2112 \
        == ssm_hybrid.kv_cache_bytes(full, 1, 2112) \
        == config()["sizes"]["kv_cache_bytes_a_row_at_2112"]
    nodes = {n["class_type"]: n["inputs"] for n in config()["graph"].values()}
    assert registry.detect_lm_family(
        nodes["LanguageModelLoader"]["model_name"]) == ("granite", "full")


# --- the manifest, by membership ---------------------------------------------------

def test_the_manifest_gained_one_configuration_one_cell_and_four_readers():
    m = manifest()
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert entry == m["configs"][5] and entry["reduced"] == []
    assert entry["file"] == f"benchmarks/chip/configs/{CONFIG}.json"
    assert entry["source"] == config()["source"]
    assert len(entry["why"]) <= 200
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert cell == m["workloads"][8]
    assert {k: cell[k] for k in ("config", "traffic", "chips")} == {
        "config": CONFIG, "traffic": "closed4_unique", "chips": 1}
    assert len(cell["why"]) <= 200 and "Three rows of four" in cell["why"]
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == \
        ["sdxl_1024_fanout4"]
    # (what PR 42 added stands behind them)
    assert len(m["workloads"]) >= 9 and len(m["configs"]) >= 6
    assert {x["name"] for x in m["end_to_end"]
            if CELL in x.get("workloads", [CELL])} == {
        "images_per_s", "tti_p50_s", "setup_s"}
    new = [x for x in m["per_layer"] if x["name"] in NEW_READERS]
    assert [x["name"] for x in new] == NEW_READERS == \
        [x["name"] for x in m["per_layer"][41:45]]
    for x in new:
        # (PR 46's family has the two classes the class readers read: its
        # cell stands behind this one there)
        assert x["workloads"] in (
            [CELL], [CELL, "phi4flash_expand_sd15_512_sat4"]
            if "_ssm_device_s" in x["name"] else [CELL]) \
            and x["layer"] == "Language model" \
            and x["source"] == "device_trace"
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           x["name"] + ".py"))
    by_name = {x["name"]: x for x in new}
    assert (by_name["lm_ssm_device_s_per_request"]["moves"],
            by_name["lm_prefill_ssm_device_s_per_request"]["moves"]) == \
        ("images_per_s", "tti_p50_s")
    for name in NEW_READERS[2:]:
        assert (by_name[name]["unit"], by_name[name]["better"],
                by_name[name]["moves"]) == ("%", "higher", "images_per_s")


def test_the_cell_is_appended_where_the_reader_is_family_neutral():
    """It stands behind K-EXAONE's cell in every list that cell is in,
    but for the four whose counts are that family's."""
    m = manifest()

    def listed(cell):
        return {x["name"] for g in ("end_to_end", "per_layer")
                for x in m[g] if cell in x.get("workloads", [])}
    assert listed(EXAONE4) - listed(CELL) == NOT_THIS_FAMILYS
    assert listed(CELL) - listed(EXAONE4) == set(NEW_READERS)
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
    assert ssm_bytes.blocks(lm) == (36, 4)
    assert ssm_bytes.mixer_matrices(lm) == 2048 * 8512 + 4096 * 2048
    assert ssm_bytes.mixer_params(lm) == 25_847_232
    assert ssm_bytes.attention_params(lm) == 10_485_760
    assert ssm_bytes.mlp_params(lm) == 50_331_648
    assert ssm_bytes.key_bytes(lm) == 2048                  # 2 KiB a key
    state = 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert ssm_bytes.state_bytes_per_row(lm) == state == 76_437_504
    weights = 2 * 3_191_396_096
    # one row, nothing attended to: its state read and written, its
    # embedding row, the four keys and values it writes
    assert ssm_bytes.decode_bytes_per_step(lm) == weights + 2 * state \
        + 2 * 2048 + 4 * 2048
    # four program rows whose three real ones attend to 2,020 keys a
    # layer: the weights once, the state a row
    keys = 3 * 4 * 2020
    four = ssm_bytes.decode_bytes_per_step(lm, 4.0, keys)
    assert four == weights + 4 * (2 * state + 4096 + 8192) + keys * 2048
    assert four / 1e9 == pytest.approx(7.04, abs=0.01)
    assert 4 * 2 * state / four == pytest.approx(0.087, abs=0.001)


def test_prefill_flops_against_hand_counts():
    lm = config()["lm"]
    matrices = 36 * (2048 * 8512 + 4096 * 2048 + 50_331_648) \
        + 4 * (10_485_760 + 50_331_648)
    assert ssm_bytes.block_matrices(lm) == matrices == 2_984_771_584
    positions, rows, real = 4 * 2048, 4.0, 1990.0
    products = 2.0 * matrices * positions
    recurrence = 36 * 4.0 * 64 * 64 * 128 * positions
    attention = 4.0 * 2048 * 4 * rows * real * (real + 1) / 2
    head = 2.0 * 2048 * 100352 * rows
    assert ssm_bytes.prefill_flops(lm, positions, rows, real) \
        == products + recurrence + attention + head
    assert (products / 1e12, recurrence / 1e12, attention / 1e12) == (
        pytest.approx(48.9, abs=0.05), pytest.approx(0.62, abs=0.01),
        pytest.approx(0.26, abs=0.01))
    # a prefix served from a snapshot computes fewer positions, and the
    # count follows the program's counter, not the configuration's 2048
    assert ssm_bytes.prefill_flops(lm, 4 * 98, rows, real) \
        < 0.06 * ssm_bytes.prefill_flops(lm, positions, rows, real)


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
            "lm.scan_chunks": executions * 4 * 36 * 8,
            "lm.state_steps": executions * 4 * 36 * 64,
            "lm.keys_attended_full": requests * 4 * (64 * 1990 + 2080)})
    prof = ctx.metrics_window.get("profile")
    if prof:
        program = prof["programs"]["jit_lm_generate"]
        if classes:
            program["classes"].update(lm_ssm=0.21, lm_state=0.06)
        if account:
            program["account"] = {"by_class": {}, "by_phase": {
                "prefill": {"lm_mlp": 0.4, "lm_proj": 0.15, "lm_ssm": 0.11
                            if classes else 0.0, "lm_state": 0.01
                            if classes else 0.0, "idle": 0.03},
                "decode": {"lm_mlp": 0.35, "lm_proj": 0.2, "lm_ssm": 0.04,
                           "lm_state": 0.05, "idle": 0.0}}}
    return ctx


def test_the_two_class_readers_are_the_mixers_own_seconds_per_request():
    ctx = context()
    assert reader("lm_ssm_device_s_per_request")(ctx) \
        == pytest.approx((0.21 + 0.06) / 3)
    assert reader("lm_prefill_ssm_device_s_per_request")(ctx) \
        == pytest.approx((0.11 + 0.01) / 3)


def test_the_roofline_reader_counts_the_state_of_every_program_row():
    ctx = context()
    lm = ctx.config["lm"]
    # 3 requests in 4 program rows, 64 steps of the decode phase's WALL
    # seconds; the keys of the three real rows, a step
    keys = 3 * 4 * (64 * 1990 + 2080) / 64
    nbytes = ssm_bytes.decode_bytes_per_step(lm, 4.0, keys)
    want = 100.0 * (nbytes / 819e9) / (0.64 / 64)
    assert reader("lm_ssm_decode_hbm_roofline_pct")(ctx) \
        == pytest.approx(want)
    assert 84 < want < 87
    # one program row fewer is 153 MB a step fewer
    one_less = context(padded=0)
    assert reader("lm_ssm_decode_hbm_roofline_pct")(one_less) \
        == pytest.approx(want - 100.0 * 2 * 76_449_792 / 819e9 / 0.01,
                         abs=0.01)


def test_the_utilisation_reader_counts_the_positions_the_program_counted():
    ctx = context()
    flops = ssm_bytes.prefill_flops(ctx.config["lm"], 4 * 2048, 4.0, 1990.0)
    want = 100.0 * flops / 0.70 / 197e12
    assert reader("lm_ssm_prefill_flops_util_pct")(ctx) \
        == pytest.approx(want)
    assert 35 < want < 37
    # a program that computed a tenth of the positions (a shared prefix
    # served from a snapshot) is credited with a tenth of the products,
    # whatever `prompt_tokens` says
    ctx.metrics_window["pipeline"]["counters"]["lm.prefill_positions"] //= 10
    assert reader("lm_ssm_prefill_flops_util_pct")(ctx) < 0.13 * want


@pytest.mark.parametrize("other", ["ouro-2.6b-expand-sd15-512",
                                   "pangu-ultra-moe-expand-sd15-512",
                                   "k-exaone-236b-expand-sd15-512"])
@pytest.mark.parametrize("name", NEW_READERS)
def test_the_new_readers_give_nothing_on_the_other_families_programs(
        name, other):
    """The other three families' programs, and the parent's, have no such
    class and count no such thing: the readers give nothing and do not
    raise; nor without a trace, a profile, an account or the program's
    pattern."""
    nothing = dict(classes=False, counted=False)
    assert reader(name)(context(name=other, **nothing)) is None
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
    """The cell lists the accepted language-model readers that count no
    family's bytes: each finds its program and its classes in this
    configuration; the mixer's projections are in ``lm_proj``, so in
    ``lm_attn_device_s_per_request``."""
    ctx = context(lm_s=1.4)
    assert reader("lm_device_s_per_request")(ctx) == pytest.approx(1.4 / 3)
    assert reader("lm_decode_ms_per_token")(ctx) \
        == pytest.approx(1400.0 / 64)
    assert reader("lm_mlp_device_s_per_request")(ctx) > 0
    assert reader("lm_attn_device_s_per_request")(ctx) \
        == pytest.approx((0.50 + 0.10 + 0.06) / 3)
    assert reader("lm_decode_step_ms")(ctx) == pytest.approx(10.0)
    assert reader("lm_prefill_attn_device_s_per_request")(ctx) \
        == pytest.approx(0.15 / 3)
    assert 0 < reader("lm_share_of_busy_pct")(ctx) < 100
    assert reader("lm_experts_device_s_per_request")(ctx) is None
    assert reader("lm_prefill_experts_device_s_per_request")(ctx) is None


def test_the_shipped_workflow_is_the_configurations_graph():
    """``workflows/prompt-expand-longshot-txt2img.json`` is what the cell
    times, with PreviewImage where the configuration saves."""
    with open(os.path.join(REPO, "workflows",
                           "prompt-expand-longshot-txt2img.json")) as f:
        shipped = json.load(f)
    assert "1,950 ids" in shipped.pop("__doc__")
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
         "--seed", str(2 ** 31 + 40), "--seconds", "4", "--trace", "0",
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
    # the instructions' 14 ids (13 words behind the first id) are every
    # request's: each row starts from their snapshot, made at warm-up, and
    # the program computes the 34 of 48 prompt positions behind it in 5
    # chunks of 8; 4 new tokens, 4 Mamba blocks
    assert counters["lm.prefill_positions"] == rows * (48 - 14)
    assert counters["lm.scan_chunks"] == rows * 4 * 5
    assert counters["lm.state_steps"] == rows * 4 * 4
    assert counters["lm.prefix_hits"] == counters["lm.rows"]
    assert counters["lm.prefix_positions_served"] == counters["lm.rows"] * 14
    assert counters.get("lm.prefix_misses", 0) == 0
    assert counters.get("lm.prefix_evictions", 0) == 0
    assert counters["lm.keys_attended_full"] > 0
    assert "lm.expert_pairs" not in counters
