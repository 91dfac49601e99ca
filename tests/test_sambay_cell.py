"""What PR 46 added to the benchmark, tested from outside it (the
benchmark's own test files are not a ``model_config`` PR's to edit): the
configuration against the catalog and against the program, the manifest's
entries by membership, ``lib/lm_sambay_bytes.py`` against hand counts, the
four readers on a made-up context (with the program's counters, classes
and phases, and on the other five families' programs, which have none of
them, as the parent), the accepted readers on the new program, and the
cell's and the verify script's rehearsals on the CPU.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")
sys.path.insert(0, BENCH)

from lib import lm_sambay_bytes as sambay_bytes     # noqa: E402

CELL = "phi4flash_expand_sd15_512_sat4"
KEYE4 = "keye_expand_sd15_512_sat4"
GRANITE4 = "granite_expand_sd15_512_sat4"
CONFIG = "phi-4-mini-flash-expand-sd15-512"
KEYE = "keye-vl-2.0-30b-a3b-expand-sd15-512"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ["lm_gmu_device_s_per_request",
               "lm_cross_device_s_per_request",
               "lm_sambay_decode_hbm_roofline_pct",
               "lm_sambay_prefill_flops_util_pct"]
SSM_READERS = {"lm_ssm_device_s_per_request",
               "lm_prefill_ssm_device_s_per_request"}
OTHER_FAMILIES = ["lm_ssm_decode_hbm_roofline_pct",
                  "lm_ssm_prefill_flops_util_pct",
                  "lm_dsa_decode_hbm_roofline_pct",
                  "lm_dsa_prefill_flops_util_pct",
                  "lm_index_device_s_per_request",
                  "lm_experts_device_s_per_request",
                  "lm_swa_moe_decode_hbm_roofline_pct"]


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lm_cell = _load("chipbench_tests_lm_cell_for_sambay",
                os.path.join(BENCH, "tests", "test_lm_cell.py"))


def config(name=CONFIG):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def reader(name):
    return _load(f"sambay_metric_{name}",
                 os.path.join(BENCH, "layer_metrics", name + ".py")).read


# --- the configuration ---------------------------------------------------------

def test_the_configuration_is_the_published_one_with_nothing_reduced():
    cfg, lm = config(), config()["lm"]
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    # every number stands at the top level too, where the driver compares
    for key, value in lm.items():
        assert cfg[key] == value, key
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["sliding_window"],
            cfg["mb_per_layer"], cfg["vocab_size"]) == (
        2560, 10240, 32, 40, 20, 512, 2, 200064)
    assert cfg["tie_word_embeddings"] is True and cfg["mlp_bias"] is False \
        and cfg["lm_head_bias"] is False
    assert "WHOLE" in cfg["deployment"] and "Nothing reduced" \
        in cfg["deployment"]
    if os.path.isfile(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            rows = [json.loads(line) for line in f]
        (row,) = [r for r in rows
                  if r["name"] == "Phi-4-mini-flash-reasoning"]
        assert cfg["source"] == row["source_url"]
        assert {k for k, v in row["config"].items()
                if k not in cfg or cfg[k] != v} == set()


@pytest.mark.parametrize("reading", [
    "mamba_d_state 16", "mamba_dt_rank ceil(2560 / 16) = 160",
    "biases: on Wqkv and out_proj", "3,852,562,944",
    "query heads 2j and 2j+1 make differential head j",
    "lam_0 = 0.8 - 0.6 exp(-0.3 l)", "eps 1e-5 (layer_norm_eps)",
    "[a | b] in that order", "with no norm on any",
    "the memory m includes the D skip", "THE STATE IS FLOAT32",
    "no positional encoding of any kind", "PAIRS of key-value heads",
    "for each row's LAST position only", "N(0, 0.02^2)"])
def test_every_assumed_reading_is_written_in_the_file(reading):
    assert any(reading in a for a in config()["assumed"]), reading


def test_the_configuration_file_stays_a_file_the_driver_reads():
    """Strict JSON under 65,536 bytes (PR 42 was refused once for 66,011:
    the instructions alone are 51,467 and stay written out)."""
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


def test_the_graph_is_keyes_with_another_model_behind_the_same_prompt():
    """Two families behind one prompt: the generate node, its 8,100
    instruction ids among it, bit for bit."""
    cfg, other = config(), config(KEYE)
    assert set(cfg["graph"]) == set(other["graph"])
    assert {nid for nid in cfg["graph"]
            if cfg["graph"][nid] != other["graph"][nid]} == {"20"}
    assert cfg["graph"]["20"]["inputs"] == {
        "model_name": "phi-4-mini-flash-reasoning.safetensors"}
    node = cfg["graph"]["21"]["inputs"]
    assert node == other["graph"]["21"]["inputs"]
    assert (node["prompt_tokens"], node["max_new_tokens"],
            node["temperature"]) == (8192, 64, 0.0)
    assert len(node["instructions"].split()) == 8100 \
        and len(node["instructions"]) == 51_467
    for key in ("programs", "unet", "vary", "text_encoders", "vae"):
        assert cfg[key] == other[key], key
    assert cfg["trace_slice"]["after_counter"] == "lm.executions"
    assert [s[:2] for s in cfg["rehearsal"]["set"]] == \
        [s[:2] for s in other["rehearsal"]["set"]]
    from comfyui_distributed_tpu.models import registry, tokenizer
    tok = tokenizer.make_lm_tokenizer(None, 200064)
    ids = tok.encode(f"{node['instructions']} "
                     + registry.EXPAND_TEMPLATE.format(text="a " * 12))
    assert 8100 + 12 < len(ids) <= 8192 and max(ids) < 200064


def test_the_program_serves_what_the_configuration_states():
    from comfyui_distributed_tpu.models import registry, sambay
    lm, full = config()["lm"], sambay.PHI_4_MINI_FLASH
    for key in ("hidden_size", "intermediate_size", "num_hidden_layers",
                "num_attention_heads", "num_key_value_heads",
                "sliding_window", "mb_per_layer", "layer_norm_eps",
                "vocab_size", "tie_word_embeddings", "mlp_bias",
                "lm_head_bias", "max_position_embeddings", "mamba_d_state",
                "mamba_d_conv", "mamba_expand", "mamba_dt_rank"):
        assert getattr(full, key) == lm[key], key
    # the bytes of the benchmark count the program's tree
    sizes = config()["sizes"]
    assert sambay.param_count(full) == sizes["param_count"] \
        == sambay_bytes.resident_params(lm) == 3_852_562_944 \
        == 9 * sizes["mamba_layer"]["layer"] \
        + 9 * sizes["attention_layer"]["layer"] \
        + 7 * sizes["gmu_layer"]["layer"] \
        + 7 * sizes["cross_layer"]["layer"] \
        + sizes["embedding_tied_counted_once"] + sizes["final_layernorm"]
    assert sizes["bytes_bf16"] == 2 * sizes["param_count"]
    by_kind = sambay.kv_cache_bytes_by_kind(full, 1, 8256)
    assert by_kind == {
        "recurrent": sizes["recurrent_state_bytes_a_row_9_layers"],
        "ring": sizes["ring_bytes_a_row_8_layers_x_512_slots"],
        "full": sizes["full_cache_bytes_a_row_at_8256"]}
    assert sum(by_kind.values()) == sizes["state_bytes_a_row_at_8256"]
    assert sambay_bytes.state_bytes_per_row(lm) == by_kind["recurrent"]
    assert sambay_bytes.key_bytes(lm) \
        == sizes["full_cache_bytes_a_position_a_row"] == 5120
    nodes = {n["class_type"]: n["inputs"] for n in config()["graph"].values()}
    assert registry.detect_lm_family(
        nodes["LanguageModelLoader"]["model_name"]) == ("phi4flash", "full")


# --- the manifest, by membership ---------------------------------------------------

def test_the_manifest_gained_one_configuration_one_cell_and_four_readers():
    m = manifest()
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert entry == m["configs"][7] and entry["reduced"] == []
    assert entry["file"] == f"benchmarks/chip/configs/{CONFIG}.json"
    assert entry["source"] == config()["source"]
    assert len(entry["why"]) <= 200
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert cell == m["workloads"][10]
    assert {k: cell[k] for k in ("config", "traffic", "chips")} == {
        "config": CONFIG, "traffic": "closed4_unique", "chips": 1}
    assert len(cell["why"]) <= 200 and "8192-id prefill" in cell["why"]
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == \
        ["sdxl_1024_fanout4"]
    assert len(m["workloads"][:11]) == 11 and len(m["configs"][:8]) == 8
    assert {x["name"] for x in m["end_to_end"]
            if CELL in x.get("workloads", [CELL])} == {
        "images_per_s", "tti_p50_s", "setup_s"}
    new = [x for x in m["per_layer"] if x["name"] in NEW_READERS]
    assert [x["name"] for x in new] == NEW_READERS == \
        [x["name"] for x in m["per_layer"][49:53]]     # (PR 49 added three)
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
            ("s", "lower")
    for name in NEW_READERS[2:]:
        assert (by_name[name]["unit"], by_name[name]["better"]) == \
            ("%", "higher")
    with open(os.path.join(REPO, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) <= 64 * 1024


def test_the_cell_stands_where_keyes_and_granites_both_stand():
    """Behind both in every list both are in, behind granite's in the two
    state-space readers (this family has the classes they read), and
    alone in its own four; in none of a family's own byte counts."""
    m = manifest()

    def listed(cell):
        return {x["name"] for g in ("end_to_end", "per_layer")
                for x in m[g] if cell in x.get("workloads", [])}
    assert listed(CELL) == (listed(KEYE4) & listed(GRANITE4)) \
        | SSM_READERS | set(NEW_READERS)
    assert not listed(CELL) & set(OTHER_FAMILIES)
    for group in ("end_to_end", "per_layer"):
        for x in m[group]:
            cells = x.get("workloads", [])
            if CELL in cells:       # (PR 49's cell may stand behind it)
                assert CELL in cells[-2:], x["name"]
    assert {"lm_device_s_per_request", "lm_decode_step_ms",
            "lm_prefill_device_s_per_request", "peak_hbm_gb",
            "compiles_in_window", "device_idle_pct"} <= listed(CELL)


# --- bytes and FLOPs from shapes ---------------------------------------------------

def test_decode_bytes_against_hand_counts():
    lm = config()["lm"]
    assert sambay_bytes.layers(lm) == {"mamba": 9, "swa": 8, "full": 1,
                                       "gmu": 7, "cross": 7}
    assert sambay_bytes.mamba_matrices(lm) == 41_123_840
    assert sambay_bytes.mlp_matrices(lm) == 78_643_200
    # layers 0..16 and layer 17's key / value projection: 1.87 B values
    assert sambay_bytes.front_matrices(lm) == 9 * (41_123_840 + 78_643_200) \
        + 8 * (2560 * 5120 + 2560 * 2560 + 78_643_200) + 2560 * 2560 \
        == 1_870_888_960
    assert sambay_bytes.back_matrices(lm) == 1_468_006_400
    resident = 3_852_562_944
    assert sambay_bytes.resident_params(lm) == resident
    state = 9 * (5120 * 16 * 4 + 3 * 5120 * 2)
    assert sambay_bytes.state_bytes_per_row(lm) == state == 3_225_600
    # one row, nothing attended to: the weights, its states read and
    # written, its embedding row, the nine keys and values it writes
    assert sambay_bytes.decode_bytes_per_step(lm) == 2 * resident \
        + 2 * state + 2 * 2560 + 9 * 5120
    # four program rows whose three real ones see 512 slots in each of
    # eight rings and 8,200 keys of the one cache through eight readers
    keys = 3 * (8 * 512 + 8 * 8200)
    step = sambay_bytes.decode_bytes_per_step(lm, 4.0, keys)
    assert step == 2 * resident + 4 * (2 * state + 5120 + 9 * 5120) \
        + keys * 5120
    assert step / 1e9 == pytest.approx(8.80, abs=0.01)
    # had every one of 32 layers a cache of its own
    assert (2 * resident + 3 * 32 * 8200 * 5120) / 1e9 \
        == pytest.approx(11.74, abs=0.01)


def test_prefill_flops_against_hand_counts():
    lm = config()["lm"]
    positions, rows, real = 4 * 8192, 4.0, 8127
    front = (2.0 * 1_870_888_960 + 9 * 6.0 * 5120 * 16) * positions
    back = 2.0 * 1_468_006_400 * rows
    band = 8 * (512 * 513 / 2 + (real - 512) * 512)
    attention = 4.0 * 2560 * rows * (band + 8 * real)
    head = 2.0 * 2560 * 200_064 * rows
    assert sambay_bytes.band_pairs(512, real) == band / 8
    assert sambay_bytes.band_pairs(512, 100) == 100 * 101 / 2
    assert sambay_bytes.prefill_flops(lm, positions, rows, rows, real) \
        == front + back + attention + head
    assert (front / 1e12, back / 1e12, attention / 1e12) == (
        pytest.approx(122.75, abs=0.01), pytest.approx(0.0117, abs=0.0001),
        pytest.approx(1.32, abs=0.01))
    # the band, not the square: the triangle of 8 window layers alone
    # would be 8 times the band's pairs
    assert 8 * real * (real + 1) / 2 / band == pytest.approx(8.2, abs=0.1)
    # a back half over EVERY position would nearly double the products
    assert 2.0 * 1_468_006_400 * positions / front \
        == pytest.approx(0.78, abs=0.01)


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
            "lm.cross_positions": executions * 4,
            "lm.scan_chunks": executions * 4 * 9 * 16,
            "lm.state_steps": executions * 4 * 9 * 64,
            "lm.keys_attended_ring": requests * 64 * 8 * 512,
            "lm.keys_attended_full": requests * 64 * 8 * 8160})
    prof = ctx.metrics_window.get("profile")
    if prof:
        program = prof["programs"]["jit_lm_generate"]
        if classes:
            program["classes"].update(lm_gmu=0.012, lm_cross=0.09,
                                      lm_ssm=0.8, lm_state=0.02)
        if account:
            program["phases"] = {"prefill": 2.4, "decode": 0.96}
            program["account"] = {"by_class": {}, "by_phase": {
                "prefill": {"lm_proj": 0.6, "lm_mlp": 0.7, "lm_attn": 0.25,
                            "lm_ssm": 0.75 if classes else 0.0,
                            "lm_state": 0.01 if classes else 0.0,
                            "idle": 0.09},
                "decode": {"lm_proj": 0.3, "lm_mlp": 0.4, "lm_attn": 0.05,
                           "lm_cross": 0.09, "lm_head": 0.1, "idle": 0.02}}}
    return ctx


def test_the_two_class_readers_are_their_classes_seconds_per_request():
    ctx = context()
    assert reader("lm_gmu_device_s_per_request")(ctx) \
        == pytest.approx(0.012 / 3)
    assert reader("lm_cross_device_s_per_request")(ctx) \
        == pytest.approx(0.09 / 3)
    # and the state-space hybrid's two read this family's scan and states
    assert reader("lm_ssm_device_s_per_request")(ctx) \
        == pytest.approx(0.82 / 3)
    assert reader("lm_prefill_ssm_device_s_per_request")(ctx) \
        == pytest.approx(0.76 / 3)


def test_the_roofline_reader_counts_a_key_once_for_each_layer_that_reads_it():
    ctx = context()
    lm = ctx.config["lm"]
    # 3 requests in 4 program rows, 64 steps of the decode phase's WALL
    # seconds; a step: what the three real rows saw in eight rings and,
    # through eight readers, in the one cache
    nbytes = sambay_bytes.decode_bytes_per_step(
        lm, 4.0, 3 * 8 * (512 + 8160))
    want = 100.0 * (nbytes / 819e9) / (0.96 / 64)
    assert reader("lm_sambay_decode_hbm_roofline_pct")(ctx) \
        == pytest.approx(want)
    assert 70 < want < 73 and want < 100
    # the counter decides, not the shapes
    ctx.metrics_window["pipeline"]["counters"]["lm.keys_attended_full"] //= 8
    assert reader("lm_sambay_decode_hbm_roofline_pct")(ctx) < want - 5


def test_the_utilisation_reader_counts_what_the_program_counted():
    ctx = context()
    flops = sambay_bytes.prefill_flops(ctx.config["lm"], 4 * 8192, 4, 4.0,
                                       8127)
    want = 100.0 * flops / 2.4 / 197e12
    assert reader("lm_sambay_prefill_flops_util_pct")(ctx) \
        == pytest.approx(want)
    assert 25 < want < 28
    # a program that ran its back half over every position is credited
    # with those products; one that computed a tenth of the front with a
    # tenth, whatever `prompt_tokens` says
    counters = ctx.metrics_window["pipeline"]["counters"]
    counters["lm.prefill_positions"] //= 10
    assert reader("lm_sambay_prefill_flops_util_pct")(ctx) < 0.2 * want


@pytest.mark.parametrize("other", [
    "ouro-2.6b-expand-sd15-512", "pangu-ultra-moe-expand-sd15-512",
    "k-exaone-236b-expand-sd15-512", "granite-4.0-h-micro-expand-sd15-512",
    KEYE])
@pytest.mark.parametrize("name", NEW_READERS)
def test_the_new_readers_give_nothing_on_the_other_families_programs(
        name, other):
    """The other five families' programs, and the parent's, have no such
    class and count no such thing: the readers give nothing and do not
    raise; nor without a trace, a profile, an account or the program's
    pattern."""
    nothing = dict(classes=False, counted=False)
    ctx = context(name=other, **nothing)
    if "granite" in other or "keye" in other:   # they count positions too
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


@pytest.mark.parametrize("name", OTHER_FAMILIES)
def test_the_other_families_readers_are_not_asked_of_this_cell(name):
    m = manifest()
    (x,) = [x for x in m["per_layer"] if x["name"] == name]
    assert CELL not in x["workloads"]


def test_the_accepted_lm_readers_read_the_new_program():
    ctx = context(lm_s=3.4)
    assert reader("lm_device_s_per_request")(ctx) == pytest.approx(3.4 / 3)
    assert reader("lm_decode_ms_per_token")(ctx) \
        == pytest.approx(3400.0 / 64)
    assert reader("lm_mlp_device_s_per_request")(ctx) is not None
    assert reader("lm_attn_device_s_per_request")(ctx) > 0
    assert reader("lm_decode_step_ms")(ctx) == pytest.approx(15.0)
    assert reader("lm_prefill_device_s_per_request")(ctx) \
        == pytest.approx(2.4 / 3)
    assert reader("lm_prefill_attn_device_s_per_request")(ctx) \
        == pytest.approx((0.6 + 0.25) / 3)
    assert 0 < reader("lm_share_of_busy_pct")(ctx) < 100


# --- the cell and the verify script, rehearsed --------------------------------------

def _env():
    env = {k: v for k, v in os.environ.items() if not k.startswith("DTPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_the_cell_rehearses_on_the_cpu(tmp_path):
    """``run.py --rehearse`` of the new cell: a tiny model of THIS family
    behind the same nodes, hand-over and drain wait, every request
    served, nothing compiled in the window, the program's counters on the
    window's record: every row started from the snapshot of the
    rehearsal's instructions, the front walked what follows them, the
    back half ran on one position a row."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 46), "--seconds", "4", "--trace", "0",
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
    # every row starts from the snapshot of the rehearsal's instructions
    # (14 ids with the first; nothing here reads a position's index, so
    # it stands at each row's offset) and the front walks the 34 of 48
    # positions behind them: 6 chunks of 6 behind 2 padded positions; 4
    # new tokens, 3 Mamba layers
    held, walked = 14, 36
    assert counters["lm.prefix_hits"] == counters["lm.rows"]
    assert counters["lm.prefix_positions_served"] \
        == counters["lm.rows"] * held
    assert "lm.prefix_misses" not in counters      # made by the warm-ups
    assert "lm.prefix_evictions" not in counters
    assert counters["lm.prefill_positions"] == rows * walked
    assert counters["lm.cross_positions"] == rows
    assert counters["lm.scan_chunks"] == rows * 3 * 6
    assert counters["lm.state_steps"] == rows * 3 * 4
    # a window of 8 in two layers; one cache read by two
    assert counters["lm.keys_attended_ring"] == counters["lm.rows"] * 4 * 2 * 8
    assert counters["lm.keys_attended_full"] \
        > counters["lm.keys_attended_ring"]


def test_the_cells_instructions_are_a_true_prefix_of_every_request():
    """What `LanguageModel.shared_prefix` needs of the CELL's traffic, at
    the published vocabulary: the operator's instructions encode to 8,101
    ids (the start id and 8,100 words) that are the first ids of every
    request's prompt, with 1 to 91 ids of the row's own behind them inside
    the 8192 positions.  A silent fall-back to the whole prompt would
    fail here, not only on the chip.  And what stays resident for them."""
    import random
    import numpy as np
    from comfyui_distributed_tpu.models import registry, sambay, tokenizer
    node = config()["graph"]["21"]["inputs"]
    model = registry.LanguageModel(
        "phi-4-mini-flash-reasoning.safetensors", sambay.PHI_4_MINI_FLASH,
        None, tokenizer.make_lm_tokenizer(None, 200064), "phi4flash")
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
    # 91 positions are one chunk of the front's walk, not 512
    assert sambay.chunk_of(model.cfg, 91, {}) == 91
    # one row with other instructions, and the execution runs whole
    other = [*rows[:3], registry.LMRow("a cat", 3, instructions="draw it")]
    assert model.shared_prefix(other, node["prompt_tokens"]) is None
    # nine states and tails 3.2 MB, eight rings' worth 21.0, the cache's
    # part 41.5: 65.7 MB, where a row's whole state at 8,256 is 66.5
    sizes = config()["sizes"]
    assert sambay.prefix_bytes(model.cfg, 8101) == 65_674_240 \
        == sizes["recurrent_state_bytes_a_row_9_layers"] \
        + sizes["ring_bytes_a_row_8_layers_x_512_slots"] \
        + 8101 * sizes["full_cache_bytes_a_position_a_row"]


def test_the_verify_script_rehearses(tmp_path):
    """``verify_lm_sambay.py --rehearse`` (the benchmark's, unedited): its
    served requests carry the rehearsal's instructions, so each starts
    from their snapshot; one request alone and four as the rows of one
    execution inside every limit against the reference of ALL layers at
    EVERY position of the WHOLE prompt, every reading that has to fail
    outside one."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "verify_lm_sambay.py"),
         "--rehearse", "--out", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO, env=_env(), timeout=900)
    assert p.returncode == 0, p.stderr[-3000:] + p.stdout[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["ok"] is True and len(got["served"]) == 5
    for row in got["served"]:
        assert row["correct"] and 14 < row["prompt_ids"] <= 48
    assert got["together"]["executions"] == 1 \
        and got["together"]["rows"] == 4
    for reading in ("weights_8bit", "memory_gated", "window_off"):
        assert got[reading]["correct"] is False, reading
