"""What PR 32 added to the benchmark, tested from outside it (the
benchmark's own test files are not a ``model_config`` PR's to edit): the
expert model's configuration against the catalog, ``lib/lm_moe_bytes.py``
against hand counts, the two readers on a made-up context (with the
program's routing counters, and on a program that has none, as the
parent), and the routing comparison of ``verify_lm_moe.py``.
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")
sys.path.insert(0, BENCH)

from lib import lm_moe_bytes                    # noqa: E402

CELL = "pangu_expand_sd15_512_sat4"
CONFIG = "pangu-ultra-moe-expand-sd15-512"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 16,
           "vocab_size": 19200, "num_nextn_predict_layers": 0}


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lm_cell = _load("chipbench_tests_lm_cell_for_moe",
                os.path.join(BENCH, "tests", "test_lm_cell.py"))
verify = _load("chipbench_verify_lm_moe_for_cell",
               os.path.join(BENCH, "verify_lm_moe.py"))


def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def reader(name):
    return _load(f"moe_metric_{name}",
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
    # every width as published
    assert (lm["hidden_size"], lm["num_attention_heads"], lm["q_lora_rank"],
            lm["kv_lora_rank"], lm["qk_nope_head_dim"],
            lm["qk_rope_head_dim"], lm["v_head_dim"],
            lm["moe_intermediate_size"], lm["intermediate_size"],
            lm["num_experts_per_tok"], lm["routed_scaling_factor"]) \
        == (7680, 128, 1536, 512, 128, 64, 128, 2048, 18432, 8, 2.5)
    # the router keeps its published width; the held counts stand beside
    # the published ones
    assert lm["router_outputs"] == 256 == cfg["published"]["n_routed_experts"]
    assert cfg["published"] == {**cfg["published"], "num_hidden_layers": 61,
                                "first_k_dense_replace": 3,
                                "vocab_size": 153600,
                                "num_nextn_predict_layers": 1}
    assert cfg["held_here"] == {
        "dense_blocks": 1, "expert_blocks": 4, "routed_experts": [48, 64],
        "vocabulary_rows": 19200, "chips_sharing_a_layer": 16}
    assert "16 chips share each layer" in cfg["deployment"]
    assert "NOT HELD" in cfg["multi_token_prediction"]
    assert len(cfg["assumed"]) >= 10
    # no width among the cuts
    for key in cfg["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) \
            or key == "vocab_size"
    # every key stands at the top level too, where the driver compares
    for key, value in lm.items():
        if key not in ("router_outputs", "dense_layers_held",
                       "experts_first"):
            assert cfg[key] == value, key
    if os.path.isfile(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            rows = [json.loads(line) for line in f]
        (row,) = [r for r in rows if r["name"] == "openPangu-Ultra-MoE-718B"]
        assert cfg["source"] == row["source_url"]
        differs = {k: cfg[k] for k, v in row["config"].items()
                   if cfg.get(k) != v}
        assert differs == REDUCED


def test_the_program_serves_what_the_configuration_states():
    from comfyui_distributed_tpu.models import mla_moe
    lm, share = config()["lm"], mla_moe.OPENPANGU_ULTRA_MOE_SHARE
    same = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok", "n_shared_experts",
            "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
            "rope_theta", "vocab_size", "num_hidden_layers")
    for key in same:
        assert getattr(share, key) == lm[key], key
    assert share.n_routed_experts == lm["router_outputs"]
    assert share.experts_held == lm["n_routed_experts"]
    assert share.experts_first == lm["experts_first"]
    assert share.first_k_dense_replace == lm["dense_layers_held"]
    # and the bytes of the benchmark count the program's tree
    assert lm_moe_bytes.resident_params(lm) \
        + 4 * 16 * lm_moe_bytes.expert_params(lm) \
        + lm["hidden_size"] * lm["vocab_size"] \
        == mla_moe.param_count(share)


# --- bytes from shapes -----------------------------------------------------------

def test_decode_bytes_against_hand_counts():
    lm = config()["lm"]
    mla = 7680 * 1536 + 1536 + 1536 * 128 * 192 + 7680 * 576 + 512 \
        + 512 * 128 * 256 + 128 * 128 * 7680
    assert lm_moe_bytes.attention_params(lm) == mla == 196_577_280
    assert lm_moe_bytes.expert_params(lm) == 3 * 7680 * 2048 == 47_185_920
    assert lm_moe_bytes.latent_values_per_position(lm) == 5 * 576
    resident = (mla + 4 * 7680 + 3 * 7680 * 18432) \
        + 4 * (mla + 4 * 7680 + 7680 * 256 + 47_185_920) \
        + 7680 + 7680 * 19200
    assert lm_moe_bytes.resident_params(lm) == resident
    assert resident * 2 / 1e9 == pytest.approx(3.504, abs=0.001)
    empty = lm_moe_bytes.decode_bytes_per_step(lm)
    assert empty == 2 * (resident + 7680 + 5 * 576)
    # a cached position adds 5.8 KB a row; a hit expert 94.4 MB
    assert lm_moe_bytes.decode_bytes_per_step(lm, 95, 4) - empty \
        == 2 * (3 * (7680 + 5 * 576) + 4 * 95 * 5 * 576)
    assert lm_moe_bytes.decode_bytes_per_step(lm, hits=5.8) - empty \
        == pytest.approx(5.8 * 94_371_840)
    # ISSUE 32: 4.2 GB a step of 4 rows reading only the hit experts
    # (7.6 over four layers), 9.5 GB if all 16 are streamed
    assert lm_moe_bytes.decode_bytes_per_step(lm, 95, 4, 7.6) / 1e9 \
        == pytest.approx(4.25, abs=0.05)
    assert lm_moe_bytes.decode_bytes_per_step(lm, 95, 4, 64) / 1e9 \
        == pytest.approx(9.57, abs=0.05)


# --- the readers -------------------------------------------------------------------

def context(routing=True, requests=21, rows=3, padded=1, **kw):
    """test_lm_cell's made-up window with this cell's configuration and,
    with ``routing``, what the expert model's program counts and the
    class its trace summary has."""
    ctx = lm_cell.context(requests=requests, rows=rows, padded=padded, **kw)
    ctx.config = config()
    counters = ctx.metrics_window["pipeline"]["counters"]
    if routing:
        executions = counters["lm.executions"]
        counters.update({
            "lm.expert_pairs": requests * 64 * 4 * 8,
            "lm.expert_pairs_local": requests * 64 * 2,
            "lm.expert_hits": int(executions * 64 * 5.5),
            "lm.expert_pairs_dropped": 0})
        prof = ctx.metrics_window.get("profile")
        if prof:
            prof["programs"]["jit_lm_generate"]["classes"]["lm_experts"] \
                = 0.09
    return ctx


def test_the_roofline_reader_counts_only_the_experts_hit():
    ctx = context(lm_s=0.5)
    lm = ctx.config["lm"]
    # 3 requests in 4 program rows, 64 steps, 27 prompt ids a row: the
    # mean position a step attends to is 27 + 31.5; 5.5 experts a step
    nbytes = lm_moe_bytes.decode_bytes_per_step(lm, 27 + 31.5, 4.0, 5.5)
    want = 100.0 * (nbytes / 819e9) / (0.5 / 64)
    assert reader("lm_moe_decode_hbm_roofline_pct")(ctx) \
        == pytest.approx(want)
    assert 60 < want < 70
    # a program that hit every expert it holds is held to more bytes
    ctx.metrics_window["pipeline"]["counters"]["lm.expert_hits"] *= 11
    assert reader("lm_moe_decode_hbm_roofline_pct")(ctx) > 100


def test_the_experts_reader_is_per_request():
    assert reader("lm_experts_device_s_per_request")(context()) \
        == pytest.approx(0.09 / 3)


@pytest.mark.parametrize("name", ["lm_moe_decode_hbm_roofline_pct",
                                  "lm_experts_device_s_per_request"])
def test_the_new_readers_give_nothing_on_a_program_without_experts(name):
    """The parent serves this cell with a model that has no router: no
    ``lm.expert_*`` counter and no ``lm_experts`` class.  The readers give
    nothing and do not raise; nor without a trace, a profile, or the
    program's pattern."""
    assert reader(name)(context(routing=False)) is None
    assert reader(name)(context(traced=False)) is None
    assert reader(name)(context(profile=False)) is None or \
        name == "lm_moe_decode_hbm_roofline_pct"
    ctx = context()
    del ctx.config["programs"]["lm_generate"]
    assert reader(name)(ctx) is None
    ctx = context()
    for key in ("lm.executions", "lm.rows", "lm.padded_rows"):
        del ctx.metrics_window["pipeline"]["counters"][key]
    assert reader(name)(ctx) is None


def test_the_accepted_lm_readers_read_the_expert_models_program():
    """The cell lists five of the six accepted language-model readers
    (not the dense decoder's byte count): each finds its program and its
    classes in this configuration."""
    ctx = context(lm_s=0.5)
    assert reader("lm_device_s_per_request")(ctx) == pytest.approx(0.5 / 3)
    assert reader("lm_decode_ms_per_token")(ctx) \
        == pytest.approx(500.0 / 64)
    assert reader("lm_mlp_device_s_per_request")(ctx) > 0
    assert reader("lm_attn_device_s_per_request")(ctx) > 0
    assert 0 < reader("lm_share_of_busy_pct")(ctx) < 100


# --- the routing comparison -----------------------------------------------------------

def _scores(seed=0, n=6, layers=2, experts=16):
    return np.random.default_rng(seed).uniform(
        0.05, 0.95, (n, layers, experts))


def _top(scores, k=4):
    return np.argsort(-scores, axis=-1)[..., :k]


def test_equal_routing_is_correct_and_a_far_flip_is_not():
    ref = _scores()
    got = verify.compare_routing(ref + 1e-4, _top(ref), ref, 1e-3)
    assert got["correct"] and got["flipped"] == 0
    assert got["choices"] == 12 and got["scores_max_diff"] < 1e-3
    # the program chose the reference's LAST expert in place of its first
    wrong = _top(ref)
    wrong[0, 0, 0] = np.argsort(-ref[0, 0])[-1]
    got = verify.compare_routing(ref, wrong, ref, 1e-3)
    assert not got["correct"] and got["unexcused_flips"] == 1
    assert got["flipped"] == 1 and got["flipped_share"] == 1 / 12
    # scores off by more than the tolerance fail on their own
    assert not verify.compare_routing(ref + 0.01, _top(ref), ref,
                                      1e-3)["correct"]


def test_a_flip_is_excused_only_where_the_references_own_cut_is_that_close():
    ref = _scores(1)
    order = np.argsort(-ref[2, 1])
    fourth, fifth = order[3], order[4]
    # the reference's 4th and 5th 0.001 apart: the program took the 5th
    ref[2, 1, fifth] = ref[2, 1, fourth] - 0.001
    swapped = _top(ref)
    swapped[2, 1, 3] = fifth
    assert set(swapped[2, 1]) != set(_top(ref)[2, 1])
    near = verify.compare_routing(ref, swapped, ref, 1e-3)
    assert near["correct"] and near["flipped"] == 1
    assert near["smallest_margin"] == pytest.approx(0.001)
    # the same swap where the cut is 0.01 wide is a fault
    ref[2, 1, fifth] = ref[2, 1, fourth] - 0.01
    far = verify.compare_routing(ref, swapped, ref, 1e-3)
    assert not far["correct"] and far["unexcused_flips"] == 1


def test_the_programs_choices_cover_every_position_the_rows_depend_on():
    served = {"prompt_ids": np.arange(5),
              "prompt_choices": np.arange(8 * 2 * 4).reshape(8, 2, 4),
              "expert_choices": -np.arange(3 * 2 * 4).reshape(3, 2, 4)}
    got = verify.program_choices(served)
    # prompt positions 0..3 (the buffer's 3..6; the last prompt id's
    # choices are the first decoded position's), then the 3 decoded
    assert got.shape == (4 + 3, 2, 4)
    assert np.array_equal(got[:4], served["prompt_choices"][3:7])
    assert np.array_equal(got[4:], served["expert_choices"])
