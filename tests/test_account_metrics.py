"""The six readers PR 38 added to the benchmark (``denoise_gaps_``,
``denoise_other_``, ``denoise_glue_s_per_image``, ``lm_decode_step_ms``,
``lm_prefill_attn_`` and ``lm_prefill_experts_device_s_per_request``) and
``lib/account.py``, tested from outside the benchmark on a made-up context:
each value from a made-up ``profile`` block; nothing (never 0) with no
trace, a summary without ``account`` (the parent's) or a program without
phases; the denoise identity printed; the manifest's entries."""

import copy
import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")
sys.path.insert(0, BENCH)

from lib import account as account_lib              # noqa: E402
from lib.server import BenchFailure                 # noqa: E402

DENOISE_READERS = ["denoise_gaps_s_per_image", "denoise_other_s_per_image",
                   "denoise_glue_s_per_image"]
PHASE_READERS = ["lm_decode_step_ms", "lm_prefill_attn_device_s_per_request",
                 "lm_prefill_experts_device_s_per_request"]
READERS = DENOISE_READERS + PHASE_READERS
# (PR 40's cell stands behind K-EXAONE's wherever the reader is
# family-neutral: it has no routed expert; PR 42's stands last, and its
# model has experts; PR 46's stands behind that, and has none; PR 49's
# last, with experts)
DENOISE_CELLS = ["sdxl_1024_sat", "sd15_512_sat", "ouro_expand_sd15_512_sat4",
                 "pangu_expand_sd15_512_sat4", "exaone_expand_sd15_512_sat4",
                 "granite_expand_sd15_512_sat4", "keye_expand_sd15_512_sat4",
                 "phi4flash_expand_sd15_512_sat4",
                 "longcat_expand_sd15_512_sat4"]
EXPANDER_CELLS = ["ouro_expand_sd15_512_sat", "ouro_expand_sd15_512_sat4",
                  "pangu_expand_sd15_512_sat4", "exaone_expand_sd15_512_sat4",
                  "granite_expand_sd15_512_sat4", "keye_expand_sd15_512_sat4",
                  "phi4flash_expand_sd15_512_sat4",
                  "longcat_expand_sd15_512_sat4"]
CELLS = {"denoise_gaps_s_per_image": DENOISE_CELLS,
         "denoise_other_s_per_image": DENOISE_CELLS,
         "denoise_glue_s_per_image": DENOISE_CELLS,
         "lm_decode_step_ms": EXPANDER_CELLS,
         "lm_prefill_attn_device_s_per_request": EXPANDER_CELLS[-5:],
         "lm_prefill_experts_device_s_per_request": [EXPANDER_CELLS[-5],
                                                     EXPANDER_CELLS[-3],
                                                     EXPANDER_CELLS[-1]]}


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lm_cell = _load("chipbench_tests_lm_cell_for_account",
                os.path.join(BENCH, "tests", "test_lm_cell.py"))


def reader(name):
    return _load(f"account_metric_{name}",
                 os.path.join(BENCH, "layer_metrics", name + ".py")).read


# one denoise execution of 0.36 s: the accepted classes leave 0.012 s of
# convolutions out (``dropped_s``), which their ``gaps`` holds
DENOISE = {
    "count": 2.0, "mean_s": 0.36,
    "classes": {"attn_self": 0.07, "attn_cross": 0.01, "attn_proj": 0.05,
                "ff": 0.04, "resblock": 0.11, "resample": 0.02,
                "norm": 0.01, "sampler": 0.008, "embed": 0.002,
                "other": 0.02, "gaps": 0.02},
    "account": {
        "by_class": {"attn_self": 0.07, "attn_cross": 0.01,
                     "attn_proj": 0.05, "ff": 0.04, "resblock": 0.12,
                     "resample": 0.022, "norm": 0.01, "sampler": 0.008,
                     "embed": 0.002, "other": 0.02, "idle": 0.008},
        "overlap_s": 0.0, "dropped_s": 0.012, "top_idle": []}}
# one execution of the generate program, 0.40 s, 3 requests in 4 rows
GENERATE = {
    "by_class": {"lm_proj": 0.10, "lm_attn": 0.05, "lm_cache": 0.01,
                 "lm_experts": 0.08, "lm_mlp": 0.12, "lm_norm": 0.01,
                 "lm_head": 0.02, "embed": 0.001, "idle": 0.009},
    "overlap_s": 0.0, "dropped_s": 0.0, "top_idle": [],
    "by_phase": {
        "prefill": {"lm_proj": 0.03, "lm_attn": 0.02, "lm_cache": 0.001,
                    "lm_experts": 0.018, "lm_mlp": 0.02, "idle": 0.001},
        "decode": {"lm_proj": 0.07, "lm_attn": 0.03, "lm_cache": 0.009,
                   "lm_experts": 0.062, "lm_mlp": 0.10, "lm_norm": 0.01,
                   "lm_head": 0.02, "embed": 0.001, "idle": 0.006},
        "none": {"idle": 0.002}}}


def context(account=True, phases=True, **kw):
    ctx = lm_cell.context(requests=21, rows=3, padded=1, **kw)
    prof = ctx.metrics_window.get("profile")
    if prof:
        prof["programs"]["jit_core"] = copy.deepcopy(DENOISE)
        prof["programs"]["jit_lm_generate"]["account"] = \
            copy.deepcopy(GENERATE)
        if not phases:
            del prof["programs"]["jit_lm_generate"]["account"]["by_phase"]
        if not account:
            for program in prof["programs"].values():
                program.pop("account", None)
    return ctx


def test_the_three_denoise_readers_split_what_no_accepted_reader_reads(
        capsys):
    ctx = context()
    assert reader("denoise_gaps_s_per_image")(ctx) == pytest.approx(0.008)
    assert reader("denoise_other_s_per_image")(ctx) == pytest.approx(0.02)
    # sampler + embed: every class beside the seven accepted ones
    assert reader("denoise_glue_s_per_image")(ctx) == pytest.approx(0.010)
    out = capsys.readouterr().out
    # the identity beside each value: the accepted groups as ``classes``
    # has them, then the account's three, against the execution
    assert out.count("attn + proj_ff + conv + norm (inclusive, accepted) "
                     "0.310000 + glue 0.010000 + other 0.020000 + gaps "
                     "0.008000 (exclusive) = 0.348000 against denoise "
                     "0.360000; overlap_s 0.000000, dropped_s 0.012000") == 3


def test_a_class_named_later_is_glue_and_per_image_divides_by_the_batch():
    ctx = context()
    by_class = ctx.metrics_window["profile"]["programs"]["jit_core"][
        "account"]["by_class"]
    by_class["cfg"] = by_class.pop("other")
    for node in ctx.config["graph"].values():
        if node["class_type"] == "EmptyLatentImage":
            node["inputs"]["batch_size"] = 2
    assert reader("denoise_glue_s_per_image")(ctx) == pytest.approx(0.015)
    # no operation of no class: the row is read as 0 s, the account exists
    assert reader("denoise_other_s_per_image")(ctx) == 0.0
    assert reader("denoise_gaps_s_per_image")(ctx) == pytest.approx(0.004)


def test_a_step_is_the_decode_phases_wall_seconds_over_its_steps(capsys):
    # every row under ``decode``, its idle stretches too: 0.308 s, 64 steps
    assert reader("lm_decode_step_ms")(context()) \
        == pytest.approx(1e3 * 0.308 / 64)
    assert "0.308000 s under `decode` (0.006000 idle) in 64.0 steps; an " \
        "execution served 3.000 request(s) in 4.000 program row(s)" \
        in capsys.readouterr().out


def test_the_prefills_attention_and_experts_per_request():
    ctx = context()
    assert reader("lm_prefill_attn_device_s_per_request")(ctx) \
        == pytest.approx((0.03 + 0.02 + 0.001) / 3)
    assert reader("lm_prefill_experts_device_s_per_request")(ctx) \
        == pytest.approx(0.018 / 3)
    # a model without experts has no such row: nothing, not 0
    del ctx.metrics_window["profile"]["programs"]["jit_lm_generate"][
        "account"]["by_phase"]["prefill"]["lm_experts"]
    assert reader("lm_prefill_experts_device_s_per_request")(ctx) is None


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("why", [
    "no trace", "no summary", "a summary without account (the parent's)"])
def test_a_reader_gives_nothing_never_zero(name, why):
    ctx = {"no trace": lambda: context(traced=False),
           "no summary": lambda: context(profile=False),
           "a summary without account (the parent's)":
               lambda: context(account=False)}[why]()
    assert reader(name)(ctx) is None


@pytest.mark.parametrize("name", PHASE_READERS)
def test_a_program_without_phases_gives_nothing(name):
    assert reader(name)(context(phases=False)) is None
    # nor a configuration that names no generate program (the UNet cells')
    ctx = context()
    del ctx.config["programs"]["lm_generate"]
    assert reader(name)(ctx) is None


def test_two_programs_under_one_pattern_are_an_error_never_a_sum():
    ctx = context()
    programs = ctx.metrics_window["profile"]["programs"]
    programs["jit_core_2"] = copy.deepcopy(programs["jit_core"])
    ctx.config["programs"]["denoise"] = "jit_core"
    with pytest.raises(BenchFailure, match="2 programs"):
        reader("denoise_gaps_s_per_image")(ctx)


def test_glue_is_every_class_but_the_seven_read_other_and_idle():
    assert account_lib.glue_s(DENOISE["account"]["by_class"]) \
        == pytest.approx(0.010)
    assert set(account_lib.READ_CLASSES) == {
        "attn_self", "attn_cross", "attn_proj", "ff", "resblock",
        "resample", "norm"}


# --- the manifest ----------------------------------------------------------------

@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("name", READERS)
def test_the_manifests_entry(manifest, name):
    """The metric is a file, lists cells that exist, and moves an
    end-to-end metric that each of them reports."""
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["source"] == "device_trace" and entry["better"] == "lower"
    assert entry["unit"] == ("ms" if name.endswith("_ms") else "s")
    assert entry["layer"] == ("Denoise" if name in DENOISE_READERS
                              else "Language model")
    assert entry["workloads"] == CELLS[name]
    cells = {w["name"] for w in manifest["workloads"]}
    assert set(entry["workloads"]) <= cells
    (moved,) = [m for m in manifest["end_to_end"]
                if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved.get("workloads", cells))


def test_the_six_are_appended_and_nothing_that_was_there_moved(manifest):
    names = [m["name"] for m in manifest["per_layer"]]
    # (PR 40 appended four behind them, PR 42 four more, PR 46 four, PR 49
    # three, PR 51 nine)
    assert names[35:41] == READERS and len(names) == len(set(names)) == 65
    assert names[34] == "lm_prefill_flops_util_pct"
    # the denoise readers sit where ``norm_device_s_per_image`` does
    (norm,) = [m for m in manifest["per_layer"]
               if m["name"] == "norm_device_s_per_image"]
    assert norm["workloads"] == DENOISE_CELLS
