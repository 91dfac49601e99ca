"""The chip benchmark's own tests, collected under ``pytest tests/``.

``benchmarks/chip/tests/`` lives beside the benchmark (the one directory a
benchmark PR may write to), where tier-1 never looked: until this file
nothing in tier-1 guarded the reducer, the FLOP count, the contract line
or the readers.  Every test function of those files is re-exported here
under its own name, so each is a case of its own (parametrised ones keep
their cases); none is marked slow.
"""

import importlib.util
import json
import os

import pytest

_TESTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip", "tests")


_MODULES = {}


def _collect():
    for fname in sorted(os.listdir(_TESTS)):
        if not (fname.startswith("test_") and fname.endswith(".py")):
            continue
        spec = importlib.util.spec_from_file_location(
            "chipbench_tests_" + fname[:-3], os.path.join(_TESTS, fname))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[fname[:-3]] = mod
        for name, obj in vars(mod).items():
            if name.startswith("test_") and callable(obj):
                assert name not in globals(), f"two tests named {name}"
                globals()[name] = obj


_collect()


# the cell PR 28 appended, to the cells and to the ``workloads`` of the
# metrics it reports, and the one PR 32 appended behind it (PR 34's,
# EXAONE4, is further down, with the test of the three)
SAT4 = "ouro_expand_sd15_512_sat4"
PANGU4 = "pangu_expand_sd15_512_sat4"
ROOT = _MODULES["test_span_metrics"].ROOT


def _manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_four_caller_cell_is_an_entry_with_a_mix_of_its_own():
    """PR 28 added data alone: a mix file and entries.  The cell is the
    two-caller cell with four callers, and reports what that one does."""
    m = _manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    two, four = cells["ouro_expand_sd15_512_sat"], cells[SAT4]
    assert four in m["workloads"] and len(four["why"]) <= 200
    assert {k: four[k] for k in ("config", "chips")} == \
        {k: two[k] for k in ("config", "chips")}
    assert four["traffic"] == "closed4_unique" != two["traffic"]
    bench = os.path.dirname(_TESTS)
    mixes = {}
    for name in ("closed2_unique", "closed4_unique"):
        with open(os.path.join(bench, "traffic", name + ".json")) as f:
            mixes[name] = json.load(f)
    assert {**mixes["closed4_unique"], "clients": 2, "why": ""} == \
        {**mixes["closed2_unique"], "why": ""}
    assert mixes["closed4_unique"]["clients"] == 4
    # the generator that is there serves it: four texts, all distinct
    traffic = _MODULES["test_chip_benchmark"].Traffic(
        mixes["closed4_unique"], four["config"], 2 ** 31 + 11)
    assert traffic.clients == 4 and traffic.loop == "closed"
    assert len({traffic.next_request()["text"] for _ in range(8)}) == 8
    for group in ("end_to_end", "per_layer"):
        for x in m[group]:
            cells_of = x.get("workloads", [SAT4])
            if "ouro_expand_sd15_512_sat" in cells_of:
                assert SAT4 in cells_of, x["name"]


# The three expander cells under ``closed4_unique``: each reports every
# accepted share of a roofline or of a peak that moves what it reports (a
# claim in a cell needs them: PR 27 was refused for ``attn_roofline_pct``),
# but for the stated exceptions, each with what its reader would find
# there.  Shares are told by their unit and by what they move, not by a
# word in their name: ``denoise_flops_util_pct`` is a share of the peak
# too.
EXAONE4 = "exaone_expand_sd15_512_sat4"
_ONE_CHIP = "the least busy of the chips of a mesh: one chip"
_EXAONE_ONLY = {
    "lm_swa_moe_decode_hbm_roofline_pct": "the bytes of a decoder with a "
                                          "ring beside a full cache, from "
                                          "counters only its program has",
    "lm_prefill_flops_util_pct": "the FLOPs of a 512-position prefill under "
                                 "a phase scope only that program has",
}
# (PR 40: the two shares of the decoder of state-space and attention layers)
GRANITE4 = "granite_expand_sd15_512_sat4"
_GRANITE_ONLY = {
    "lm_ssm_decode_hbm_roofline_pct": "the bytes of a decoder that reads "
                                      "and writes a recurrent state, from "
                                      "counters only its program has",
    "lm_ssm_prefill_flops_util_pct": "the FLOPs of a prefill with a "
                                     "recurrence, over positions only that "
                                     "program counts",
}
# (PR 42: the two shares of the decoder with a learned key selection)
KEYE4 = "keye_expand_sd15_512_sat4"
_KEYE_ONLY = {
    "lm_dsa_decode_hbm_roofline_pct": "the bytes of a decoder that scores "
                                      "an index-key cache and gathers the "
                                      "keys chosen, from counters only its "
                                      "program has",
    "lm_dsa_prefill_flops_util_pct": "the FLOPs of a prefill with index "
                                     "scores and attention over a "
                                     "selection, from the same counters",
}
# (PR 46: the two shares of the decoder-hybrid-decoder)
PHI4 = "phi4flash_expand_sd15_512_sat4"
_PHI_ONLY = {
    "lm_sambay_decode_hbm_roofline_pct": "the bytes of a decoder whose one "
                                         "cache is read by eight layers and "
                                         "whose states by nine, from "
                                         "counters only its program has",
    "lm_sambay_prefill_flops_util_pct": "the FLOPs of a prefill whose back "
                                        "half runs on one position a row, "
                                        "from the same counters",
}
# (PR 49: the two shares of the decoder with a shortcut-connected expert
# layer and zero-compute experts)
LONGCAT4 = "longcat_expand_sd15_512_sat4"
_LONGCAT_ONLY = {
    "lm_scmoe_decode_hbm_roofline_pct": "the bytes of a decoder with two "
                                        "latent attentions and two dense "
                                        "MLPs a layer and nothing for its "
                                        "zero experts, from counters only "
                                        "its program has",
    "lm_scmoe_prefill_flops_util_pct": "the FLOPs of that block's prefill, "
                                       "from the same counters",
}
NOT_IN_SAT4 = {
    "chip_busy_min_pct": _ONE_CHIP,
    "lm_moe_decode_hbm_roofline_pct": "the bytes of a decoder with routed "
                                      "experts: Ouro has none to count",
    **_EXAONE_ONLY, **_GRANITE_ONLY, **_KEYE_ONLY, **_PHI_ONLY,
    **_LONGCAT_ONLY,
}
NOT_IN_PANGU4 = {
    "chip_busy_min_pct": _ONE_CHIP,
    "lm_decode_hbm_roofline_pct": "its byte count is a dense looped "
                                  "decoder's and would be false here: "
                                  "lm_moe_decode_hbm_roofline_pct stands "
                                  "in its place",
    **_EXAONE_ONLY, **_GRANITE_ONLY, **_KEYE_ONLY, **_PHI_ONLY,
    **_LONGCAT_ONLY,
}
NOT_IN_EXAONE4 = {
    "chip_busy_min_pct": _ONE_CHIP,
    "lm_decode_hbm_roofline_pct": NOT_IN_PANGU4["lm_decode_hbm_roofline_pct"],
    "lm_moe_decode_hbm_roofline_pct": "its byte count reads q_lora_rank and "
                                      "kv_lora_rank, a latent cache's: "
                                      "lm_swa_moe_decode_hbm_roofline_pct "
                                      "stands in its place",
    **_GRANITE_ONLY, **_KEYE_ONLY, **_PHI_ONLY, **_LONGCAT_ONLY,
}
NOT_IN_GRANITE4 = {
    "chip_busy_min_pct": _ONE_CHIP,
    "lm_decode_hbm_roofline_pct": NOT_IN_PANGU4["lm_decode_hbm_roofline_pct"],
    "lm_moe_decode_hbm_roofline_pct": "it has no routed expert and no "
                                      "latent cache",
    **{name: "it has no routed expert, no ring and counts no local pairs: "
             "its own two stand in their place" for name in _EXAONE_ONLY},
    **_KEYE_ONLY, **_PHI_ONLY, **_LONGCAT_ONLY,
}
NOT_IN_KEYE4 = {
    "chip_busy_min_pct": _ONE_CHIP,
    "lm_decode_hbm_roofline_pct": NOT_IN_PANGU4["lm_decode_hbm_roofline_pct"],
    "lm_moe_decode_hbm_roofline_pct": "it has no latent cache",
    **{name: "it has no ring, and what a step reads of its cache is chosen "
             "by an index: its own two stand in their place"
       for name in _EXAONE_ONLY},
    **_GRANITE_ONLY, **_PHI_ONLY, **_LONGCAT_ONLY,
}
NOT_IN_PHI4 = {
    "chip_busy_min_pct": _ONE_CHIP,
    "lm_decode_hbm_roofline_pct": NOT_IN_PANGU4["lm_decode_hbm_roofline_pct"],
    "lm_moe_decode_hbm_roofline_pct": "it has no latent cache and no expert",
    **{name: "its rings stand beside states and ONE cache that eight "
             "layers read: its own two stand in their place"
       for name in {**_EXAONE_ONLY, **_GRANITE_ONLY, **_KEYE_ONLY}},
    **_LONGCAT_ONLY,
}
NOT_IN_LONGCAT4 = {
    "chip_busy_min_pct": _ONE_CHIP,
    "lm_decode_hbm_roofline_pct": NOT_IN_PANGU4["lm_decode_hbm_roofline_pct"],
    "lm_moe_decode_hbm_roofline_pct": "it counts ONE attention, a shared "
                                      "expert and sandwich norms a block "
                                      "and would be false here: two "
                                      "attentions, two dense MLPs, no "
                                      "shared expert, zero experts that "
                                      "move nothing",
    **{name: "its block is another (two latent slots a layer, experts "
             "beside dense MLPs): its own two stand in their place"
       for name in {**_EXAONE_ONLY, **_GRANITE_ONLY, **_KEYE_ONLY,
                    **_PHI_ONLY}},
}


def _config(name: str) -> dict:
    with open(os.path.join(os.path.dirname(_TESTS), "configs",
                           name + ".json")) as f:
        return json.load(f)


def _listed(m: dict, cell: str) -> set:
    return {x["name"] for x in m["per_layer"]
            if cell in x.get("workloads", [])}


def _sat4_reports(m, cells):
    """The four-caller cell: what the layers its graph runs report in
    their own cells (SD1.5's denoise by class, the VAE, CLIP), from
    sources its configuration has."""
    by_layer = {}
    for x in m["per_layer"]:
        if SAT4 in x.get("workloads", []):
            by_layer.setdefault(x["layer"], set()).add(x["name"])
    assert by_layer["Denoise"] == {
        x["name"] for x in m["per_layer"] if x["layer"] == "Denoise"}
    assert by_layer["VAE decode"] == {"vae_device_s_per_image"}
    assert by_layer["Text encode"] == {"clip_device_ms_per_request"}
    cfg, sd15 = _config("ouro-2.6b-expand-sd15-512"), _config("sd15-512")
    kernels = _MODULES["test_span_metrics"].kernels
    bound = kernels.attention_bound(cfg, {"bf16_flops_per_s": 197e12,
                                          "hbm_bytes_per_s": 819e9})
    assert bound["bound"] == "compute"
    assert round(bound["ops"] / 1e12, 3) == 5.189
    assert round(bound["seconds"], 5) == 0.02634
    flops = _MODULES["test_chip_benchmark"].flops
    assert flops.denoise_flops_per_image(cfg) == \
        flops.denoise_flops_per_image(sd15)
    assert set(cfg["programs"]) == set(sd15["programs"]) | {"lm_generate"}


def _same_graph_but(cfg: dict, other: dict, nodes: set):
    """``cfg`` is ``other``'s configuration with another language model
    in front: the same image model, programs and trace slice, the graphs
    differing in ``nodes`` alone."""
    flops = _MODULES["test_chip_benchmark"].flops
    assert flops.denoise_flops_per_image(cfg) == \
        flops.denoise_flops_per_image(other)
    assert cfg["programs"] == other["programs"]
    assert cfg["unet"] == other["unet"]
    assert cfg["trace_slice"]["after_counter"] == "lm.executions"
    assert set(cfg["graph"]) == set(other["graph"])
    assert {nid for nid in cfg["graph"]
            if cfg["graph"][nid] != other["graph"][nid]} == nodes


def _pangu4_reports(m, cells):
    """The cell PR 32 appended: the four-caller cell's configuration with
    another language model in front, the one roofline whose bytes are
    architecture-specific exchanged for its own."""
    pangu = cells[PANGU4]
    assert pangu["config"] == "pangu-ultra-moe-expand-sd15-512"
    shares = {x["name"]: x for x in m["per_layer"]}
    assert shares["lm_moe_decode_hbm_roofline_pct"]["workloads"] == [PANGU4]
    assert _listed(m, PANGU4) - _listed(m, SAT4) == {
        "lm_experts_device_s_per_request", "lm_moe_decode_hbm_roofline_pct"}
    assert _listed(m, SAT4) - _listed(m, PANGU4) == {
        "lm_decode_hbm_roofline_pct"}
    cfg = _config(pangu["config"])
    _same_graph_but(cfg, _config("ouro-2.6b-expand-sd15-512"), {"20"})
    assert cfg["graph"]["20"]["inputs"] == {
        "model_name": "openpangu-ultra-moe-718b.safetensors"}


def _exaone4_reports(m, cells):
    """The cell PR 34 appended: openPangu's with a third language model in
    front and the operator's instructions on the generate node; it lists
    what that cell lists but the latent cache's byte count, and three
    readers of its own."""
    exaone = cells[EXAONE4]
    assert exaone["config"] == "k-exaone-236b-expand-sd15-512"
    # (and PR 38's two readers of the prefill by class: only this
    # cell's prefill weighs)
    assert _listed(m, EXAONE4) - _listed(m, PANGU4) == {
        "lm_prefill_device_s_per_request", *_EXAONE_ONLY,
        "lm_prefill_attn_device_s_per_request",
        "lm_prefill_experts_device_s_per_request"}
    assert _listed(m, PANGU4) - _listed(m, EXAONE4) == {
        "lm_moe_decode_hbm_roofline_pct"}
    for x in m["per_layer"]:
        if x["name"] in _listed(m, EXAONE4) - _listed(m, PANGU4):
            # (PR 40's cell stands behind it where the reader is
            # family-neutral)
            # (and PR 42's behind that, and behind the experts' reader)
            # (and PR 46's behind the three)
            # (and PR 49's behind them, the experts' reader too)
            assert x["workloads"] in ([EXAONE4], [EXAONE4, GRANITE4],
                                      [EXAONE4, KEYE4, LONGCAT4],
                                      [EXAONE4, GRANITE4, KEYE4, PHI4,
                                       LONGCAT4]) \
                and x["layer"] == "Language model" \
                and x["source"] == "device_trace"
    cfg = _config(exaone["config"])
    _same_graph_but(cfg, _config("pangu-ultra-moe-expand-sd15-512"),
                    {"20", "21"})
    assert cfg["graph"]["20"]["inputs"] == {
        "model_name": "k-exaone-236b-a23b.safetensors"}
    node = cfg["graph"]["21"]["inputs"]
    assert (node["prompt_tokens"], node["max_new_tokens"],
            node["temperature"]) == (512, 64, 0.0)
    assert len(node["instructions"].split()) == 450
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == \
        ["sdxl_1024_fanout4"]


def _granite4_reports(m, cells):
    """The cell PR 40 appended: K-EXAONE's with a fourth language model in
    front (whole: nothing reduced) behind a 2048-id prompt; it lists what
    that cell lists but the four readers whose counts are K-EXAONE's, and
    four readers of its own."""
    granite = cells[GRANITE4]
    assert granite["config"] == "granite-4.0-h-micro-expand-sd15-512"
    own = _listed(m, GRANITE4) - _listed(m, EXAONE4)
    assert own == {*_GRANITE_ONLY, "lm_ssm_device_s_per_request",
                   "lm_prefill_ssm_device_s_per_request"}
    assert _listed(m, EXAONE4) - _listed(m, GRANITE4) == {
        *_EXAONE_ONLY, "lm_experts_device_s_per_request",
        "lm_prefill_experts_device_s_per_request"}
    for x in m["per_layer"]:
        if x["name"] in own:
            # (PR 46's cell stands behind it in the two state-space
            # readers, which read a class both families have)
            assert x["workloads"] in ([GRANITE4], [GRANITE4, PHI4]) \
                and x["layer"] == "Language model" \
                and x["source"] == "device_trace"
    cfg = _config(granite["config"])
    _same_graph_but(cfg, _config("k-exaone-236b-expand-sd15-512"),
                    {"20", "21"})
    assert cfg["reduced"] == [] and cfg["graph"]["20"]["inputs"] == {
        "model_name": "granite-4.0-h-micro.safetensors"}
    node = cfg["graph"]["21"]["inputs"]
    assert (node["prompt_tokens"], node["max_new_tokens"],
            node["temperature"]) == (2048, 64, 0.0)
    assert len(node["instructions"].split()) == 1950
    # 9 of at most 24 cells with this one, still one on four chips
    assert len(m["workloads"][:9]) == 9 and m["workloads"][8] == granite
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == \
        ["sdxl_1024_fanout4"]


def _keye4_reports(m, cells):
    """The cell PR 42 appended: granite's with a fifth language model in
    front (one pipeline stage: depth alone reduced) behind an 8192-id
    prompt; it lists what granite's lists but the state-space readers,
    the experts' two readers, and four readers of its own."""
    keye = cells[KEYE4]
    assert keye["config"] == "keye-vl-2.0-30b-a3b-expand-sd15-512"
    own = _listed(m, KEYE4) - _listed(m, GRANITE4)
    assert own == {*_KEYE_ONLY, "lm_index_device_s_per_request",
                   "lm_prefill_index_device_s_per_request",
                   "lm_experts_device_s_per_request",
                   "lm_prefill_experts_device_s_per_request"}
    assert _listed(m, GRANITE4) - _listed(m, KEYE4) == {
        *_GRANITE_ONLY, "lm_ssm_device_s_per_request",
        "lm_prefill_ssm_device_s_per_request"}
    for x in m["per_layer"]:
        if x["name"] in _KEYE_ONLY or "_index_" in x["name"]:
            assert x["workloads"] == [KEYE4] and x["layer"] == \
                "Language model" and x["source"] == "device_trace"
    assert [x["name"] for x in m["per_layer"][45:49]] == [
        "lm_index_device_s_per_request",
        "lm_prefill_index_device_s_per_request", *_KEYE_ONLY]
    cfg = _config(keye["config"])
    _same_graph_but(cfg, _config("granite-4.0-h-micro-expand-sd15-512"),
                    {"20", "21"})
    assert cfg["reduced"] == ["num_hidden_layers"] \
        and cfg["graph"]["20"]["inputs"] == {
            "model_name": "keye-vl-2.0-30b-a3b.safetensors"}
    node = cfg["graph"]["21"]["inputs"]
    assert (node["prompt_tokens"], node["max_new_tokens"],
            node["temperature"]) == (8192, 64, 0.0)
    assert len(node["instructions"].split()) == 8100
    # 10 of at most 24 cells with this one, 7 configurations
    assert len(m["workloads"][:10]) == 10 and len(m["configs"][:7]) == 7
    assert m["workloads"][9] == keye
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == \
        ["sdxl_1024_fanout4"]


def _phi4_reports(m, cells):
    """The cell PR 46 appended: Keye's with a sixth language model in
    front (whole: nothing reduced) behind the SAME 8192-id prompt; it
    lists what Keye's and granite's both list, the two state-space
    readers, and four readers of its own."""
    phi = cells[PHI4]
    assert phi["config"] == "phi-4-mini-flash-expand-sd15-512"
    own = {*_PHI_ONLY, "lm_gmu_device_s_per_request",
           "lm_cross_device_s_per_request"}
    assert _listed(m, PHI4) == (_listed(m, KEYE4) & _listed(m, GRANITE4)) \
        | own | {"lm_ssm_device_s_per_request",
                 "lm_prefill_ssm_device_s_per_request"}
    for x in m["per_layer"]:
        if x["name"] in own:
            assert x["workloads"] == [PHI4] and x["layer"] == \
                "Language model" and x["source"] == "device_trace" \
                and x["moves"] == "images_per_s"
    assert [x["name"] for x in m["per_layer"][49:53]] == [
        "lm_gmu_device_s_per_request", "lm_cross_device_s_per_request",
        *_PHI_ONLY]
    cfg = _config(phi["config"])
    keye = _config("keye-vl-2.0-30b-a3b-expand-sd15-512")
    _same_graph_but(cfg, keye, {"20"})
    assert cfg["reduced"] == [] and cfg["graph"]["20"]["inputs"] == {
        "model_name": "phi-4-mini-flash-reasoning.safetensors"}
    # two families behind one prompt: the same instructions bit for bit
    assert cfg["graph"]["21"] == keye["graph"]["21"]
    assert len(cfg["graph"]["21"]["inputs"]["instructions"].split()) == 8100
    # 11 of at most 24 cells with this one, 8 configurations
    assert len(m["workloads"][:11]) == 11 and len(m["configs"][:8]) == 8
    assert m["workloads"][10] == phi
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == \
        ["sdxl_1024_fanout4"]


def _longcat4_reports(m, cells):
    """The cell PR 49 appended: granite's with a seventh language model in
    front (one chip's share of 32: depth, experts and vocabulary reduced)
    behind the SAME 2048-id prompt; it lists what Keye's lists (an expert
    family behind a long prompt) but the readers of Keye's index and of
    its counts, and three readers of its own."""
    longcat = cells[LONGCAT4]
    assert longcat["config"] == "longcat-flash-omni-expand-sd15-512"
    own = {*_LONGCAT_ONLY, "lm_zero_device_s_per_request"}
    assert _listed(m, LONGCAT4) == (_listed(m, KEYE4) - {
        *_KEYE_ONLY, "lm_index_device_s_per_request",
        "lm_prefill_index_device_s_per_request"}) | own
    assert {"lm_experts_device_s_per_request",
            "lm_prefill_device_s_per_request",
            "lm_prefill_attn_device_s_per_request",
            "lm_prefill_experts_device_s_per_request"} \
        <= _listed(m, LONGCAT4)
    for x in m["per_layer"]:
        if x["name"] in own:
            assert x["workloads"] == [LONGCAT4] and x["layer"] == \
                "Language model" and x["source"] == "device_trace" \
                and x["moves"] == "images_per_s"
    assert [x["name"] for x in m["per_layer"][53:56]] == [
        *_LONGCAT_ONLY, "lm_zero_device_s_per_request"]
    cfg = _config(longcat["config"])
    granite = _config("granite-4.0-h-micro-expand-sd15-512")
    _same_graph_but(cfg, granite, {"20"})
    assert cfg["reduced"] == ["num_layers", "n_routed_experts",
                              "vocab_size"] \
        and cfg["graph"]["20"]["inputs"] == {
            "model_name": "longcat-flash-omni.safetensors"}
    # two families behind one prompt: the same instructions bit for bit
    assert cfg["graph"]["21"] == granite["graph"]["21"]
    assert len(cfg["graph"]["21"]["inputs"]["instructions"].split()) == 1950
    # 12 of at most 24 cells, 9 configurations, still one on four chips
    assert len(m["workloads"]) == 12 and len(m["configs"]) == 9
    assert m["workloads"][-1] == longcat
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == \
        ["sdxl_1024_fanout4"]


@pytest.mark.parametrize("cell, leaves_out, reports", [
    (SAT4, NOT_IN_SAT4, _sat4_reports),
    (PANGU4, NOT_IN_PANGU4, _pangu4_reports),
    (EXAONE4, NOT_IN_EXAONE4, _exaone4_reports),
    (GRANITE4, NOT_IN_GRANITE4, _granite4_reports),
    (KEYE4, NOT_IN_KEYE4, _keye4_reports),
    (PHI4, NOT_IN_PHI4, _phi4_reports),
    (LONGCAT4, NOT_IN_LONGCAT4, _longcat4_reports),
], ids=[SAT4, PANGU4, EXAONE4, GRANITE4, KEYE4, PHI4, LONGCAT4])
def test_an_expander_cell_reports_every_share_that_moves_what_it_does(
        cell, leaves_out, reports):
    m = _manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    four, this = cells[SAT4], cells[cell]
    assert {k: this[k] for k in ("traffic", "chips")} == \
        {k: four[k] for k in ("traffic", "chips")}
    assert len(this["why"]) <= 200
    if cell in (PANGU4, EXAONE4):        # one chip's share of the experts
        assert "attention sees more than its share" in this["why"]
    if cell == LONGCAT4:
        assert "dense sees more" in this["why"]
    reported = {x["name"] for x in m["end_to_end"]
                if cell in x.get("workloads", [cell])}
    assert reported == {"images_per_s", "tti_p50_s", "setup_s"}
    shares = {x["name"]: x for x in m["per_layer"]
              if x["unit"] == "%" and x["moves"] in reported}
    assert {"attn_roofline_pct", "lm_decode_hbm_roofline_pct",
            "denoise_flops_util_pct"} <= set(shares)
    missing = {n for n, x in shares.items() if cell not in x["workloads"]}
    assert missing == set(leaves_out)
    # appended: in every list it stands behind the cells accepted before
    # it, in the order they were accepted
    order = [w["name"] for w in m["workloads"]]
    for group in ("end_to_end", "per_layer"):
        for x in m[group]:
            listed = x.get("workloads", [])
            assert listed == sorted(listed, key=order.index), x["name"]
    reports(m, cells)
