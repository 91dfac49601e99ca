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
# metrics it reports, and the one PR 32 appended behind it
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


# the shares of a whole that move what the new cell reports and that it
# does not report, each with what its reader would find there
NOT_IN_SAT4 = {
    "chip_busy_min_pct": "the least busy of the chips of a mesh: one chip",
    "lm_moe_decode_hbm_roofline_pct": "the bytes of a decoder with routed "
                                      "experts: Ouro has none to count",
}


def test_the_four_caller_cell_reports_every_share_that_moves_what_it_does():
    """A claim in a cell needs every accepted share of a roofline or of a
    peak that moves what the cell reports (PR 27 was refused for
    ``attn_roofline_pct``), and a reader that finds something there.
    Shares are told by their unit and by what they move, not by a word in
    their name: ``denoise_flops_util_pct`` is a share of the peak too."""
    m = _manifest()
    reported = {x["name"] for x in m["end_to_end"]
                if SAT4 in x.get("workloads", [SAT4])}
    assert {"images_per_s", "tti_p50_s", "setup_s"} <= reported
    shares = {x["name"]: x for x in m["per_layer"]
              if x["unit"] == "%" and x["moves"] in reported}
    assert {"attn_roofline_pct", "lm_decode_hbm_roofline_pct",
            "denoise_flops_util_pct"} <= set(shares)
    missing = {n for n, x in shares.items() if SAT4 not in x["workloads"]}
    assert missing == set(NOT_IN_SAT4)
    # the layers the cell's graph runs report what the same programs
    # report in their own cells: SD1.5's denoise by class, the VAE, CLIP
    by_layer = {}
    for x in m["per_layer"]:
        if SAT4 in x.get("workloads", []):
            by_layer.setdefault(x["layer"], set()).add(x["name"])
    assert by_layer["Denoise"] == {
        x["name"] for x in m["per_layer"] if x["layer"] == "Denoise"}
    assert by_layer["VAE decode"] == {"vae_device_s_per_image"}
    assert by_layer["Text encode"] == {"clip_device_ms_per_request"}
    # the readers' sources exist in the cell's configuration: SD1.5's
    # UNet for the attention bound and the FLOP count, a pattern for each
    # program, the language model's shapes
    with open(os.path.join(os.path.dirname(_TESTS), "configs",
                           "ouro-2.6b-expand-sd15-512.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(os.path.dirname(_TESTS), "configs",
                           "sd15-512.json")) as f:
        sd15 = json.load(f)
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


# what the expert model's cell does not report of the shares that move
# what it does: ONE stated exception beside the mesh's
NOT_IN_PANGU4 = {
    "chip_busy_min_pct": "the least busy of the chips of a mesh: one chip",
    "lm_decode_hbm_roofline_pct": "its byte count is a dense looped "
                                  "decoder's and would be false here: "
                                  "lm_moe_decode_hbm_roofline_pct stands "
                                  "in its place",
}


def test_the_expert_cell_reports_every_share_that_moves_what_it_does():
    """The twin of the test above for the cell PR 32 appended: the
    four-caller cell's configuration with another language model in
    front, so it reports what that cell reports, with the one roofline
    whose bytes are architecture-specific exchanged for its own."""
    m = _manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    four, pangu = cells[SAT4], cells[PANGU4]
    assert {k: pangu[k] for k in ("traffic", "chips")} == \
        {k: four[k] for k in ("traffic", "chips")}
    assert pangu["config"] == "pangu-ultra-moe-expand-sd15-512"
    assert len(pangu["why"]) <= 200
    assert "attention sees more than its share" in pangu["why"]
    reported = {x["name"] for x in m["end_to_end"]
                if PANGU4 in x.get("workloads", [PANGU4])}
    assert reported == {x["name"] for x in m["end_to_end"]
                        if SAT4 in x.get("workloads", [SAT4])}
    shares = {x["name"]: x for x in m["per_layer"]
              if x["unit"] == "%" and x["moves"] in reported}
    missing = {n for n, x in shares.items() if PANGU4 not in x["workloads"]}
    assert missing == set(NOT_IN_PANGU4)
    assert shares["lm_moe_decode_hbm_roofline_pct"]["workloads"] == [PANGU4]
    # everything else the four-caller cell lists, this one lists too, and
    # two readers of its own
    of = {cell: {x["name"] for x in m["per_layer"]
                 if cell in x.get("workloads", [])} for cell in (SAT4, PANGU4)}
    assert of[PANGU4] - of[SAT4] == {"lm_experts_device_s_per_request",
                                     "lm_moe_decode_hbm_roofline_pct"}
    assert of[SAT4] - of[PANGU4] == {"lm_decode_hbm_roofline_pct"}
    for x in m["per_layer"]:
        if PANGU4 in x.get("workloads", []):
            assert x["workloads"][-1] == PANGU4, x["name"]
    # 7 of at most 24 cells, still one on four chips
    assert len(m["workloads"]) == 7
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == \
        ["sdxl_1024_fanout4"]
    # the readers' sources exist in the cell's configuration
    bench = os.path.dirname(_TESTS)
    with open(os.path.join(bench, "configs", pangu["config"] + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "configs",
                           "ouro-2.6b-expand-sd15-512.json")) as f:
        ouro = json.load(f)
    flops = _MODULES["test_chip_benchmark"].flops
    assert flops.denoise_flops_per_image(cfg) == \
        flops.denoise_flops_per_image(ouro)
    assert cfg["programs"] == ouro["programs"] and cfg["unet"] == ouro["unet"]
    assert cfg["trace_slice"]["after_counter"] == "lm.executions"
    # the graph is the four-caller cell's with the loader's name changed
    changed = {nid for nid in cfg["graph"]
               if cfg["graph"][nid] != ouro["graph"][nid]}
    assert changed == {"20"} and set(cfg["graph"]) == set(ouro["graph"])
    assert cfg["graph"]["20"]["inputs"] == {
        "model_name": "openpangu-ultra-moe-718b.safetensors"}
