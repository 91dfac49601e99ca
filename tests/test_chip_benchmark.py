"""The chip benchmark's own tests, collected under ``pytest tests/``.

``benchmarks/chip/tests/`` lives beside the benchmark (the one directory a
benchmark PR may write to), where tier-1 never looked: until this file
nothing in tier-1 guarded the reducer, the FLOP count, the contract line
or the readers.  Every test function of those files is re-exported here
under its own name, so each is a case of its own (parametrised ones keep
their cases); none is marked slow.
"""

import hashlib
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


_span = _MODULES["test_span_metrics"]
_lm = _MODULES["test_lm_cell"]
_accepted_entries = _span.test_every_new_metric_has_its_reader_and_its_cells
_accepted_lm_cell = \
    _lm.test_the_cell_its_configuration_and_its_metrics_are_entries

# the cell PR 28 appended, to the cells and to the ``workloads`` of the
# metrics it reports
SAT4 = "ouro_expand_sd15_512_sat4"


def _manifest() -> dict:
    with open(os.path.join(_span.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# sha256 of ``json.dumps(manifest, sort_keys=True)`` of the BENCHMARK.json
# that PR 26 left and the accepted tests were written against
ACCEPTED_SHA256 = \
    "1d9a95a931cf2192d97a550ae8f9ace5fabf39458da09666d10fd6ac1101e770"


def _without(m: dict, cell: str) -> dict:
    """The manifest as it stood before ``cell`` was appended to it: the
    accepted one to the letter, so that running the accepted tests on it
    hides no other edit."""
    assert m["workloads"][-1]["name"] == cell
    m["workloads"] = m["workloads"][:-1]
    for group in ("end_to_end", "per_layer"):
        for x in m[group]:
            if cell in x.get("workloads", []):
                assert x["workloads"][-1] == cell
                x["workloads"] = x["workloads"][:-1]
    assert hashlib.sha256(json.dumps(m, sort_keys=True).encode()
                          ).hexdigest() == ACCEPTED_SHA256
    return m


def test_every_new_metric_has_its_reader_and_its_cells(  # noqa: F811
        tmp_path, monkeypatch):
    """That test holds the manifest to the 24 per-layer entries it had at
    PR 24 and ``attn_roofline_pct`` to the two cells it then had, and a PR
    that appends may not edit a file the benchmark already has (PR 26
    appended six metrics, PR 28 a cell).  So it runs here as it is, on
    the manifest without what was appended since: the accepted entries
    stand first, unchanged, and every other check of it holds on today's
    file."""
    m = _without(_manifest(), SAT4)
    assert len(m["per_layer"]) >= 24
    m["per_layer"] = m["per_layer"][:24]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    monkeypatch.setattr(_span, "ROOT", str(tmp_path))
    _accepted_entries()


def test_the_cell_its_configuration_and_its_metrics_are_entries(  # noqa: F811
        tmp_path, monkeypatch):
    """PR 26's cell closed its lists and was its metrics' only cell; it
    runs on the manifest without the cell PR 28 appended behind it."""
    (tmp_path / "BENCHMARK.json").write_text(
        json.dumps(_without(_manifest(), SAT4)))
    monkeypatch.setattr(_lm, "ROOT", str(tmp_path))
    _accepted_lm_cell()


def test_the_four_caller_cell_is_an_entry_with_a_mix_of_its_own():
    """PR 28 added data alone: a mix file and entries.  The cell is the
    two-caller cell with four callers, and reports what that one does."""
    m = _manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    two, four = cells["ouro_expand_sd15_512_sat"], cells[SAT4]
    assert m["workloads"][-1] is four and len(four["why"]) <= 200
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
                assert cells_of[-1] == SAT4, x["name"]


# the shares of a whole that move what the new cell reports and that it
# does not report, each with what its reader would find there
NOT_IN_SAT4 = {
    "chip_busy_min_pct": "the least busy of the chips of a mesh: one chip",
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
